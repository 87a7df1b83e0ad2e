"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
        --reduced --steps 200 --ckpt-dir /data/ckpt --resume

On this CPU container --reduced (default) runs the family-faithful small
config on one device. On a real TPU slice, drop --reduced: the script
builds the production mesh, resolves divisibility-aware shardings
(TP/DP/EP + ZeRO-1), and runs the same Trainer with fault tolerance.

Scale-out flags documented for real deployments:
  * XLA_FLAGS="--xla_tpu_enable_async_collective_fusion=true
      --xla_tpu_enable_latency_hiding_scheduler=true" — overlap collectives
      with compute (the standard v5e setting for the schedules this repo
      lowers).
  * preemption: SIGTERM -> trainer.request_checkpoint() (wired below).
  * elastic restart: the checkpoint restores onto any mesh shape
    (repro.checkpoint; tested 8 -> 4 devices).
"""

from __future__ import annotations

import argparse
import signal

import jax

from repro.configs import SHAPES, get_config, reduced_config
from repro.data import SyntheticLM
from repro.distributed.sharding import auto_rules, resolve_tree
from repro.kernels import tuning
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import adamw, warmup_cosine
from repro.train import Trainer, TrainerConfig, make_sharded_train_step, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--autotune", action="store_true",
                    help="empirically time attention tile candidates on "
                         "this device (persisted in the autotune cache)")
    ap.add_argument("--sram-budget", type=int, default=None,
                    help="tuner SRAM budget in bytes for the analytic "
                         "tile chooser")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full config on the production mesh (TPU slice)")
    args = ap.parse_args()

    enable_compile_cache()
    tuning.configure_tuning(sram_budget=args.sram_budget,
                            autotune=args.autotune or None)
    if args.reduced:
        cfg = reduced_config(args.arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw(warmup_cosine(args.lr, 20, args.steps))
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(model, opt, grad_accum=args.grad_accum,
                                       deterministic=True))
        shardings = (None, None)
    else:
        from repro.launch.mesh import make_production_mesh
        cfg = get_config(args.arch)
        mesh = make_production_mesh()
        model = build_model(cfg)
        rules = auto_rules(cfg, mesh, global_batch=args.batch)
        _, batch_specs = model.input_specs(SHAPES["train_4k"])
        opt = adamw(warmup_cosine(args.lr, 2000, args.steps))
        step, sh = make_sharded_train_step(
            model, opt, mesh, rules=rules, zero1=True,
            grad_accum=args.grad_accum, batch_specs=batch_specs)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                                sh["params"])
        opt_state = jax.device_put(opt.init(params), sh["opt"])
        shardings = (sh["params"], sh["opt"])

    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.1f}M seq={args.seq} "
          f"batch={args.batch} accum={args.grad_accum}")

    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=0)
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, async_ckpt=True),
        step, params, opt_state, lambda s: data.batch_at(s),
        param_shardings=shardings[0], opt_shardings=shardings[1])

    signal.signal(signal.SIGTERM, lambda *_: trainer.request_checkpoint())

    if args.resume and trainer.try_resume():
        print(f"resumed from step {trainer.step}")
    hist = trainer.run()
    for rec in hist[:: max(1, len(hist) // 10)]:
        print(f"step {rec['step']:>5}  loss {rec['loss']:.4f}  "
              f"{rec['step_time_s']*1e3:.0f} ms/step")
    if trainer.slow_steps:
        print(f"straggler-flagged steps: {trainer.slow_steps}")


if __name__ == "__main__":
    main()
