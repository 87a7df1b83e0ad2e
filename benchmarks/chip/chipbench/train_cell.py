"""Training cells: ``Trainer`` over the program's ``make_train_step``, fed
by the harness's packed-document generator.

Set-up makes the weights from the seed, compiles the step and drives the
one trainer object through its first three steps, the window's own call
and feed on rows that all differ. From those steps it keeps what the check
compares: each step's loss, the clipped gradient the optimizer took at step
one (its first moment over ``1 - b1``), and the change of the parameters
after step three. The window then starts steps until its time is up and
closes when the last of them completes, so it holds whole steps only and
its length is measured, not set. After the window the
trainer is freed and the plain float32 reference runs the same three
steps from the same weights and batches.
"""

from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, traffic
from chipbench.common import (Run, annotate, device_peak_bytes, dims_of,
                              program_model, profile_window)
from chipbench.weights import make_params

CHECK_STEPS = 3


def leaf_norms(tree) -> dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def change_norms(new, old) -> dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def optimizer_of(mix: dict):
    from repro.optim import adamw, warmup_cosine

    o = mix["optimizer"]
    return adamw(warmup_cosine(o["peak_lr"], o["warmup_steps"],
                               o["total_steps"], o.get("end_lr_frac", 0.1)),
                 b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"])


def data_of(mix: dict, vocab: int, seed: int) -> traffic.PackedDocs:
    S = int(mix["seq_len"])
    return traffic.PackedDocs(vocab, S, int(mix["tokens_per_step"]) // S,
                              seed, noise=mix["noise"],
                              mean_doc_len=mix["mean_doc_len"])


def run_train(cell, seed: int, seconds: float, trace: bool, run: Run,
              trace_dir: str | None = None) -> None:
    from repro import train as rtrain

    mix = cell.mix
    model = program_model(cell.config)
    cfg = model.cfg
    D = run.dims = dims_of(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = optimizer_of(mix)
    data = data_of(mix, cfg.vocab_size, seed)
    step_flops: dict[int, float] = {}

    def batch_fn(i: int):
        with annotate("data.batch"):
            b = data.batch_at(i)
            step_flops[i] = flops.train_flops(
                D, b["tokens"].size, traffic.segment_lengths(b["segment_ids"]))
            return b

    jitted = jax.jit(rtrain.make_train_step(
        model, opt, clip_norm=mix["optimizer"]["clip_norm"],
        deterministic=True), donate_argnums=(0, 1))

    def step_fn(params, opt_state, batch):
        with annotate("train.step"):
            return jitted(params, opt_state, batch)

    params = make_params(shapes, seed)
    with tempfile.TemporaryDirectory(prefix="chipbench_ckpt_") as ckpt:
        trainer = rtrain.Trainer(
            rtrain.TrainerConfig(total_steps=1 << 40, ckpt_every=1 << 40,
                                 ckpt_dir=ckpt),
            step_fn, params, opt.init(params), batch_fn)
        del params
        trainer.run(max_steps=1)
        b1 = mix["optimizer"]["b1"]
        first_grad = {k: v / (1.0 - b1) for k, v in
                      leaf_norms(trainer.opt_state["mu"]).items()}
        trainer.run(max_steps=CHECK_STEPS - 1)
        losses = [h["loss"] for h in trainer.history]
        p0 = make_params(shapes, seed)
        change = change_norms(trainer.params, p0)
        del p0

        clock = time.perf_counter
        t0 = clock()
        t1 = t0 + seconds
        prof = profile_window(trace_dir, t0, seconds, float(mix["trace_s"])) \
            if trace else None
        recs = []
        while True:
            now = clock()
            if prof is not None:
                prof.tick(now)
            if now >= t1:
                break
            i = trainer.step
            with annotate("train.sync"):
                trainer.run(max_steps=1)
            te = clock()
            recs.append((now, te, data.seq_len * data.batch,
                         step_flops.pop(i), trainer.history[-1]["loss"]))
        if prof is not None:
            prof.close()
            run.trace_window = tuple(prof.host) if prof.host else None
        run.device["memory_peak_bytes"] = device_peak_bytes()
        del trainer
    run.window = (t0, recs[-1][1] if recs else t1)
    run.train_steps = recs
    run.attempted = len(recs)
    run.failed = sum(not np.isfinite(r[4]) for r in recs)
    run.check_inputs = {"losses": losses, "first_grad": first_grad,
                        "change": change, "shapes": shapes,
                        "batches": [data.batch_at(i) for i in range(CHECK_STEPS)]}
