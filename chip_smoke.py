"""Bring-up smoke run of the system on TPU chips.

    python chip_smoke.py              # one chip: all phases below
    python chip_smoke.py --chips 4    # four chips: tensor/sequence-parallel
                                      # serving only

One chip, in one process, phase by phase:

* device  - the platform is a TPU whose kind has a row in the peak table;
* kernels - the five main-path Pallas kernels (flash forward, its dq/dkv
  backward, paged prefill, contiguous and paged split-KV decode) at real
  widths, each against the float32 oracle of ``kernels/ref.py``, with the
  compiled program holding the Mosaic kernel (nothing ran interpreted);
* model   - granite-3-2b at its published config (random weights from the
  seed): last-token logits of the Pallas path against the default XLA path;
* serve   - that model behind ``ServingEngine`` (paged pool, chunked
  prefill), once on the default path and once on the kernel path;
* train   - gpt2-small through ``Trainer``/``make_train_step`` on the
  Pallas path for a few steps.

With ``--chips 4`` it serves granite-3-2b at tp=4 and at sp=2 x tp=2 and
compares both with the single-device engine on the same requests.

Every phase prints its compile and run seconds and the device's peak
memory. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed; without a TPU the script exits
non-zero before any phase. Tiles come from the analytic chooser (autotune
off), and compiled programs go to the persistent compilation cache
(``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_config  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels import tuning  # noqa: E402
from repro.kernels.flash_decode import (flash_decode,  # noqa: E402
                                        flash_decode_paged)
from repro.kernels.ops import flash_attention, flash_prefill_paged  # noqa: E402
from repro.kernels.ref import standard_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import adamw, warmup_cosine  # noqa: E402
from repro.serve import ServingEngine  # noqa: E402
from repro.train import Trainer, TrainerConfig, make_train_step  # noqa: E402

# bf16 kernel outputs against the float32 oracle: the tolerance of
# tests/test_kernels_flash.py for bf16 inputs.
KERNEL_TOL = dict(rtol=3e-2, atol=3e-2)
# Pallas vs default-path last-token logits of the 40-layer bf16 model, as a
# share of the largest |logit| (see CHANGES.md for the reasoning).
LOGITS_REL_BOUND = 0.05
# first-step loss of gpt2-small, Pallas vs default path (absolute nats)
LOSS_TOL = 2e-2


class PhaseClock:
    """Wall seconds of a phase, split into compile (XLA backend compiles,
    persistent-cache retrievals included, from JAX's own monitoring events;
    they never nest, unlike tracing) and the rest (tracing and running)."""
    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name, fn, *args, **kwargs):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        print(f"[{name}] done: wall_s={wall:.3f} compile_s={comp:.3f} "
              f"run_s={wall - comp:.3f} cache_hits={self.cache_hits - h0} "
              f"peak_bytes_in_use={peaks}", flush=True)
        return out


def compiled_kernel_program(fn, *args):
    """Compile ``fn`` for ``args`` and assert the program holds a Mosaic
    kernel (``tpu_custom_call``): nothing on this path ran interpreted.
    The executable is what the phase then runs."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{getattr(fn, '__name__', fn)}: no Mosaic kernel in the program")
    return compiled


def _check_close(name, got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite output"
    err = float(np.max(np.abs(got - want)))
    print(f"  {name}: max_abs_err={err:.3e} shape={got.shape}", flush=True)
    np.testing.assert_allclose(got, want, err_msg=name, **KERNEL_TOL)


def phase_device():
    devs = jax.devices()
    d = devs[0]
    print(f"  platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devs)}", flush=True)
    assert tuning.nominal_hbm_bw(d.device_kind) is not None, (
        f"device kind {d.device_kind!r} has no row in the peak table "
        f"(kernels/tuning._NOMINAL_HBM_BW)")


def phase_kernels(seed):
    """The five main-path kernels once each, at the widths of
    tests/test_tpu_compile.py, against the float32 oracle. They are called
    as a user calls them, so the Mosaic-kernel check also shows that the
    default ``interpret`` resolution picked the compiled kernel."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def rand(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def run(name, fn, ref_fn, *args):
        got = compiled_kernel_program(fn, *args)(*args)
        _check_close(name, got, jax.jit(ref_fn)(*args))

    # flash forward, granite widths
    q, k, v = rand((1, 32, 2048, 64)), rand((1, 8, 2048, 64)), \
        rand((1, 8, 2048, 64))
    run("flash_fwd[granite 32q/8kv s=2048 causal]",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: standard_attention(*f32(q, k, v), causal=True),
        q, k, v)

    # forward + dq/dkv, gpt2-small widths, random cotangent
    q, k, v, ct = (rand((2, 12, 1024, 64)) for _ in range(4))

    def grads(attn):
        def g(q, k, v, ct):
            _, vjp = jax.vjp(attn, q, k, v)
            return jnp.stack([x.astype(jnp.float32) for x in vjp(ct)])
        return g

    run("flash_fwd_bwd[gpt2-small 12h s=1024] dq/dk/dv",
        grads(lambda q, k, v: flash_attention(q, k, v, causal=True)),
        grads(lambda q, k, v: standard_attention(
            *f32(q, k, v), causal=True).astype(jnp.bfloat16)),
        q, k, v, ct)

    # paged prefill: a 512-row chunk at positions 512.. over 16-row pages
    b, sq, ps, T, pages = 2, 512, 16, 64, 256
    q = rand((b, 32, sq, 64))
    kp, vp = rand((8, pages, ps, 64)), rand((8, pages, ps, 64))
    page_list = jax.random.permutation(next(keys), pages)[:b * T]
    page_list = page_list.reshape(b, T).astype(jnp.int32)
    q_pos = jnp.broadcast_to(512 + jnp.arange(sq, dtype=jnp.int32), (b, sq))
    kv_pos = jnp.broadcast_to(jnp.arange(T * ps, dtype=jnp.int32),
                              (b, T * ps))

    def gather(pool, table):               # (hkv, P, ps, d) -> (b, hkv, T*ps, d)
        x = pool[:, table]
        return x.transpose(1, 0, 2, 3, 4).reshape(
            table.shape[0], pool.shape[0], -1, pool.shape[-1])

    run("flash_prefill_paged[granite ps=16 chunk=512]",
        lambda q, kp, vp, pl_, qp, kvp: flash_prefill_paged(
            q, kp, vp, pl_, q_positions=qp, kv_positions=kvp),
        lambda q, kp, vp, pl_, qp, kvp: standard_attention(
            *f32(q, gather(kp, pl_), gather(vp, pl_)), causal=True,
            q_positions=qp, kv_positions=kvp),
        q, kp, vp, page_list, q_pos, kv_pos)

    # contiguous split-KV decode: 8 lanes over a 2048-slot cache
    q = rand((8, 32, 1, 64))
    k, v = rand((8, 8, 2048, 64)), rand((8, 8, 2048, 64))
    kv_len = jax.random.randint(next(keys), (8,), 1, 2049, jnp.int32)

    def valid(kv_len, n):
        return jnp.arange(n)[None, :] < kv_len[:, None]

    run("flash_decode[granite 8 lanes cap=2048]",
        lambda q, k, v, kl: flash_decode(q, k, v, kl),
        lambda q, k, v, kl: standard_attention(
            *f32(q, k, v), kv_mask=valid(kl, k.shape[2])),
        q, k, v, kv_len)

    # paged split-KV decode: 4 lanes, 8 kv heads, 16-row pages
    T = 2048 // ps
    q = rand((4, 32, 1, 64))
    kp, vp = rand((8, 4 * T, ps, 64)), rand((8, 4 * T, ps, 64))
    table = jax.random.permutation(next(keys), 4 * T).reshape(4, T)
    table = table.astype(jnp.int32)
    kv_len = jax.random.randint(next(keys), (4,), 1, T * ps + 1, jnp.int32)
    run("flash_decode_paged[granite 4 lanes ps=16 cap=2048]",
        lambda q, kp, vp, t, kl: flash_decode_paged(q, kp, vp, t, kl),
        lambda q, kp, vp, t, kl: standard_attention(
            *f32(q, gather(kp, t), gather(vp, t)),
            kv_mask=valid(kl, T * ps)),
        q, kp, vp, table, kv_len)


def granite(**overrides):
    return dataclasses.replace(get_config("granite-3-2b"), **overrides)


KERNEL_PATH = dict(attn_impl="pallas", use_decode_kernel=True)


def init_params(cfg, seed):
    return jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))


def phase_model(params, seed):
    """Last-token logits of the published granite config on the Pallas
    path against the default XLA path, same params and prompts."""
    rng = np.random.default_rng(seed)
    cfg = granite()
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(2, 1024)),
                         jnp.int32)
    out = {}
    for name, c in (("chunked", cfg), ("pallas", granite(**KERNEL_PATH))):
        model = build_model(c)
        def last_logits(p, t, m=model):
            return m.prefill(p, {"tokens": t}, t.shape[1])[1]

        fn = (compiled_kernel_program(last_logits, params, tokens)
              if name == "pallas" else jax.jit(last_logits))
        out[name] = np.asarray(fn(params, tokens), np.float32)[:, -1]
    ref, got = out["chunked"], out["pallas"]
    assert np.isfinite(got).all() and np.isfinite(ref).all()
    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    print(f"  granite-3-2b last-token logits pallas vs chunked: "
          f"max_abs_diff={diff:.6g} max_abs_logit={scale:.6g} "
          f"rel={diff / scale:.6g} (bound {LOGITS_REL_BOUND}) "
          f"argmax_agreement={agree:.3f}", flush=True)
    assert diff <= LOGITS_REL_BOUND * scale, "logits outside the bound"


def serve_requests(seed, vocab):
    """8 requests, prompts of 128-1024 tokens, 32 new tokens each."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(128, 1025, size=8)
    return [[int(t) for t in rng.integers(1, vocab, size=n)] for n in lens]


def serve(cfg, params, prompts, **engine_kw):
    """Drain ``prompts`` through a paged, chunked-prefill ServingEngine
    (8 lanes, capacity 2048, 16-row pages, 512-token chunks); returns the
    outputs by request id and the engine."""
    eng = ServingEngine(build_model(cfg), params, num_slots=8,
                        capacity=2048, page_size=16, chunk_size=512,
                        prefill_bucket=512, **engine_kw)
    for p in prompts:
        eng.submit(p, max_new_tokens=32)
    done = eng.run()
    outs = {r.rid: list(r.output) for r in done}
    assert len(outs) == len(prompts), f"{len(outs)}/{len(prompts)} finished"
    for rid, toks in outs.items():
        assert len(toks) == 32, f"request {rid}: {len(toks)} tokens"
        assert all(0 <= t < cfg.vocab_size for t in toks), f"request {rid}"
    return outs, eng


def agreement(a, b):
    same = sum(x == y for rid in a for x, y in zip(a[rid], b[rid]))
    return same / sum(len(v) for v in a.values())


def phase_serve(clock, params, seed):
    cfg = granite()
    prompts = serve_requests(seed, cfg.vocab_size)
    print(f"  prompt lengths {[len(p) for p in prompts]}", flush=True)
    default = clock.phase("serve/default", serve, cfg, params, prompts)[0]
    kernel = clock.phase("serve/kernels", serve,
                         granite(**KERNEL_PATH), params, prompts)[0]
    print(f"  greedy-token agreement kernels vs default: "
          f"{agreement(default, kernel):.4f}", flush=True)


def phase_train(seed, tmp):
    """gpt2-small, seq 1024, batch 8, Pallas attention, 3 Trainer steps."""
    base = get_config("gpt2-small")
    cfg = dataclasses.replace(base, attn_impl="pallas")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    opt = adamw(warmup_cosine(6e-4, 20, 3))
    data = SyntheticLM(cfg.vocab_size, 1024, 8, seed=seed)
    step = compiled_kernel_program(
        make_train_step(model, opt, deterministic=True),
        params, opt.init(params), data.batch_at(0))

    ref_model = build_model(base)
    ref_loss = float(jax.jit(lambda p, b: ref_model.loss(
        p, b, deterministic=True)[0])(params, data.batch_at(0)))

    trainer = Trainer(TrainerConfig(total_steps=3, ckpt_every=10**9,
                                    ckpt_dir=tmp),
                      step, params, opt.init(params), data.batch_at)
    hist = trainer.run()
    losses = [h["loss"] for h in hist]
    print(f"  gpt2-small losses {losses} (chunked first-step loss "
          f"{ref_loss:.6f}, diff {abs(losses[0] - ref_loss):.3e}, "
          f"tol {LOSS_TOL})", flush=True)
    assert len(losses) == 3 and np.isfinite(losses).all()
    assert abs(losses[0] - ref_loss) <= LOSS_TOL, "first-step loss mismatch"


def run_one_chip(clock, seed):
    clock.phase("kernels", phase_kernels, seed)
    params = clock.phase("init", init_params, granite(), seed)
    clock.phase("model", phase_model, params, seed)
    clock.phase("serve", phase_serve, clock, params, seed)
    del params
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".chip_smoke_") as tmp:
        clock.phase("train", phase_train, seed, tmp)


def run_four_chips(clock, seed):
    """granite-3-2b served at tp=4 and sp=2 x tp=2 against tp=1."""
    cfg = granite()
    params = clock.phase("init", init_params, cfg, seed)
    prompts = serve_requests(seed, cfg.vocab_size)
    base, _ = clock.phase("serve/tp1", serve, cfg, params, prompts)
    for name, kw in (("tp4", dict(tp=4)), ("sp2xtp2", dict(sp=2, tp=2))):
        outs, eng = clock.phase(f"serve/{name}", serve, cfg, params,
                                prompts, **kw)
        census = {"decode": eng.decode_collective_census(),
                  "chunk": eng.prefill_collective_census("chunk")}
        if eng.sp == 1:
            census["packed"] = eng.prefill_collective_census("packed")
            census["scatter"] = eng.prefill_collective_census("scatter")
        print(f"  {name}: greedy-token agreement vs tp=1 "
              f"{agreement(base, outs):.4f}; census {census}; "
              f"KV pool {eng.per_shard_cache_bytes()} bytes/shard", flush=True)
        del eng


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devs)} "
                 f"device(s) visible")

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    tuning.configure_tuning(autotune=False)
    clock = PhaseClock()
    t0 = time.perf_counter()
    clock.phase("device", phase_device)
    if args.chips == 4:
        run_four_chips(clock, args.seed)
    else:
        run_one_chip(clock, args.seed)
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.compile_s:.3f}", flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
