"""Named scopes and kernel names (``telemetry/scopes.py``): the compiled
decode and train step programs with scopes equal those without once the
op metadata is stripped, the scopes reach the ops' ``op_name`` metadata
that a profile reports, and every Pallas kernel carries its stable name."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core.masks import POS_PAD
from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.models import build_model
from repro.optim import adamw
from repro.telemetry import scopes
from repro.train import make_train_step

_METADATA = re.compile(r", metadata=\{[^}]*\}")
_SOURCE_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.MULTILINE)


def _strip(text: str) -> str:
    """The program without its op metadata and source-location tables,
    instructions renamed in order of appearance (the lowering numbers them
    from their source locations)."""
    text = _METADATA.sub("", _SOURCE_TABLES.sub("", text))
    names: dict[str, str] = {}
    return re.sub(r"%[\w.-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def _compiled(fn, *args) -> str:
    # a fresh lambda each call: no trace cached from the other variant
    return jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()


def _both(monkeypatch, fn, *args) -> tuple[str, str]:
    scoped = _compiled(fn, *args)
    monkeypatch.setattr(scopes, "named",
                        lambda name: contextlib.nullcontext())
    bare = _compiled(fn, *args)
    monkeypatch.undo()
    return scoped, bare


def _op_scopes(text: str) -> set[str]:
    return {s for path in re.findall(r'op_name="([^"]*)"', text)
            for s in re.split(r"[/()]", path) if s in scopes.DEVICE_SCOPES}


def test_decode_step_scopes_change_metadata_only(monkeypatch):
    model = build_model(reduced_config("granite-3-2b"))
    params = model.init(jax.random.PRNGKey(0))
    state = model.init_paged_decode_state(2, 8, 8, 4)
    state["page_table"] = jnp.asarray([[0, 1, -1, -1], [2, -1, -1, -1]],
                                      jnp.int32)
    state["kv_len"] = jnp.asarray([9, 3], jnp.int32)
    tok = jnp.asarray([5, 7], jnp.int32)
    scoped, bare = _both(monkeypatch, model.decode_step, params, state, tok)
    assert _strip(scoped) == _strip(bare)
    assert {"attention", "kv_write", "mlp", "logits"} <= _op_scopes(scoped)
    assert not _op_scopes(bare)


def test_train_step_scopes_change_metadata_only(monkeypatch):
    model = build_model(reduced_config("gpt2-small"))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw(1e-3)
    step = make_train_step(model, opt, deterministic=True)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, model.cfg.vocab_size, (2, 32)), jnp.int32)
    batch = {"tokens": toks,
             "segment_ids": jnp.zeros_like(toks).at[:, 20:].set(1)}
    scoped, bare = _both(monkeypatch, step, params, opt.init(params), batch)
    assert _strip(scoped) == _strip(bare)
    assert {"attention", "mlp", "logits", "loss", "optimizer"} <= \
        _op_scopes(scoped)


def _pallas_names(fn, *args) -> list[str]:
    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, out)
        return out
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


def test_pallas_kernels_carry_stable_names():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    flash = jax.grad(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, interpret=True).sum(), argnums=(0, 1, 2))
    assert sorted(_pallas_names(flash, q, kv, kv)) == \
        sorted([scopes.FLASH_FWD, scopes.FLASH_DQ, scopes.FLASH_DKV])

    pool = jnp.asarray(rng.normal(size=(2, 4, 16, 32)), jnp.float32)
    pages = jnp.asarray([[0, 2, -1, -1]], jnp.int32)
    qpos = jnp.arange(64, dtype=jnp.int32)[None] % 24
    kpos = jnp.where(jnp.arange(64) < 32, jnp.arange(64), POS_PAD)[None]
    paged = jax.grad(lambda q, kp, vp: ops.flash_prefill_paged(
        q, kp, vp, pages, q_positions=qpos,
        kv_positions=kpos.astype(jnp.int32), interpret=True).sum(),
        argnums=(0, 1, 2))
    assert sorted(_pallas_names(paged, q, pool, pool)) == sorted(
        [scopes.FLASH_PREFILL_PAGED_FWD, scopes.FLASH_PREFILL_PAGED_DQ,
         scopes.FLASH_PREFILL_PAGED_DKV])

    q1 = q[:, :, :1]
    kv_len = jnp.asarray([40], jnp.int32)
    assert _pallas_names(lambda q, k, v: flash_decode(
        q, k, v, kv_len, interpret=True), q1, kv, kv) == [scopes.FLASH_DECODE]
    assert _pallas_names(lambda q, k, v: flash_decode_paged(
        q, k, v, pages, jnp.asarray([20], jnp.int32), interpret=True),
        q1, pool, pool) == [scopes.FLASH_DECODE_PAGED]
