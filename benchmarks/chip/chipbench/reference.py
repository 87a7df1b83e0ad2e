"""Plain references of the benchmark's decoder-only transformers.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, no
imports from the program. Matmuls run at ``highest`` precision so that
float32 means float32 on a TPU. Weights are the harness's (``weights.py``),
read by name from the program's layout: ``embed`` (V, d), tied as the LM
head; ``final_norm``; ``blocks`` stacked over layers with ``attn_norm``,
``attn.{wq,wk,wv,wo}``, ``mlp_norm`` and ``mlp.{w_gate,w_up,w_down}``
(SwiGLU) or ``mlp.{w_up,w_down}`` (GELU, tanh form).

The block: pre-norm residual attention then MLP; rotary embeddings on the
two halves of each head (``x1 cos - x2 sin, x1 sin + x2 cos``) with
frequencies ``theta ** (-2i / head_dim)``; grouped-query attention, query
head ``h`` reading key/value head ``h // (heads / kv_heads)``; softmax
scaled by ``1 / sqrt(head_dim)``; causal, and within one packed document
when segment ids are given, with positions counted from each document's
start.

``precision`` selects the computation: ``"f32"`` (the reference), or the
controls one step below a configuration's precision: ``"bf16"`` (every
tensor, weight and update in bfloat16) and ``"fp8"`` (every matmul operand
rounded to float8 e4m3 with a per-tensor scale, float32 elsewhere).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rmsnorm" | "layernorm"
    norm_eps: float
    mlp: str             # "swiglu" | "gelu_tanh"
    rope_theta: float


def arch_from_config(config: dict) -> Arch:
    """The reference's reading of a configuration file (``"reference"``
    block: the block's equations as the configuration states them)."""
    r = config["reference"]
    return Arch(**{f.name: r[f.name] for f in dataclasses.fields(Arch)})


# --------------------------------------------------------------- numerics

def _compute_dtype(precision: str):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, precision: str, spec: str = "...k,kn->...n"):
    if precision == "fp8":
        a, b = _fp8(a.astype(jnp.float32)), _fp8(b.astype(jnp.float32))
    dt = _compute_dtype(precision)
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


def _norm(x, p, arch: Arch, dt):
    xf = x.astype(jnp.float32)
    if arch.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + arch.norm_eps)
        y = y * p["w"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + arch.norm_eps)
        y = y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
    return y.astype(dt)


def _rope(x, pos, theta: float):
    """x: (h, s, hd); pos: (s,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(p, x, pos, seg, arch: Arch, precision: str):
    """One layer over one sequence. x: (s, d); pos, seg: (s,)."""
    dt = _compute_dtype(precision)
    s = x.shape[0]
    hq, hkv, hd = arch.heads, arch.kv_heads, arch.head_dim
    h = _norm(x, p["attn_norm"], arch, dt)
    a = p["attn"]
    q = _mm(h, a["wq"], precision).reshape(s, hq, hd).transpose(1, 0, 2)
    k = _mm(h, a["wk"], precision).reshape(s, hkv, hd).transpose(1, 0, 2)
    v = _mm(h, a["wv"], precision).reshape(s, hkv, hd).transpose(1, 0, 2)
    q = _rope(q, pos, arch.rope_theta).astype(dt)
    k = _rope(k, pos, arch.rope_theta).astype(dt)
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    scores = _mm(q, k, precision, "hqd,hkd->hqk").astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    idx = jnp.arange(s)
    allowed = (idx[None, :] <= idx[:, None]) & (seg[None, :] == seg[:, None])
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    o = _mm(probs, v, precision, "hqk,hkd->hqd")
    o = o.transpose(1, 0, 2).reshape(s, hq * hd)
    x = (x + _mm(o, a["wo"], precision)).astype(dt)
    h = _norm(x, p["mlp_norm"], arch, dt)
    m = p["mlp"]
    if arch.mlp == "swiglu":
        g = _mm(h, m["w_gate"], precision).astype(jnp.float32)
        u = _mm(h, m["w_up"], precision).astype(jnp.float32)
        f = (jax.nn.silu(g) * u).astype(dt)
    else:
        f = jax.nn.gelu(_mm(h, m["w_up"], precision).astype(jnp.float32),
                        approximate=True).astype(dt)
    return (x + _mm(f, m["w_down"], precision)).astype(dt)


def _layer(params, i: int):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def _segment_positions(seg):
    idx = jnp.arange(seg.shape[0])
    start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(start, idx, 0))


def _head(params, x, arch: Arch, precision: str):
    dt = _compute_dtype(precision)
    h = _norm(x, params["final_norm"], arch, dt)
    return _mm(h, params["embed"], precision, "sd,vd->sv").astype(jnp.float32)


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _serve_layer(p, x, pos, seg, arch, precision):
    with jax.default_matmul_precision("highest"):
        return _block(p, x, pos, seg, arch, precision)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _serve_head(params, x, arch, precision):
    with jax.default_matmul_precision("highest"):
        return _head(params, x, arch, precision)


def serve_logits(params, tokens, arch: Arch, precision: str = "f32",
                 pad_to: int = 1024):
    """(S,) tokens -> (S', V) float32 logits on the device of a causal
    forward pass, layer by layer so that only one layer's weights are upcast
    at a time. The sequence is padded at its end to ``S'``, a multiple of
    ``pad_to`` (causal rows never see the padding), so that few shapes
    compile; rows past ``S`` are padding."""
    n = len(tokens)
    S = n + (-n) % pad_to
    toks = np.zeros((S,), np.int32)
    toks[:n] = tokens
    dt = _compute_dtype(precision)
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(dt)
    pos = jnp.arange(S, dtype=jnp.int32)
    seg = jnp.zeros((S,), jnp.int32)
    for i in range(arch.layers):
        x = _serve_layer(_layer(params, i), x, pos, seg, arch, precision)
    return _serve_head(params, x, arch, precision)


@jax.jit
def logit_gaps(logits, rows, toks):
    """``max(logits[r]) - logits[r, t]`` for each (row, token) pair: how far
    the token lies below the row's best."""
    sel = logits[rows]
    return jnp.max(sel, -1) - jnp.take_along_axis(sel, toks[:, None], -1)[:, 0]


@jax.jit
def row_argmax(logits, rows):
    return jnp.argmax(logits[rows], -1).astype(jnp.int32)


# --------------------------------------------------------------- training

def _row_loss_sum(params, tokens, mask, seg, arch: Arch, precision: str):
    """Sum over one row of the masked next-token NLL."""
    dt = _compute_dtype(precision)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    pos = _segment_positions(seg)
    layer = jax.checkpoint(functools.partial(_block, arch=arch,
                                             precision=precision))
    for i in range(arch.layers):
        x = layer(_layer(params, i), x, pos, seg)
    logits = _head(params, x, arch, precision)[:-1]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum((logz - gold) * mask[1:])


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _row_grad(params, tokens, mask, seg, arch, precision):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_row_loss_sum)(params, tokens, mask, seg,
                                                 arch, precision)


def loss_and_grad(params, batch, arch: Arch, precision: str = "f32"):
    """Mean masked next-token loss of a batch and its gradient, one row at
    a time (each row's layers recomputed in the backward pass)."""
    tot, grads = 0.0, None
    for r in range(batch["tokens"].shape[0]):
        l, g = _row_grad(params, jnp.asarray(batch["tokens"][r]),
                         jnp.asarray(batch["loss_mask"][r]),
                         jnp.asarray(batch["segment_ids"][r]), arch, precision)
        tot = tot + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    denom = max(float(np.sum(batch["loss_mask"][:, 1:])), 1.0)
    return float(tot) / denom, jax.tree.map(lambda g: g / denom, grads)


def warmup_cosine_lr(step: int, opt: dict) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine to
    ``end_lr_frac * peak_lr`` at ``total_steps``."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    end = opt.get("end_lr_frac", 0.1)
    return peak * (end + (1 - end) * 0.5 * (1.0 + np.cos(np.pi * prog)))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _adamw(params, grads, mu, nu, step, lr, clip, b1, b2, eps, wd, dtype):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    b1c = 1.0 - b1 ** step
    b2c = 1.0 - b2 ** step

    def one(p, g, m, v):
        g = (g.astype(jnp.float32) * scale).astype(dtype)
        m = (b1 * m + (1 - b1) * g).astype(dtype)
        v = (b2 * v + (1 - b2) * g * g).astype(dtype)
        u = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
        if p.ndim >= 2:
            u = u + wd * p
        return (p - lr * u).astype(dtype), m, v

    out = jax.tree.map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def train_steps(params, batches, arch: Arch, opt: dict,
                precision: str = "f32"):
    """AdamW with global-norm clipping over ``batches``, as a plain loop.
    Returns ``(losses, first_grads, final_params)``: the loss of every step,
    the clipped gradient the optimizer took at the first step, and the
    parameters after the last."""
    dt = _compute_dtype(precision)
    params = jax.tree.map(lambda p: p.astype(dt), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        loss, grads = loss_and_grad(params, batch, arch, precision)
        losses.append(loss)
        step = i + 1
        params, mu, nu = _adamw(
            params, grads, mu, nu, jnp.float32(step),
            jnp.float32(warmup_cosine_lr(step, opt)),
            jnp.float32(opt["clip_norm"]), opt["b1"], opt["b2"], opt["eps"],
            opt["weight_decay"], dt)
        if first is None:
            first = jax.tree.map(lambda m: m.astype(jnp.float32) / (1 - opt["b1"]), mu)
    return losses, first, params
