"""Transformer assembly: blocks for every assigned family, scan-over-layers,
decoder-only / encoder-decoder stacks, KV-cache decode paths.

Families (DESIGN.md §4):
  dense   — pre-norm attention + MLP                     (olmo, internlm2,
            granite, qwen3, phi-3-vision backbone)
  moe     — pre-norm attention + MoE FFN                 (olmoe, phi3.5-moe)
  ssm     — Mamba2 SSD blocks, attention-free            (mamba2-2.7b)
  hybrid  — parallel attention + SSM heads, then MLP     (hymba-1.5b)
  encdec  — encoder (non-causal) + decoder w/ cross-attn (seamless-m4t)

Layers are stacked along a leading axis and executed with lax.scan
(compile-time O(1) in depth — required for the 512-device dry-run) with
optional per-block remat (activation checkpointing; the model-level
analogue of the paper's backward recomputation).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention_layer as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, init_mlp, init_norm,
                                 mlp_specs, norm_specs, rms_normalize)
from repro.telemetry import scopes

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# single block (per family)
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype, *, cross_attn: bool = False) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {}
    if cfg.family != "ssm":
        p["attn_norm"] = init_norm(ks[0], cfg.d_model, cfg.norm_type, dtype)
        p["attn"] = attn_mod.init_attention(ks[1], cfg, dtype)
    if cfg.family == "ssm" or cfg.hybrid:
        p["ssm_norm"] = init_norm(ks[2], cfg.d_model, cfg.norm_type, dtype)
        p["ssm"] = ssm_mod.init_ssm(ks[3], cfg, dtype)
    if cross_attn:
        p["cross_norm"] = init_norm(ks[4], cfg.d_model, cfg.norm_type, dtype)
        p["cross_attn"] = attn_mod.init_attention(ks[5], cfg, dtype)
    if cfg.family == "moe":
        p["mlp_norm"] = init_norm(ks[6], cfg.d_model, cfg.norm_type, dtype)
        p["moe"] = moe_mod.init_moe(ks[7], cfg, dtype)
    elif cfg.d_ff > 0:
        p["mlp_norm"] = init_norm(ks[6], cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = init_mlp(ks[7], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def block_specs(cfg: ModelConfig, *, cross_attn: bool = False):
    s: Params = {}
    if cfg.family != "ssm":
        s["attn_norm"] = norm_specs(cfg.norm_type)
        s["attn"] = attn_mod.attention_specs(cfg)
    if cfg.family == "ssm" or cfg.hybrid:
        s["ssm_norm"] = norm_specs(cfg.norm_type)
        s["ssm"] = ssm_mod.ssm_specs(cfg)
    if cross_attn:
        s["cross_norm"] = norm_specs(cfg.norm_type)
        s["cross_attn"] = attn_mod.attention_specs(cfg)
    if cfg.family == "moe":
        s["mlp_norm"] = norm_specs(cfg.norm_type)
        s["moe"] = moe_mod.moe_specs(cfg)
    elif cfg.d_ff > 0:
        s["mlp_norm"] = norm_specs(cfg.norm_type)
        s["mlp"] = mlp_specs(cfg.mlp_type)
    return s


def apply_ffn(params: Params, cfg: ModelConfig, x):
    """The block's pre-norm feed-forward sub-layer (dense MLP or MoE) with
    its residual, under the ``mlp`` scope. Returns (x, aux_loss); a block
    without one (d_ff 0) returns x unchanged."""
    aux = jnp.float32(0.0)
    if "moe" not in params and "mlp" not in params:
        return x, aux
    with scopes.named(scopes.MLP):
        h = apply_norm(params["mlp_norm"], x, cfg.norm_type)
        if "moe" in params:
            y, aux = moe_mod.apply_moe(params["moe"], cfg, h)
        else:
            y = attn_mod._tp_reduce(
                apply_mlp(params["mlp"], h, cfg.mlp_type), cfg)
    return x + y, aux


def apply_block(params: Params, cfg: ModelConfig, x, *,
                enc_out=None, enc_mask=None, segment_ids=None,
                deterministic=True, dropout_seed=0,
                causal_override: bool | None = None):
    """One block, full sequence. Returns (x, aux_loss).

    ``segment_ids`` (b, s) isolates packed documents in the self-attention
    path (mask + segment-relative RoPE). SSM blocks scan the raw sequence
    and do NOT reset state at boundaries — packing is an attention-family
    feature (DESIGN.md §8).
    """
    if cfg.sp_activations and x.ndim == 3:
        # sequence-parallel residual stream (§Perf lever): shard the seq dim
        # over the model axis between blocks, so norms/elementwise run on
        # 1/TP of the tokens and the TP boundary becomes reduce-scatter +
        # all-gather instead of all-reduce of the full stream.
        from jax.sharding import PartitionSpec as P
        try:
            x = jax.lax.with_sharding_constraint(x, P("data", "model", None))
        except (ValueError, RuntimeError):
            pass
    spec = attn_mod.attn_spec_from_config(cfg)
    if causal_override is not None:
        spec = attn_mod.AttentionSpec(**{**spec.__dict__,
                                         "causal": causal_override,
                                         "window": cfg.window if causal_override else None})

    if cfg.hybrid:
        # Hymba: attention heads and SSM heads consume the SAME normalized
        # input in parallel; per-path RMS-normalized outputs are averaged.
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        a = attn_mod.apply_attention(params["attn"], cfg, h, spec=spec,
                                     segment_ids=segment_ids,
                                     deterministic=deterministic,
                                     dropout_seed=dropout_seed)
        m = ssm_mod.apply_ssm(params["ssm"], cfg, h)
        x = x + 0.5 * (rms_normalize(a) + rms_normalize(m))
    elif cfg.family == "ssm":
        h = apply_norm(params["ssm_norm"], x, cfg.norm_type)
        x = x + ssm_mod.apply_ssm(params["ssm"], cfg, h)
    else:
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        x = x + attn_mod.apply_attention(params["attn"], cfg, h, spec=spec,
                                         segment_ids=segment_ids,
                                         deterministic=deterministic,
                                         dropout_seed=dropout_seed)

    if "cross_attn" in params and enc_out is not None:
        h = apply_norm(params["cross_norm"], x, cfg.norm_type)
        x = x + attn_mod.apply_attention(params["cross_attn"], cfg, h,
                                         kv_x=enc_out, kv_mask=enc_mask,
                                         deterministic=deterministic)

    return apply_ffn(params, cfg, x)


# ---------------------------------------------------------------------------
# stacks (scan over layers)
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, num_layers: int, dtype, *,
               cross_attn: bool = False) -> Params:
    keys = jax.random.split(key, num_layers)
    if cfg.scan_layers:
        return jax.vmap(lambda k: init_block(k, cfg, dtype, cross_attn=cross_attn))(keys)
    return [init_block(k, cfg, dtype, cross_attn=cross_attn) for k in keys]


def stack_specs(cfg: ModelConfig, *, cross_attn: bool = False):
    base = block_specs(cfg, cross_attn=cross_attn)

    def add_layer_dim(spec):
        return P(*((None,) + tuple(spec)))

    if cfg.scan_layers:
        return jax.tree.map(add_layer_dim, base,
                            is_leaf=lambda x: isinstance(x, P))
    return [base] * cfg.num_layers


def apply_stack(params: Params, cfg: ModelConfig, x, *,
                enc_out=None, enc_mask=None, segment_ids=None,
                deterministic=True, dropout_seed=0, causal_override=None):
    """Scan over stacked layers. Returns (x, total_aux_loss)."""
    block_fn = functools.partial(
        apply_block, cfg=cfg, enc_out=enc_out, enc_mask=enc_mask,
        segment_ids=segment_ids,
        deterministic=deterministic, dropout_seed=dropout_seed,
        causal_override=causal_override)

    if not cfg.scan_layers:
        aux_total = jnp.float32(0.0)
        fn = (jax.checkpoint(lambda p, h: block_fn(p, x=h),
                             policy=jax.checkpoint_policies.nothing_saveable)
              if cfg.remat else (lambda p, h: block_fn(p, x=h)))
        for p_l in params:
            x, aux = fn(p_l, x)
            aux_total = aux_total + aux
        return x, aux_total

    def body(carry, p_l):
        x, aux_total = carry
        fn = (jax.checkpoint(lambda p, h: block_fn(p, x=h),
                             policy=jax.checkpoint_policies.nothing_saveable)
              if cfg.remat else (lambda p, h: block_fn(p, x=h)))
        x, aux = fn(p_l, x)
        return (x, aux_total + aux), None

    (x, aux_total), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params)
    return x, aux_total


# ---------------------------------------------------------------------------
# decode path (single token through the stack, carrying caches)
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, capacity: int, dtype,
                      *, enc_len: int = 0):
    """Per-layer caches stacked on a leading layer axis."""
    def one_layer(_):
        c: Params = {}
        if cfg.family != "ssm":
            c["kv"] = attn_mod.init_kv_cache(cfg, batch, capacity, dtype)
        if cfg.family == "ssm" or cfg.hybrid:
            c["ssm"] = ssm_mod.init_ssm_state(cfg, batch, dtype)
        if cfg.num_encoder_layers > 0 and enc_len > 0:
            c["cross_kv"] = attn_mod.init_kv_cache(cfg, batch, enc_len, dtype)
        return c

    return jax.vmap(one_layer)(jnp.arange(cfg.num_layers))


def decode_cache_specs(cfg: ModelConfig, *, enc: bool = False):
    def add_layer(spec):
        return P(*((None,) + tuple(spec)))
    c: Params = {}
    if cfg.family != "ssm":
        c["kv"] = attn_mod.kv_cache_specs()
    if cfg.family == "ssm" or cfg.hybrid:
        c["ssm"] = ssm_mod.ssm_state_specs()
    if enc:
        c["cross_kv"] = attn_mod.kv_cache_specs()
    return jax.tree.map(add_layer, c, is_leaf=lambda x: isinstance(x, P))


def apply_block_decode(params: Params, cfg: ModelConfig, x, cache, kv_len,
                       *, enc_mask=None):
    """One block for one new token. Returns (x, new_cache)."""
    new_cache: Params = {}
    if cfg.hybrid:
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        a, new_cache["kv"] = attn_mod.decode_attention_step(
            params["attn"], cfg, h, cache["kv"], kv_len)
        m, new_cache["ssm"] = ssm_mod.decode_ssm_step(params["ssm"], cfg, h,
                                                      cache["ssm"])
        x = x + 0.5 * (rms_normalize(a) + rms_normalize(m))
    elif cfg.family == "ssm":
        h = apply_norm(params["ssm_norm"], x, cfg.norm_type)
        y, new_cache["ssm"] = ssm_mod.decode_ssm_step(params["ssm"], cfg, h,
                                                      cache["ssm"])
        x = x + y
    else:
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        a, new_cache["kv"] = attn_mod.decode_attention_step(
            params["attn"], cfg, h, cache["kv"], kv_len)
        x = x + a

    if "cross_attn" in params and "cross_kv" in cache:
        h = apply_norm(params["cross_norm"], x, cfg.norm_type)
        ck = cache["cross_kv"]
        hq, hd = cfg.num_heads, cfg.head_dim
        qh = (h @ params["cross_attn"]["wq"]).reshape(
            h.shape[0], 1, hq, hd).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            qh = rms_normalize(qh) * params["cross_attn"]["q_norm"]
        from repro.core.attention import decode_attention as _dec
        enc_len = jnp.full((x.shape[0],), ck["k"].shape[2], jnp.int32)
        spec = attn_mod.attn_spec_from_config(cfg)
        o = _dec(qh, ck["k"], ck["v"], enc_len, spec)
        o = o.transpose(0, 2, 1, 3).reshape(h.shape[0], 1, hq * hd)
        x = x + o @ params["cross_attn"]["wo"]
        new_cache["cross_kv"] = ck

    x, _ = apply_ffn(params, cfg, x)
    return x, new_cache


def init_paged_decode_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                            dtype):
    """Every layer's page pool, lane-dense: (L, num_pages, page_size,
    hkv*hd) per K/V leaf. Paged decode is an attention-family feature (a
    page holds token-indexed K/V rows); SSM/hybrid recurrent state and
    encoder streams have no such rows — ``Model.supports_paged_decode``
    gates those families to the dense path.
    """
    assert cfg.family in ("dense", "moe") and not cfg.hybrid, cfg.family
    return {"kv": attn_mod.init_paged_kv_cache(cfg, num_pages, page_size,
                                               dtype)}


def paged_decode_cache_specs():
    return {"kv": attn_mod.paged_kv_cache_specs()}


def apply_block_decode_paged(params: Params, cfg: ModelConfig, x, caches,
                             layer, page_table, kv_len):
    """One dense/moe block for one new token against layer ``layer`` of
    the page pools ``caches``."""
    h = apply_norm(params["attn_norm"], x, cfg.norm_type)
    a, kv = attn_mod.paged_decode_attention_step(
        params["attn"], cfg, h, caches["kv"], layer, page_table, kv_len)
    x = x + a
    x, _ = apply_ffn(params, cfg, x)
    return x, {"kv": kv}


def apply_block_chunk_prefill(params: Params, cfg: ModelConfig, x, caches,
                              layer, dest_page, dest_off, page_list,
                              q_seg, kv_seg, q_pos, kv_pos):
    """One dense/moe block for a packed batch of prefill CHUNKS against
    layer ``layer`` of the page pools (scatter new rows, attend each
    segment's prefix in place through the page list — no per-layer gather
    copy)."""
    h = apply_norm(params["attn_norm"], x, cfg.norm_type)
    a, kv = attn_mod.chunk_prefill_attention_step(
        params["attn"], cfg, h, caches["kv"], layer, dest_page, dest_off,
        page_list, q_seg, kv_seg, q_pos, kv_pos)
    x = x + a
    x, _ = apply_ffn(params, cfg, x)
    return x, {"kv": kv}


def _apply_stack_paged(block, params: Params, cfg: ModelConfig, x, caches):
    """Run ``block(p_l, x, caches, l)`` over the layers with the stacked
    page pools as loop CARRY: each layer gets every layer's pool and its
    index ``l``, writes its rows with one scatter at ``[l, page, offset]``
    and reads them with one gather ``pool[l, pages]``, so no layer of the
    pool is sliced out or written back (DESIGN.md §6). The scan takes
    ``l`` from ``arange(L)`` beside the params; ``scan_layers=False``
    unrolls the same block with a Python ``l``."""
    L = jax.tree.leaves(caches)[0].shape[0]
    if not cfg.scan_layers:
        for l in range(L):
            p_l = (params[l] if isinstance(params, list)
                   else jax.tree.map(lambda p: p[l], params))
            x, caches = block(p_l, x, caches, l)
        return x, caches

    def body(carry, inp):
        return block(inp[0], *carry, inp[1]), None

    (x, caches), _ = jax.lax.scan(body, (x, caches),
                                  (params, jnp.arange(L)))
    return x, caches


def apply_stack_chunk_prefill(params: Params, cfg: ModelConfig, x, caches,
                              dest_page, dest_off, page_list,
                              q_seg, kv_seg, q_pos, kv_pos):
    """Packed prefill chunks through all layers, the pools threaded as
    carry (``_apply_stack_paged``). The scatter map and kv page list are
    layer-invariant (one logical sequence maps to the same pages in every
    layer's pool).

    Sequence-parallel contract (``cfg.sp_axis`` set, DESIGN.md §14): x,
    ``q_seg`` and ``q_pos`` are this shard's contiguous SLAB of the packed
    chunk — the per-segment traced positions make the offset slab exact —
    while ``dest_page``/``dest_off``/``page_list``/``kv_seg``/``kv_pos``
    cover the FULL chunk on every shard (the pool is sp-replicated; the
    per-layer KV gather happens inside the attention step)."""
    def block(p_l, x, caches, l):
        return apply_block_chunk_prefill(p_l, cfg, x, caches, l, dest_page,
                                         dest_off, page_list, q_seg, kv_seg,
                                         q_pos, kv_pos)

    return _apply_stack_paged(block, params, cfg, x, caches)


def apply_stack_decode_paged(params: Params, cfg: ModelConfig, x, caches,
                             page_table, kv_len):
    """A single token through all layers, the pools threaded as carry
    (``_apply_stack_paged``). ``page_table`` / ``kv_len`` are
    layer-invariant (one logical sequence maps to the same pages in every
    layer's pool)."""
    def block(p_l, x, caches, l):
        return apply_block_decode_paged(p_l, cfg, x, caches, l, page_table,
                                        kv_len)

    return _apply_stack_paged(block, params, cfg, x, caches)


def apply_stack_decode(params: Params, cfg: ModelConfig, x, caches, kv_len):
    """Scan a single token through all layers, threading per-layer caches."""
    if not cfg.scan_layers:
        outs = []
        L = jax.tree.leaves(caches)[0].shape[0]
        for l in range(L):
            p_l = jax.tree.map(lambda p: p[l], params) \
                if not isinstance(params, list) else params[l]
            c_l = jax.tree.map(lambda c: c[l], caches)
            x, nc = apply_block_decode(p_l, cfg, x, c_l, kv_len)
            outs.append(nc)
        new_caches = jax.tree.map(lambda *cs: jnp.stack(cs), *outs)
        return x, new_caches

    def body(x, inp):
        p_l, cache_l = inp
        x, new_cache = apply_block_decode(p_l, cfg, x, cache_l, kv_len)
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# prefill path (full sequence + cache write)
# ---------------------------------------------------------------------------

def apply_block_prefill(params: Params, cfg: ModelConfig, x, capacity: int,
                        *, kv_mask=None, enc_out=None, segment_ids=None,
                        positions=None):
    """One block over the prompt; returns (x, cache_l). ``segment_ids`` /
    ``positions`` make the prompt a PACKED batch of requests (serving's
    packed prefill; see serve/engine.py and DESIGN.md §6)."""
    cache_l: Params = {}
    dtype = x.dtype
    b = x.shape[0]
    if cfg.hybrid:
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        kv = attn_mod.init_kv_cache(cfg, b, capacity, dtype)
        a, cache_l["kv"] = attn_mod.prefill_attention(
            params["attn"], cfg, h, kv, kv_mask=kv_mask,
            segment_ids=segment_ids, positions=positions)
        m, cache_l["ssm"] = ssm_mod.apply_ssm(params["ssm"], cfg, h,
                                              return_final_state=True)
        x = x + 0.5 * (rms_normalize(a) + rms_normalize(m))
    elif cfg.family == "ssm":
        h = apply_norm(params["ssm_norm"], x, cfg.norm_type)
        y, cache_l["ssm"] = ssm_mod.apply_ssm(params["ssm"], cfg, h,
                                              return_final_state=True)
        x = x + y
    else:
        h = apply_norm(params["attn_norm"], x, cfg.norm_type)
        kv = attn_mod.init_kv_cache(cfg, b, capacity, dtype)
        a, cache_l["kv"] = attn_mod.prefill_attention(
            params["attn"], cfg, h, kv, kv_mask=kv_mask,
            segment_ids=segment_ids, positions=positions)
        x = x + a

    if "cross_attn" in params and enc_out is not None:
        h = apply_norm(params["cross_norm"], x, cfg.norm_type)
        x = x + attn_mod.apply_attention(params["cross_attn"], cfg, h,
                                         kv_x=enc_out)
        # cache the encoder K/V for decode
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        ck = (enc_out @ params["cross_attn"]["wk"]).reshape(
            b, -1, hkv, hd).transpose(0, 2, 1, 3)
        cv = (enc_out @ params["cross_attn"]["wv"]).reshape(
            b, -1, hkv, hd).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            ck = rms_normalize(ck) * params["cross_attn"]["k_norm"]
        cache_l["cross_kv"] = {"k": ck.astype(dtype), "v": cv.astype(dtype)}

    x, _ = apply_ffn(params, cfg, x)
    return x, cache_l


def apply_stack_prefill(params: Params, cfg: ModelConfig, x, capacity: int,
                        *, kv_mask=None, enc_out=None, segment_ids=None,
                        positions=None):
    """Prompt through all layers; emits the stacked decode cache."""
    if not cfg.scan_layers:
        outs = []
        L = (len(params) if isinstance(params, list)
             else jax.tree.leaves(params)[0].shape[0])
        for l in range(L):
            p_l = (params[l] if isinstance(params, list)
                   else jax.tree.map(lambda p: p[l], params))
            x, cache_l = apply_block_prefill(p_l, cfg, x, capacity,
                                             kv_mask=kv_mask, enc_out=enc_out,
                                             segment_ids=segment_ids,
                                             positions=positions)
            outs.append(cache_l)
        caches = jax.tree.map(lambda *cs: jnp.stack(cs), *outs)
        return x, caches

    def body(x, p_l):
        x, cache_l = apply_block_prefill(p_l, cfg, x, capacity,
                                         kv_mask=kv_mask, enc_out=enc_out,
                                         segment_ids=segment_ids,
                                         positions=positions)
        return x, cache_l

    x, caches = jax.lax.scan(body, x, params)
    return x, caches
