"""Multi-head / grouped-query attention layer built on the flash core.

Supports three execution modes:
  * full-sequence (training / prefill)  — ``core.attention`` dispatch
  * prefill-with-cache                  — full-seq attention + cache write
  * single-token decode                 — ``core.decode_attention`` against
                                          a fixed-capacity KV cache
plus cross-attention (enc-dec) where K/V come from the encoder stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.attention import (AttentionSpec, attention, decode_attention,
                                  paged_decode_attention,
                                  paged_prefill_attention)
from repro.core.masks import segment_relative_positions
from repro.models.layers import apply_rope, dense_init, rms_normalize
from repro.telemetry import scopes


def attn_spec_from_config(cfg: ModelConfig) -> AttentionSpec:
    return AttentionSpec(
        impl=cfg.attn_impl, causal=cfg.causal, window=cfg.window,
        dropout_p=cfg.attn_dropout, unroll_chunks=cfg.unroll_chunks,
        chunk_size=cfg.attn_chunk_size, pv_bf16=cfg.attn_pv_bf16,
        banded_window=cfg.banded_window,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        num_decode_splits=cfg.num_decode_splits,
        use_decode_kernel=cfg.use_decode_kernel,
        tp_shards=cfg.tp_shards)


def _tp_reduce(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Sum the output projection's partial result over the tensor-parallel
    axis. With heads sharded over ``cfg.tp_axis``, each shard's
    ``_merge_heads(o) @ wo`` covers only its local head columns/rows of wo
    — the ONE collective the attention layer needs (DESIGN.md §13): Q/K/V
    projection, RoPE, cache writes, and attention itself are head-local
    because every q-head group lives with its kv head."""
    if cfg.tp_axis is None:
        return y
    return jax.lax.psum(y, cfg.tp_axis)


def init_attention(key, cfg: ModelConfig, dtype):
    hq, hkv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype),
        "wo": dense_init(ks[3], hq * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_specs(cfg: ModelConfig):
    s = {
        "wq": P("embed", "heads"),
        "wk": P("embed", "heads"),
        "wv": P("embed", "heads"),
        "wo": P("heads", "embed"),
    }
    if cfg.qk_norm:
        s["q_norm"] = P(None)
        s["k_norm"] = P(None)
    return s


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _project_qkv(params, cfg: ModelConfig, x, kv_x, positions, kv_positions):
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _split_heads(x @ params["wq"], hq, hd)
    k = _split_heads(kv_x @ params["wk"], hkv, hd)
    v = _split_heads(kv_x @ params["wv"], hkv, hd)
    if cfg.qk_norm:
        q = rms_normalize(q) * params["q_norm"]
        k = rms_normalize(k) * params["k_norm"]
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
    if kv_positions is not None:
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def apply_attention(
    params, cfg: ModelConfig, x,
    *,
    spec: AttentionSpec | None = None,
    kv_x: jax.Array | None = None,        # cross-attention source
    positions: jax.Array | None = None,
    kv_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,  # (b, s) packed-document ids
    block_layout=None,
    deterministic: bool = True,
    dropout_seed: int = 0,
):
    """Full-sequence attention. x: (b, s, d_model) -> (b, s, d_model).

    ``segment_ids`` isolates packed documents in self-attention AND makes
    RoPE segment-relative (positions restart at each document boundary), so
    packed execution is position-identical to per-document execution.
    Cross-attention ignores segment_ids (encoder K/V are a single stream).
    """
    cross = kv_x is not None
    kv_src = kv_x if cross else x
    sq = x.shape[1]
    if positions is None:
        if segment_ids is not None and not cross:
            positions = segment_relative_positions(segment_ids)
        else:
            positions = jnp.arange(sq)
    # cross-attention carries no RoPE (decoder q / encoder k live in
    # different position spaces); self-attention ropes both.
    q_positions = None if cross else positions
    kv_positions = None if cross else positions
    q, k, v = _project_qkv(params, cfg, x, kv_src, q_positions, kv_positions)
    spec = spec or attn_spec_from_config(cfg)
    if cross:
        spec = AttentionSpec(**{**spec.__dict__, "causal": False, "window": None})
    o = attention(q, k, v, spec, kv_mask=kv_mask,
                  segment_ids=None if cross else segment_ids,
                  block_layout=block_layout,
                  deterministic=deterministic, dropout_seed=dropout_seed)
    return _tp_reduce(_merge_heads(o) @ params["wo"], cfg)


# ---------------------------------------------------------------------------
# KV cache paths (serving)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype):
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, hkv, capacity, hd), dtype),
        "v": jnp.zeros((batch, hkv, capacity, hd), dtype),
    }


def kv_cache_specs():
    # capacity ("kv_seq") shards on the model axis: always divisible (32k/512k
    # cells), and decode attention over a sequence-sharded cache is the XLA
    # analogue of the split-KV decode kernel (DESIGN.md §6). KV-head counts
    # (5/8/...) often do NOT divide TP=16, so heads stay local.
    return {"k": P("data", None, "kv_seq", None),
            "v": P("data", None, "kv_seq", None)}


def prefill_attention(params, cfg: ModelConfig, x, cache, *, kv_mask=None,
                      segment_ids=None, positions=None,
                      spec: AttentionSpec | None = None):
    """Full-seq attention that also writes K/V into the cache at [0, s).

    Packed prefill passes ``segment_ids`` (and usually segment-relative
    ``positions``): each packed request's K/V rows are then identical to a
    batch-1 prefill of that request alone, so the serving engine can scatter
    row ranges straight into per-slot caches.
    """
    sq = x.shape[1]
    if positions is None:
        positions = (segment_relative_positions(segment_ids)
                     if segment_ids is not None else jnp.arange(sq))
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions)
    spec = spec or attn_spec_from_config(cfg)
    o = attention(q, k, v, spec, kv_mask=kv_mask, segment_ids=segment_ids,
                  deterministic=True)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    return _tp_reduce(_merge_heads(o) @ params["wo"], cfg), cache


def decode_attention_step(params, cfg: ModelConfig, x, cache, kv_len,
                          *, spec: AttentionSpec | None = None):
    """Single-token decode. x: (b, 1, d_model); kv_len: (b,) current lengths.

    Writes the new K/V at position kv_len (per sequence), then attends over
    [0, kv_len]. Returns (out, new_cache).
    """
    b = x.shape[0]
    positions = kv_len[:, None]                  # (b, 1) position of new token
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions)

    # scatter the new token's K/V at per-sequence write positions.
    if cfg.masked_cache_write:
        # iota-mask select: elementwise on the capacity dim, so a sequence-
        # sharded cache updates LOCALLY (no gather/reshard — §Perf decode
        # lever). Costs a full cache rewrite, which donation makes an
        # in-place HBM pass.
        capacity = cache["k"].shape[2]
        hit = (jnp.arange(capacity)[None, None, :, None]
               == kv_len[:, None, None, None])

        def _upd(c, new):
            return jnp.where(hit, new.astype(c.dtype), c)

        cache = {"k": _upd(cache["k"], k), "v": _upd(cache["v"], v)}
    else:
        # dynamic_update_slice (vmapped over batch) writes O(1 token); with
        # a sequence-sharded cache, the traced per-sequence index forces
        # GSPMD to reshard (measured in §Roofline as the decode collective
        # term) — flip cfg.masked_cache_write to trade it for a local pass.
        def _upd(c, new, pos):  # c: (hkv, cap, hd); new: (hkv, 1, hd)
            return jax.lax.dynamic_update_slice(c, new, (0, pos, 0))

        cache = {
            "k": jax.vmap(_upd)(cache["k"], k.astype(cache["k"].dtype), kv_len),
            "v": jax.vmap(_upd)(cache["v"], v.astype(cache["v"].dtype), kv_len),
        }

    spec = spec or attn_spec_from_config(cfg)
    o = decode_attention(q, cache["k"], cache["v"], kv_len + 1, spec)
    return _tp_reduce(_merge_heads(o) @ params["wo"], cfg), cache


# ---------------------------------------------------------------------------
# Paged KV cache path (serving; DESIGN.md §6)
# ---------------------------------------------------------------------------

def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype):
    """The shared page pool of every layer, LANE-DENSE: each K/V leaf is
    (L, num_pages, page_size, hkv*hd), so a page is one contiguous
    (page_size, hkv*hd) tile and the layer loop writes and reads it with
    one scatter and one gather indexed by (layer, page), with no layout
    change of the pool (DESIGN.md §6). Unlike the dense per-slot cache
    there is no batch dim: pages are the unit of allocation and any
    sequence's page table may point anywhere in the pool."""
    shape = (cfg.num_layers, num_pages, page_size,
             cfg.num_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_kv_cache_specs():
    # pages shard like the dense capacity dim ("kv_seq" on the model axis):
    # the pool's page dim is the sharded analogue of split-KV decode.
    return {"k": P(None, "kv_seq", None, None),
            "v": P(None, "kv_seq", None, None)}


def _sp_gather_kv(k: jax.Array, v: jax.Array, cfg: ModelConfig):
    """Assemble the chunk's FULL K/V across the sequence-parallel axis
    (DESIGN.md §14). Inside an sp-sharded chunk-prefill step each shard
    projects only its contiguous slab of the packed query rows; the page
    pool is REPLICATED over sp, so every shard must scatter the whole
    chunk — slab K/V therefore move between shards here, by one of two
    strategies costed in ``io_model.sp_prefill_hbm_bytes``:

    * ``"allgather"``: one ``all_gather(tiled=True)`` per layer over the
      stacked (k, v) pair — slab order matches the ``P(None, "sp")``
      input sharding, so the gathered sequence axis is exactly the packed
      chunk.
    * ``"ring"``: ``sp - 1`` neighbor ``ppermute`` steps; each shard
      starts from its own slab and places every arriving slab at the
      sender's (traced) slot, never materializing more than one in-flight
      slab beyond the output buffer.

    Both return bit-identical (k, v) of the full chunk on every shard —
    which is what keeps the sp-replicated pool replicas identical after
    the scatter. No-op when the config is not sp-sharded.
    """
    if cfg.sp_axis is None or cfg.sp_shards <= 1:
        return k, v
    kv = jnp.stack([k, v])                       # (2, 1, hkv, slab, hd)
    n = cfg.sp_shards
    if cfg.sp_strategy == "ring":
        slab = kv.shape[3]
        full = jnp.zeros(kv.shape[:3] + (slab * n,) + kv.shape[4:], kv.dtype)
        src = jax.lax.axis_index(cfg.sp_axis)
        cur = kv
        perm = [(i, (i + 1) % n) for i in range(n)]
        for step in range(n):
            full = jax.lax.dynamic_update_slice_in_dim(full, cur, src * slab,
                                                       axis=3)
            if step < n - 1:
                cur = jax.lax.ppermute(cur, cfg.sp_axis, perm)
                src = (src - 1) % n              # the slab now held came
                                                 # from the left neighbor
    elif cfg.sp_strategy == "allgather":
        full = jax.lax.all_gather(kv, cfg.sp_axis, axis=3, tiled=True)
    else:
        raise ValueError(f"unknown sp_strategy {cfg.sp_strategy!r}")
    return full[0], full[1]


def chunk_prefill_attention_step(params, cfg: ModelConfig, x, pool, layer,
                                 dest_page, dest_off, page_list,
                                 q_seg, kv_seg, q_pos, kv_pos,
                                 *, spec: AttentionSpec | None = None):
    """Packed chunked-prefill attention against the shared page pool,
    IN PLACE (DESIGN.md §10, §11).

    x: (1, S, d_model) — the NEXT prefill chunks of several sequences
    packed into one varlen call (q_seg isolates them); ``pool`` is every
    layer's pool and ``layer`` this layer's index into it. The new K/V rows
    are scattered straight into pool pages at ``(layer, dest_page,
    dest_off)``
    (logical positions ``hist_i + r``, pages grown chunk-by-chunk); the kv
    side — each segment's FULL logical prefix ``[0, hist_i + C_i)``,
    history written by earlier chunks plus the rows just scattered — is
    then attended THROUGH ``page_list`` (``kv_cache.paged_prefix_lists``):
    no per-layer gather copy ever materializes the prefix. The causal term
    runs on the traced logical positions (``q_pos``: hist_i + r;
    ``kv_pos``: 0..hist_i+C_i — the per-segment q_offset), so a chunk's
    queries attend all prior KV of their own sequence and themselves
    causally: chunked prefill is EXACT attention over the same prefix the
    atomic prefill sees. RoPE uses the same logical positions, making the
    K rows written here bit-compatible with atomic-prefill and decode-step
    writes. Returns (out, new_pool).

    Under sequence parallelism (``cfg.sp_axis`` set, DESIGN.md §14) x and
    the q-side rows (``q_seg``, ``q_pos``) are this shard's SLAB of the
    packed chunk while everything kv-side (``dest_page``/``dest_off``/
    ``page_list``/``kv_seg``/``kv_pos``) stays replicated: the projection
    and RoPE run on the slab's own traced positions (exact for any
    offset), ``_sp_gather_kv`` assembles the full chunk's K/V, and the
    scatter + paged attention below are unchanged — each shard writes all
    chunk rows (keeping pool replicas identical) and attends only its
    slab's queries.
    """
    q, k, v = _project_qkv(params, cfg, x, x, q_pos, q_pos)
    k, v = _sp_gather_kv(k, v, cfg)

    def _scat(c, new):  # c: (L, P, ps, hkv*hd); new: (1, hkv, S, hd)
        rows = new[0].transpose(1, 0, 2).reshape(new.shape[2], -1)
        return c.at[layer, dest_page, dest_off].set(rows.astype(c.dtype),
                                                    mode="drop")

    with scopes.named(scopes.KV_WRITE):
        pool = {"k": _scat(pool["k"], k), "v": _scat(pool["v"], v)}
    spec = spec or attn_spec_from_config(cfg)
    o = paged_prefill_attention(q, pool["k"], pool["v"], layer, page_list,
                                spec,
                                q_segment_ids=q_seg, kv_segment_ids=kv_seg,
                                q_positions=q_pos, kv_positions=kv_pos)
    return _tp_reduce(_merge_heads(o) @ params["wo"], cfg), pool


def paged_decode_attention_step(params, cfg: ModelConfig, x, pool, layer,
                                page_table, kv_len,
                                *, spec: AttentionSpec | None = None):
    """Single-token decode against the shared page pool.

    x: (b, 1, d_model); pool leaves (L, num_pages, page_size, hkv*hd),
    ``layer`` this layer's index into them; page_table: (b, pages_per_seq)
    int32, negative = unallocated; kv_len: (b,) logical lengths. Writes
    the new K/V into layer ``layer``, physical page
    ``page_table[b, kv_len // page_size]`` at offset ``kv_len % page_size``
    (one batched scatter; rows whose table entry is unallocated — idle
    batch rows — are DROPPED, so they can never corrupt another sequence's
    pages), then attends over [0, kv_len]. RoPE positions are the logical
    ``kv_len`` exactly as in the dense path, so paged decode is
    token-identical to dense decode. Returns (out, new_pool).
    """
    positions = kv_len[:, None]                  # (b, 1) position of new token
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions)

    num_pages, page_size = pool["k"].shape[1:3]
    T = page_table.shape[1]
    lp = jnp.minimum(kv_len // page_size, T - 1)
    off = kv_len % page_size
    phys = jnp.take_along_axis(page_table, lp[:, None], axis=1)[:, 0]
    # unallocated entries AND rows already at full table capacity -> index
    # num_pages, out of bounds under mode='drop'. Without the capacity
    # guard the lp clamp above would redirect an overflow write into the
    # LAST allocated page — silent corruption of live rows instead of a
    # dropped write.
    phys = jnp.where((phys < 0) | (kv_len >= T * page_size), num_pages, phys)

    def _upd(c, new):  # c: (L, P, ps, hkv*hd); new: (b, hkv, 1, hd)
        rows = new.reshape(new.shape[0], -1).astype(c.dtype)  # (b, hkv*hd)
        return c.at[layer, phys, off].set(rows, mode="drop")

    with scopes.named(scopes.KV_WRITE):
        pool = {"k": _upd(pool["k"], k), "v": _upd(pool["v"], v)}
    spec = spec or attn_spec_from_config(cfg)
    o = paged_decode_attention(q, pool["k"], pool["v"], layer, page_table,
                               kv_len + 1, spec)
    return _tp_reduce(_merge_heads(o) @ params["wo"], cfg), pool
