"""Attention dispatch: one API, multiple IO-aware implementations.

``attention(...)`` picks the implementation:
  * ``pallas``    — the FlashAttention Pallas kernels (real TPU, or
                    interpret-mode for tests). The paper's contribution.
  * ``chunked``   — Algorithm 1 expressed at the XLA level with lax.scan
                    (online softmax, O(N) memory). Used by the large-scale
                    dry-run on the CPU backend where a TPU kernel cannot
                    lower; also the production fallback for shapes the
                    kernel does not cover.
  * ``reference`` — Algorithm 0 (materializes S/P). The paper's baseline;
                    kept as a first-class impl so every benchmark can
                    compare standard vs flash on equal footing.
  * ``block_sparse`` — the same Pallas path with an Alg. 5 sparse pattern.

There is no block-sparse-vs-dense fork: EVERY Pallas call's masks compile
to a block layout (``core.masks.compile_block_layout`` in kernels/ops.py);
"block_sparse" merely adds a sparse pattern to that compilation, and the
oracles evaluate the same ``core.masks`` fused element mask (DESIGN.md §3).

``decode_attention(...)`` is the single-token serving path (split-KV flash
decode kernel or an XLA softmax fallback — decode scores are (b,h,1,L), so
the XLA path is already O(L) memory; the kernel exists for IO/parallelism).
Both paths derive key validity from ``masks.decode_kv_valid`` (kv_len +
window + optional slot mask) and mask with the shared NEG_INF sentinel.

Implementations are numerically interchangeable (tests assert pairwise
agreement) — exactness is the paper's core claim. Each entry point traces
under the ``attention`` named scope (``telemetry/scopes.py``), whatever
implementation it dispatches to, so a profile splits attention's device
time from the rest of a step program.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import masks
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.telemetry.scopes import ATTENTION, scoped

AttnImpl = Literal["pallas", "chunked", "reference", "block_sparse"]


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Static attention configuration carried by model configs.

    ``block_q`` / ``block_k`` / ``num_decode_splits`` default to ``None`` =
    **auto**: every consumer resolves them through ``kernels.tuning`` (the
    analytic SRAM-budget chooser, or the empirical autotuner when enabled)
    at the call site where the true shapes are known. Explicit integers pin
    the geometry and are validated, never silently adjusted.
    """
    impl: AttnImpl = "chunked"
    causal: bool = True
    window: int | None = None
    dropout_p: float = 0.0
    block_q: int | None = None
    block_k: int | None = None
    chunk_size: int = 1024
    variant: str = "fa2"            # pallas accumulator variant: "paper"|"fa2"
    num_decode_splits: int | None = None
    use_decode_kernel: bool = False
    unroll_chunks: bool = False     # dry-run cost probes only
    pv_bf16: bool = False           # cast P to bf16 for P@V (f32 accumulate)
    banded_window: bool = False     # banded layout for sliding-window attn
    tp_shards: int = 1              # tensor-parallel shard count of the
                                    # calling step: joins the tuning cache
                                    # key and biases tile choice toward
                                    # per-shard grid occupancy (head counts
                                    # seen here are then per-shard)


@scoped(ATTENTION)
def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    spec: AttentionSpec,
    *,
    kv_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,   # (b, s) packed-document ids
    q_segment_ids: jax.Array | None = None,   # (b, sq) explicit q-side ids
    kv_segment_ids: jax.Array | None = None,  # (b, sk) explicit kv-side ids
    q_positions: jax.Array | None = None,     # (b, sq) logical positions
    kv_positions: jax.Array | None = None,    # (b, sk) logical positions
    block_layout=None,
    dropout_seed: int = 0,
    deterministic: bool = True,
    q_offset: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """(b, hq, sq, d) x (b, hkv, sk, d)^2 -> (b, hq, sq, d).

    ``segment_ids`` makes packed (varlen) sequences first-class for every
    impl: tokens attend only within their own segment (DESIGN.md §8).
    Suffix shapes (sq != sk) pass ``q_segment_ids``/``kv_segment_ids``
    explicitly, and ``q_positions``/``kv_positions`` give the causal term
    a per-segment q_offset (chunked prefill, DESIGN.md §10) — every impl
    evaluates the same fused mask either way.
    """
    dropout_p = 0.0 if deterministic else spec.dropout_p
    common = dict(causal=spec.causal, window=spec.window, kv_mask=kv_mask,
                  segment_ids=segment_ids, q_segment_ids=q_segment_ids,
                  kv_segment_ids=kv_segment_ids, q_positions=q_positions,
                  kv_positions=kv_positions, scale=scale, q_offset=q_offset)
    if spec.impl in ("pallas", "block_sparse"):
        # One path: every call's masks compile to a block layout inside
        # kernels/ops.py; "block_sparse" is just the Alg. 5 sparse pattern
        # folded into the same compilation (and requires one).
        if spec.impl == "block_sparse" and block_layout is None:
            raise ValueError("impl=block_sparse requires block_layout")
        return kops.flash_attention(
            q, k, v, dropout_p=dropout_p, dropout_seed=dropout_seed,
            block_q=spec.block_q, block_k=spec.block_k, variant=spec.variant,
            block_layout=block_layout, shards=spec.tp_shards, **common)
    if spec.impl == "chunked":
        if dropout_p > 0.0:
            # chunked XLA path does not implement attention-matrix dropout;
            # models using it apply residual dropout instead (documented).
            raise ValueError("attention dropout requires impl='pallas'")
        if (spec.banded_window and spec.window is not None
                and kv_mask is None and segment_ids is None
                and q_segment_ids is None and q_positions is None
                and q.shape[2] == k.shape[2] and (q_offset in (None, 0))):
            return kref.window_banded_attention(
                q, k, v, window=spec.window, scale=scale,
                pv_bf16=spec.pv_bf16)
        return kref.chunked_attention(q, k, v, chunk_size=spec.chunk_size,
                                      unroll=spec.unroll_chunks,
                                      pv_bf16=spec.pv_bf16, **common)
    if spec.impl == "reference":
        return kref.standard_attention(
            q, k, v, dropout_p=dropout_p, dropout_seed=dropout_seed, **common)
    raise ValueError(f"unknown attention impl {spec.impl!r}")


@scoped(ATTENTION)
def decode_attention(
    q: jax.Array,            # (b, hq, 1, d)
    k_cache: jax.Array,      # (b, hkv, capacity, d)
    v_cache: jax.Array,
    kv_len: jax.Array,       # (b,) int32
    spec: AttentionSpec,
    *,
    kv_mask: jax.Array | None = None,   # (b, capacity) True = valid slot
    scale: float | None = None,
) -> jax.Array:
    if spec.use_decode_kernel:
        return flash_decode(q, k_cache, v_cache, kv_len,
                            scale=scale, block_k=spec.block_k,
                            num_splits=spec.num_decode_splits,
                            window=spec.window, kv_mask=kv_mask,
                            shards=spec.tp_shards)
    kvm = masks.decode_kv_valid(kv_len, k_cache.shape[2], window=spec.window,
                                kv_mask=kv_mask)
    return _masked_decode(q, k_cache, v_cache, kvm, scale, "bksd")


def _masked_decode(q, k, v, kv_valid, scale, kv_axes):
    """The XLA decode path: GQA-NATIVE masked softmax over a cache whose
    axes ``kv_axes`` names, "bksd" (the dense slot cache) or "bskd" (rows
    gathered from the lane-dense page pool). q is reshaped to
    (b, hkv, rep, 1, d) and contracted against the UNEXPANDED cache —
    repeat_kv would broadcast-materialize the cache and force GSPMD to
    all-gather the sequence-sharded capacity dim (measured: 2.1 GB/layer
    on qwen3 decode_32k — §Roofline decode collective term). Keeping the
    cache un-reshaped leaves the capacity dim sharded through the scores;
    the softmax reduction and P@V contraction then reduce over it with
    small collectives (the XLA analogue of split-KV flash decode)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[kv_axes.index("k")]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, hq // hkv, sq, d)
    s = jnp.einsum(f"bkrqd,{kv_axes}->bkrqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    # the same validity band the decode kernel compiles its layout from
    # (kv_len + window + optional slot mask), masked with the one NEG_INF
    # sentinel every impl shares.
    s = jnp.where(kv_valid[:, None, None, None, :], s, masks.NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s <= masks.NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    # fully-masked rows (kv_len == 0 / garbage batch rows) emit zeros, the
    # same convention as the split-KV kernel's empty-partial merge.
    p = p / jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum(f"bkrqs,{kv_axes}->bkrqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def _gather_pages(pool, layer, pages, d):
    """Layer ``layer``'s rows of the lane-dense pool (L, num_pages,
    page_size, hkv*d) at ``pages`` (b, T), as (b, T*page_size, hkv, d):
    ONE gather indexed by (layer, page), so no layer of the pool is sliced
    out first. Dead entries (negative) read page 0; the caller masks
    them."""
    num_pages, page_size, width = pool.shape[1:]
    b, T = pages.shape
    rows = pool[layer, jnp.clip(pages, 0, num_pages - 1)]
    return rows.reshape(b, T * page_size, width // d, d)


def _head_major(pool, layer, d):
    """Layer ``layer`` of the lane-dense pool re-viewed as the
    (hkv, num_pages, page_size, d) operand the paged Pallas kernels read.
    A copy of that layer on every call, on the kernel path only, until the
    kernels read lane-dense pages (ROADMAP Queue 1 item 3)."""
    num_pages, page_size, width = pool.shape[1:]
    return pool[layer].reshape(
        num_pages, page_size, width // d, d).transpose(2, 0, 1, 3)


@scoped(ATTENTION)
def paged_prefill_attention(
    q: jax.Array,            # (b, hq, sq, d) — the suffix chunk's queries
    k_pool: jax.Array,       # (L, num_pages, page_size, hkv*d) — shared pool
    v_pool: jax.Array,
    layer,                   # the calling layer's index into the pool
    page_list: jax.Array,    # (b, T) int32; negative = dead (never read)
    spec: AttentionSpec,
    *,
    q_segment_ids: jax.Array,    # (b, sq)
    kv_segment_ids: jax.Array,   # (b, T*page_size), SEG_PAD_KV on dead rows
    q_positions: jax.Array,      # (b, sq) logical positions
    kv_positions: jax.Array,     # (b, T*page_size), POS_PAD on dead rows
    scale: float | None = None,
) -> jax.Array:
    """Chunked-prefill attention over the PAGED prefix, in place.

    The Pallas path (``impl`` in {pallas, block_sparse}) hands the page
    list to ``flash_prefill_paged``: the kv BlockSpec index_map resolves
    physical pages from the scalar-prefetched table, so the kernel attends
    the (re-viewed) layer directly — one page DMA per kv block, SKIP pages
    never read.

    Every other impl is the XLA parity oracle: gather the layer's pages
    into the logical (b, hkv, T*page_size, d) view (clamped to page 0 on
    dead entries) and reuse ``attention`` verbatim. Dead rows carry the
    SEG_PAD_KV / POS_PAD sentinels in ``kv_segment_ids``/``kv_positions``,
    so the shared fused mask kills them on both paths — validity is one
    definition, not two.
    """
    d = q.shape[-1]
    common = dict(q_positions=q_positions, kv_positions=kv_positions,
                  q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
                  scale=scale)
    if spec.impl in ("pallas", "block_sparse"):
        return kops.flash_prefill_paged(
            q, _head_major(k_pool, layer, d), _head_major(v_pool, layer, d),
            page_list, causal=spec.causal, window=spec.window,
            block_q=spec.block_q, variant=spec.variant,
            shards=spec.tp_shards, **common)

    def gather(pool):
        return _gather_pages(pool, layer, page_list, d).transpose(0, 2, 1, 3)

    return attention(q, gather(k_pool), gather(v_pool), spec, **common)


@scoped(ATTENTION)
def paged_decode_attention(
    q: jax.Array,            # (b, hq, 1, d)
    k_pool: jax.Array,       # (L, num_pages, page_size, hkv*d) — shared pool
    v_pool: jax.Array,
    layer,                   # the calling layer's index into the pool
    page_table: jax.Array,   # (b, pages_per_seq) int32; negative = unallocated
    kv_len: jax.Array,       # (b,) int32
    spec: AttentionSpec,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Single-token decode against a paged KV cache.

    With ``spec.use_decode_kernel`` the split-KV Pallas kernel walks the
    page table directly (one page DMA per kv block, SKIP pages never
    fetched) over the re-viewed layer. The XLA parity path gathers the
    sequence's pages as (b, T*page_size, hkv, d) rows and contracts them
    GQA-natively (``_masked_decode``), with no transpose of the pool —
    unallocated table entries become masked slots (gather is clamped to
    page 0, then killed by the kv_mask), so both paths derive validity
    from the same ``masks.decode_kv_valid`` band.
    """
    d = q.shape[-1]
    if spec.use_decode_kernel:
        return flash_decode_paged(q, _head_major(k_pool, layer, d),
                                  _head_major(v_pool, layer, d),
                                  page_table, kv_len, scale=scale,
                                  num_splits=spec.num_decode_splits,
                                  window=spec.window,
                                  shards=spec.tp_shards)
    page_size = k_pool.shape[2]
    alloc = jnp.repeat(page_table >= 0, page_size, axis=1)   # (b, T*ps)
    kvm = masks.decode_kv_valid(kv_len, alloc.shape[1], window=spec.window,
                                kv_mask=alloc)
    return _masked_decode(q, _gather_pages(k_pool, layer, page_table, d),
                          _gather_pages(v_pool, layer, page_table, d),
                          kvm, scale, "bskd")
