"""Split-KV flash decode kernel (FlashDecoding-style adaptation of Alg. 1).

Serving decode computes attention for ONE new query token against a long KV
cache. The dense kernel's q-block grid degenerates (nq == 1), so the
parallelism must come from splitting the KV axis: each split runs the
Algorithm-1 inner loop over its KV slice and emits a *partial* softmax state
(m, l, acc); the partials are merged with the associative online-softmax
merge operator (``repro.core.online_softmax.merge_states``) — the same
algebra the paper uses to decompose softmax across blocks, here exploited
for parallelism instead of memory locality.

Block skipping uses the same mask IR as the training kernels (DESIGN.md §3):
the per-sequence validity band (``kv_len`` + optional sliding window +
optional ``kv_mask``) is lowered ONCE per call at the XLA level —
``masks.decode_kv_valid`` expresses decode as the fused mask with
``q_pos = kv_len - 1``, and ``masks.kv_block_layout`` classifies each kv
block SKIP / FULL / PARTIAL. SKIP blocks (past the valid length, before the
window start, or fully masked-out) never run; FULL blocks drop the
element-level compares entirely; PARTIAL blocks apply the fused mask.

On a real TPU the split axis is marked parallel (megacore / multiple cores);
the combine is a tiny XLA reduction.

Two cache geometries share the same kernel body:
  * ``flash_decode``       — contiguous per-sequence cache (b, hkv, sk, d);
  * ``flash_decode_paged`` — a shared page pool (hkv, pages, page_size, d)
    plus per-sequence page tables. The page is the mask IR's kv block, and
    the physical page index is resolved inside the BlockSpec index_map from
    a scalar-prefetched page table (one page DMA per grid step).
Both validate their geometry up front (capacity % block multiples) instead
of silently padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import masks as M
from repro.core.masks import NEG_INF
from repro.kernels import tuning
from repro.kernels.ops import default_interpret
from repro.kernels.flash_attention import LANES


def _decode_kernel(kvl_ref, lay_ref, q_ref, k_ref, v_ref, kvm_ref,
                   o_ref, m_ref, l_ref, acc_sc, m_sc, l_sc, *,
                   scale, block_k, window, num_blocks):
    b = pl.program_id(0)
    si, ki = pl.program_id(2), pl.program_id(3)   # split idx, block-in-split
    nk_in = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # kv_len (b,) and the flattened (b, num_blocks) layout sit in SMEM
    kv_len = kvl_ref[b]
    k0 = (si * nk_in + ki) * block_k
    blk = lay_ref[b * num_blocks + si * nk_in + ki]

    def _step(apply_mask):
        q = q_ref[0, 0].astype(jnp.float32)              # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (1, bk)

        if apply_mask:
            # decode == the fused mask at q_pos = kv_len - 1: causality is
            # k_pos < kv_len, the window keeps the last `window` valid
            # cache positions (same semantics as the XLA decode path).
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = M.element_mask(
                kv_len - 1, k_pos, causal=True, window=window,
                kv_valid=kvm_ref[0, 0] != 0 if kvm_ref is not None else None)
            s = jnp.where(ok, s, NEG_INF)

        m_prev, l_prev = m_sc[:, 0], l_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, None]))
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new[:, None], l_sc.shape)

    pl.when(blk == M.BLOCK_PARTIAL)(lambda: _step(True))
    pl.when(blk == M.BLOCK_FULL)(lambda: _step(False))

    @pl.when(ki == nk_in - 1)
    def _emit_partial():
        o_ref[0, 0, 0] = acc_sc[...]      # unnormalized partial (1, d)
        m_ref[0, 0, 0] = m_sc[...]        # lane-replicated (1, LANES)
        l_ref[0, 0, 0] = l_sc[...]


def _split_decode_call(kernel, *, grid, prefetch, q, k, v, kv_spec, kvm,
                       block_k, interpret):
    """The split-KV pallas_call shared by both cache geometries. Scalar
    prefetch carries kv_len, the flattened block layout and (paged) the
    page table; each split's partial state leaves as its own
    (1, d) / (1, LANES) block of a (b, hq, splits, 1, ·) array — the
    singleton axis keeps the block legal for any split count. Returns the
    merged (b, hq, 1, d) output."""
    b, hq, _, d = q.shape
    num_splits = grid[2]
    n_pre = len(prefetch)

    in_specs = [pl.BlockSpec((1, 1, 1, d),
                             lambda b, h, si, ki, *_: (b, h, 0, 0)),
                kv_spec, kv_spec]
    args = [q, k, v]
    if kvm is not None:
        nk_in = grid[3]
        in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                     lambda b, h, si, ki, *_:
                                     (b, si * nk_in + ki, 0, 0)))
        args.append(kvm.astype(jnp.int32).reshape(b, -1, 1, block_k))

    def wrapped(*refs):
        kvl_ref, lay_ref = refs[n_pre - 2], refs[n_pre - 1]
        q_ref, k_ref, v_ref, *rest = refs[n_pre:]
        kvm_ref = rest.pop(0) if kvm is not None else None
        return kernel(kvl_ref, lay_ref, q_ref, k_ref, v_ref, kvm_ref, *rest)

    def part(width):
        return pl.BlockSpec((1, 1, 1, 1, width),
                            lambda b, h, si, ki, *_: (b, h, si, 0, 0))

    o_p, m_p, l_p = pl.pallas_call(
        wrapped,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, grid=grid, in_specs=in_specs,
            out_specs=[part(d), part(LANES), part(LANES)],
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32),
                            pltpu.VMEM((1, LANES), jnp.float32),
                            pltpu.VMEM((1, LANES), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, num_splits, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, num_splits, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, num_splits, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch, *args)
    return _merge_split_partials(o_p[:, :, :, 0], m_p[:, :, :, 0, 0],
                                 l_p[:, :, :, 0, 0], q.dtype)


def flash_decode(
    q: jax.Array,          # (b, hq, 1, d)
    k: jax.Array,          # (b, hkv, sk, d)  — KV cache (capacity sk)
    v: jax.Array,
    kv_len: jax.Array,     # (b,) int32 valid lengths
    *,
    scale: float | None = None,
    block_k: int | None = None,        # None = resolve via kernels.tuning
    num_splits: int | None = None,
    window: int | None = None,
    kv_mask: jax.Array | None = None,   # (b, sk) True = valid cache slot
    interpret: bool | None = None,
    shards: int = 1,                    # tensor-parallel shard count (per-
                                        # shard split target + tuning key)
) -> jax.Array:
    """One-token attention against a fixed-capacity KV cache. Returns
    (b, hq, 1, d). GQA handled via kv index_map. ``window`` keeps only the
    last ``window`` valid cache positions (matches the XLA decode path's
    sliding-window semantics); ``kv_mask`` masks out individual cache slots.
    Blocks past the valid length, before the window start, or fully
    masked-out are classified SKIP by the compiled per-batch layout and
    never run.

    ``block_k``/``num_splits`` left ``None`` resolve through
    ``tuning.resolve_decode_geometry`` — divisor-valid by construction;
    explicit values are validated exactly as before (misalignment raises)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert sq == 1, "flash_decode handles single-token decode; use flash_attention otherwise"
    n_rep = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = default_interpret()

    block_k, num_splits = tuning.resolve_decode_geometry(
        sk, block_k, num_splits, head_dim=d, dtype=k.dtype, shards=shards)
    nk_in = (sk // block_k) // num_splits

    kvm = kv_mask
    kv_len = kv_len.astype(jnp.int32)
    # one XLA-level layout pass per call: (b, num_splits * nk_in) classes
    kv_valid = M.decode_kv_valid(kv_len, sk, window=window, kv_mask=kvm)
    layout = M.kv_block_layout(kv_valid, block_k).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               window=window, num_blocks=num_splits * nk_in)
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b, h, si, ki, *_: (b, h // n_rep, si * nk_in + ki, 0))
    return _split_decode_call(
        kernel, grid=(b, hq, num_splits, nk_in),
        prefetch=[kv_len, layout.reshape(-1)], q=q, k=k, v=v,
        kv_spec=kv_spec, kvm=kvm, block_k=block_k, interpret=interpret)


def validate_decode_geometry(capacity: int, block_k: int,
                             num_splits: int) -> tuple[int, int]:
    """Clamp-then-validate the contiguous decode grid. Shape-derived
    clamps are documented and deterministic: a block cannot exceed the
    cache, and there cannot be more splits than blocks. What is NOT
    silently absorbed is misalignment — the old path zero-padded the cache
    up to num_splits * block_k, which silently changed the grid (and HBM
    traffic) behind the caller's back. Called by ``flash_decode`` and by
    the serving engine at construction, so a bad (capacity, block) combo
    fails fast instead of at the first jitted decode step.
    """
    block_k = min(block_k, capacity)
    num_splits = min(num_splits, max(1, capacity // max(block_k, 1)))
    if capacity % block_k:
        raise ValueError(
            f"flash_decode: cache capacity ({capacity}) must be a multiple "
            f"of block_k ({block_k}); pad the cache at allocation time")
    nk = capacity // block_k
    if nk % num_splits:
        raise ValueError(
            f"flash_decode: cache capacity ({capacity}) must be a multiple "
            f"of num_splits * block_k ({num_splits} * {block_k}); choose a "
            f"num_splits dividing the {nk} kv blocks")
    return block_k, num_splits


def validate_paged_decode_geometry(pages_per_seq: int,
                                   num_splits: int) -> int:
    """Paged analogue: the page IS the block, so only the split count can
    misalign. Returns the clamped num_splits."""
    num_splits = min(num_splits, pages_per_seq)
    if pages_per_seq % num_splits:
        raise ValueError(
            f"flash_decode_paged: pages per sequence ({pages_per_seq}) must "
            f"be a multiple of num_splits ({num_splits})")
    return num_splits


def _merge_split_partials(o_p, m_p, l_p, dtype):
    """Combine per-split partial softmax states with the online-softmax
    merge (vectorized over splits). o_p: (b, hq, splits, d); m_p/l_p:
    (b, hq, splits). Fully-masked rows (all partials empty) emit zeros."""
    m = jnp.max(m_p, axis=-1)                                     # (b, hq)
    w = jnp.where(m_p <= NEG_INF / 2, 0.0, jnp.exp(m_p - m[..., None]))
    l = jnp.sum(l_p * w, axis=-1)
    acc = jnp.sum(o_p * w[..., None], axis=2)                     # (b, hq, d)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(dtype)
    return out[:, :, None, :]


def flash_decode_paged(
    q: jax.Array,            # (b, hq, 1, d)
    k_pool: jax.Array,       # (hkv, num_pages, page_size, d) — shared pool
    v_pool: jax.Array,
    page_table: jax.Array,   # (b, pages_per_seq) int32; negative = unallocated
    kv_len: jax.Array,       # (b,) int32 valid lengths
    *,
    scale: float | None = None,
    num_splits: int | None = None,     # None = resolve via kernels.tuning
    window: int | None = None,
    interpret: bool | None = None,
    shards: int = 1,                   # tensor-parallel shard count (per-
                                       # shard split target + tuning key)
) -> jax.Array:
    """Split-KV decode against a PAGED KV cache (DESIGN.md §6).

    The pool is shared by all sequences; ``page_table`` maps each
    sequence's logical kv block t (positions [t*page_size, (t+1)*page_size))
    to a physical pool page. The page IS the mask IR's kv block
    (block_k == page_size): ``masks.paged_block_layout`` classifies each
    logical page SKIP / FULL / PARTIAL exactly as the contiguous kernel
    classifies blocks, and the kernel's kv grid walks the page table — the
    physical page index comes from a scalar-prefetched table read inside
    the BlockSpec index_map, so each grid step DMAs exactly one page
    (indirection instead of a contiguous slice). SKIP pages (beyond
    kv_len, before the window start, or unallocated) never contribute;
    FULL pages drop the element compares.
    """
    b, hq, sq, d = q.shape
    hkv, num_pages, page_size, _ = k_pool.shape
    assert sq == 1, "flash_decode_paged handles single-token decode"
    n_rep = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = default_interpret()

    T = page_table.shape[1]
    if num_splits is None:
        _, num_splits = tuning.resolve_decode_geometry(
            T * page_size, None, None, head_dim=d, dtype=k_pool.dtype,
            page_size=page_size, shards=shards)
    num_splits = validate_paged_decode_geometry(T, num_splits)
    t_in = T // num_splits

    kv_len = kv_len.astype(jnp.int32)
    # one XLA-level lowering per call: (b, T) page classes; unallocated
    # entries are SKIP, so clamping them to page 0 for the fetch below is
    # observationally irrelevant (the kernel body never runs on them).
    layout = M.paged_block_layout(kv_len, page_table, page_size,
                                  window=window).astype(jnp.int32)
    table = jnp.maximum(page_table, 0).astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=page_size, window=window,
                               num_blocks=T)
    kv_spec = pl.BlockSpec(
        (1, 1, page_size, d),
        lambda b, h, si, ki, tab, *_: (h // n_rep, tab[b, si * t_in + ki],
                                       0, 0))
    return _split_decode_call(
        kernel, grid=(b, hq, num_splits, t_in),
        prefetch=[table, kv_len, layout.reshape(-1)], q=q, k=k_pool,
        v=v_pool, kv_spec=kv_spec, kvm=None, block_k=page_size,
        interpret=interpret)
