"""Public jit'd entry points for the Pallas kernels.

``flash_attention`` assembles the forward/backward Pallas kernels into a
differentiable op via ``jax.custom_vjp`` (residuals: q, k, v, o, m, l — the
paper's O(N) extra memory), handles padding to block multiples, and exposes
the paper-faithful / fa2 accumulator variants.

Masks are COMPILED ONCE here: the call's arguments (causal/window/q_offset,
kv padding, kv_mask, packed segment ids, optional Alg. 5 sparse pattern)
become a ``core.masks.MaskSpec``, which ``compile_block_layout`` lowers to
the block layout the fwd/dq/dkv kernels consume. The layout rides the
custom_vjp residuals, so the backward pass reuses the forward's compilation
(including the once-per-batch segment min/max reduction) instead of
re-deriving skip predicates per grid step.

``interpret`` left ``None`` resolves through ``default_interpret()``: on a
TPU the kernels compile through Mosaic (``tpu_custom_call`` in the HLO); on
any other backend — the CPU test suite — Pallas interprets the kernel body
op by op, exact but with meaningless wall-clock. The low-level wrappers in
``flash_attention`` take the resolved value and have no default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masks import (MaskSpec, POS_PAD, SEG_PAD_KV, SEG_PAD_Q,
                              compile_block_layout, paged_prefill_block_layout,
                              resolve_segment_ids)
from repro.kernels import flash_attention as fa
from repro.kernels import ref as ref_mod
from repro.kernels import tuning


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20),
)
def _flash_core(q, k, v, kv_mask, q_seg, kv_seg, q_pos, kv_pos, block_layout,
                dropout_seed, scale, causal, window, q_offset, kv_valid_len,
                dropout_p, block_q, block_k, variant, dropout_dims, interpret):
    o, _, _ = fa.flash_attention_forward(
        q, k, v, kv_mask, block_layout, scale=scale, causal=causal,
        window=window, q_offset=q_offset, kv_valid_len=kv_valid_len,
        dropout_p=dropout_p, dropout_seed=dropout_seed,
        block_q=block_q, block_k=block_k, variant=variant,
        dropout_dims=dropout_dims,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        q_positions=q_pos, kv_positions=kv_pos,
        interpret=interpret)
    return o


def _flash_core_fwd(q, k, v, kv_mask, q_seg, kv_seg, q_pos, kv_pos,
                    block_layout, dropout_seed, scale, causal, window,
                    q_offset, kv_valid_len, dropout_p, block_q, block_k,
                    variant, dropout_dims, interpret):
    o, m, l = fa.flash_attention_forward(
        q, k, v, kv_mask, block_layout, scale=scale, causal=causal,
        window=window, q_offset=q_offset, kv_valid_len=kv_valid_len,
        dropout_p=dropout_p, dropout_seed=dropout_seed,
        block_q=block_q, block_k=block_k, variant=variant,
        dropout_dims=dropout_dims,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        q_positions=q_pos, kv_positions=kv_pos,
        interpret=interpret)
    return o, (q, k, v, kv_mask, q_seg, kv_seg, q_pos, kv_pos, block_layout,
               dropout_seed, o, m, l)


def _flash_core_bwd(scale, causal, window, q_offset, kv_valid_len, dropout_p,
                    block_q, block_k, variant, dropout_dims, interpret, res, do):
    (q, k, v, kv_mask, q_seg, kv_seg, q_pos, kv_pos, block_layout,
     dropout_seed, o, m, l) = res
    dq, dk, dv = fa.flash_attention_backward(
        q, k, v, o, do, m, l, kv_mask, block_layout,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        kv_valid_len=kv_valid_len,
        dropout_p=dropout_p, dropout_seed=dropout_seed,
        block_q=block_q, block_k=block_k, dropout_dims=dropout_dims,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        q_positions=q_pos, kv_positions=kv_pos, interpret=interpret)

    def _zero_tangent(x):
        return None if x is None else np.zeros(x.shape, jax.dtypes.float0)

    return (dq, dk, dv, _zero_tangent(kv_mask), _zero_tangent(q_seg),
            _zero_tangent(kv_seg), _zero_tangent(q_pos),
            _zero_tangent(kv_pos), _zero_tangent(block_layout),
            np.zeros((), jax.dtypes.float0))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,                      # (b, hq, sq, d)
    k: jax.Array,                      # (b, hkv, sk, d)
    v: jax.Array,                      # (b, hkv, sk, d)
    *,
    kv_mask: jax.Array | None = None,  # (b, sk) True = valid
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int | None = None,
    dropout_p: float = 0.0,
    dropout_seed: int = 0,
    block_q: int | None = None,       # None = resolve via kernels.tuning
    block_k: int | None = None,
    variant: str = "fa2",              # "paper" (Alg. 1 faithful) | "fa2"
    block_layout=None,                 # (nq, nk) uint8 sparse pattern (Alg. 5)
    segment_ids: jax.Array | None = None,     # (b, s) packed ids (self-attn)
    q_segment_ids: jax.Array | None = None,   # (b, sq) explicit q-side ids
    kv_segment_ids: jax.Array | None = None,  # (b, sk) explicit kv-side ids
    q_positions: jax.Array | None = None,     # (b, sq) logical positions
    kv_positions: jax.Array | None = None,    # (b, sk) logical positions
    kv_major: bool | None = None,      # None = loop order resolved via tuning
    interpret: bool | None = None,
    shards: int = 1,                   # tensor-parallel shard count of the
                                       # calling step (per-shard tuning key)
) -> jax.Array:
    """Differentiable FlashAttention (Pallas). Pads seq dims to block
    multiples internally; GQA inferred from head counts. Every call's mask
    arguments are lowered through ``core.masks.compile_block_layout`` to the
    block layout the kernels consume — causal/window geometry, kv padding
    tails, packed-segment structure, and the optional ``block_layout``
    sparse pattern (paper Alg. 5, authoritative over geometry) all become
    SKIP / FULL / PARTIAL classes in one place. ``segment_ids`` isolates
    packed (varlen) documents: tokens attend only within their own segment.
    Padded tails get sentinel segments (q/kv pads differ), so padded rows
    come out fully masked.

    ``q_positions`` / ``kv_positions`` (both or neither) make the
    causal/window terms compare LOGICAL token positions instead of buffer
    indices — the per-segment q_offset of packed chunked prefill, where
    each segment's chunk queries at ``hist + r`` attend its gathered prefix
    at ``0..hist+C``. ``q_offset`` is ignored when positions are given, and
    padded rows take the ``masks.POS_PAD`` sentinel (causally unreachable,
    so bucket tails self-mask).

    ``block_q``/``block_k`` left ``None`` are resolved through
    ``kernels.tuning`` (analytic SRAM-budget chooser, or the empirical
    autotuner when enabled); explicit values pass through. Either way the
    blocks are then clamped to the sequence with ``tuning.round_block`` —
    rounding to a sublane multiple and padding the operands, never emitting
    an unaligned tile for tiny/ragged sequence lengths."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    q_seg, kv_seg = resolve_segment_ids(segment_ids, q_segment_ids,
                                        kv_segment_ids, sq, sk)
    if (q_positions is None) != (kv_positions is None):
        raise ValueError(
            "q_positions and kv_positions must be passed together")
    if q_positions is not None and not (causal or window is not None):
        # no geometric term consumes positions: they are inert — drop them
        # so the call takes the cheaper static-layout path.
        q_positions = kv_positions = None
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if q_offset is None:
        q_offset = sk - sq
    if interpret is None:
        interpret = default_interpret()
    if block_layout is not None and (block_q is None or block_k is None):
        # an Alg. 5 sparse pattern fixes the block grid: its shape IS the
        # tile decision, so auto-resolution must not fight it.
        nq_s, nk_s = np.asarray(block_layout).shape
        block_q = -(-sq // nq_s) if block_q is None else block_q
        block_k = -(-sk // nk_s) if block_k is None else block_k
    explicit_kvm = kv_major
    if block_q is None or block_k is None:
        tiles = tuning.resolve_tiles(
            block_q, block_k, sq=sq, sk=sk, head_dim=d, dtype=q.dtype,
            heads_q=hq, heads_kv=hkv, shards=shards,
            mask_class=tuning.mask_class_of(
                causal=causal, window=window,
                has_kv_mask=kv_mask is not None,
                has_segments=q_seg is not None,
                has_sparse=block_layout is not None,
                has_positions=q_positions is not None))
        block_q, block_k = tiles.block_q, tiles.block_k
        if kv_major is None:
            kv_major = tiles.kv_major
    block_q = tuning.round_block(block_q, sq)
    block_k = tuning.round_block(block_k, sk)

    # kv-major loop order (FA-2 work repartitioning): the whole query-head
    # GROUP rides one resident VMEM block while kv streams innermost — K/V
    # are read once per kv head instead of once per (q head, q block). Not
    # legal with dropout (the counter hash is per-(q,k) buffer coordinate)
    # or with an Alg. 5 sparse override (whose PARTIAL_DATA semantics the
    # column reduction cannot preserve) — the tuner's choice silently falls
    # back on such calls; an EXPLICIT ``kv_major=True`` raises instead.
    use_kvm = bool(kv_major)
    if use_kvm and (dropout_p > 0.0 or block_layout is not None):
        if explicit_kvm is True:
            raise ValueError(
                "kv_major=True is incompatible with dropout and sparse "
                "block layouts")
        use_kvm = False
    if use_kvm and (causal or window is not None) and q_positions is None:
        # the resident group flattens (rep, row) coordinates, so geometry
        # must be position-based: synthesize the identity positions the
        # q-major iota path would have derived.
        q_positions = jnp.broadcast_to(
            jnp.arange(sq, dtype=jnp.int32) + q_offset, (b, sq))
        kv_positions = jnp.broadcast_to(
            jnp.arange(sk, dtype=jnp.int32), (b, sk))

    qp, qpad = _pad_to(q, 2, block_q)
    kp, kpad = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    kvm = None
    if kv_mask is not None:
        kvm = jnp.pad(kv_mask, ((0, 0), (0, kpad)))
    if q_seg is not None:
        q_seg = jnp.pad(jnp.asarray(q_seg, jnp.int32), ((0, 0), (0, qpad)),
                        constant_values=SEG_PAD_Q)
        kv_seg = jnp.pad(jnp.asarray(kv_seg, jnp.int32), ((0, 0), (0, kpad)),
                         constant_values=SEG_PAD_KV)
    if q_positions is not None:
        # POS_PAD keys are causally unreachable from real queries, so the
        # kv padding tail self-masks (kv_valid_len is a buffer-index term
        # and cannot combine with logical positions).
        q_positions = jnp.pad(
            jnp.asarray(q_positions, jnp.int32), ((0, 0), (0, qpad)),
            constant_values=POS_PAD)
        kv_positions = jnp.pad(
            jnp.asarray(kv_positions, jnp.int32), ((0, 0), (0, kpad)),
            constant_values=POS_PAD)

    has_pos = q_positions is not None
    spec = MaskSpec(
        causal=causal, window=window,
        q_offset=0 if has_pos else q_offset,
        kv_valid_len=None if has_pos else (sk if kpad else None),
        kv_mask=kvm, q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        q_positions=q_positions, kv_positions=kv_positions,
        sparse_layout=block_layout)
    layout = compile_block_layout(spec, qp.shape[2], kp.shape[2],
                                  block_q, block_k).as_array()

    seed = jnp.asarray(dropout_seed, jnp.uint32)
    if use_kvm:
        # Re-layout the call for the transposed loop order: flatten each kv
        # head's query GROUP (n_rep reps x sq rows) into ONE resident block
        # (block_q = R, nq = 1) so the kv axis becomes the innermost — and
        # only — streaming axis. The per-q-block layout reduces to per-kv
        # COLUMN classes; positions/segment rows tile across the group so
        # the fused element mask stays exact. The merge order over kv
        # blocks is unchanged, so o/m/l (and hence the reused q-major
        # backward) agree with the q-major forward to accumulator order.
        sq_p, sk_p = qp.shape[2], kp.shape[2]
        n_rep = hq // hkv
        r_rows = n_rep * sq_p

        def _tile_rows(x):
            return None if x is None else jnp.tile(x, (1, n_rep))

        o = _flash_core(qp.reshape(b, hkv, r_rows, d), kp, vp, kvm,
                        _tile_rows(q_seg), kv_seg, _tile_rows(q_positions),
                        kv_positions, fa.kv_major_column_layout(layout),
                        seed, scale, causal, window, spec.q_offset,
                        spec.kv_valid_len, 0.0, r_rows, block_k, variant,
                        (r_rows, sk_p), interpret)
        return o.reshape(b, hq, sq_p, d)[:, :, :sq]
    o = _flash_core(qp, kp, vp, kvm, q_seg, kv_seg, q_positions,
                    kv_positions, layout, seed, scale,
                    causal, window, spec.q_offset, spec.kv_valid_len,
                    dropout_p, block_q, block_k, variant, (sq, sk), interpret)
    return o[:, :, :sq]


# ---------------------------------------------------------------------------
# paged prefill: differentiable in-place attention against the page pool
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14))
def _paged_core(q, k_pool, v_pool, page_list, q_seg, kv_seg, q_pos, kv_pos,
                block_layout, scale, causal, window, block_q, variant,
                interpret):
    o, _, _ = fa.flash_prefill_paged_forward(
        q, k_pool, v_pool, page_list, block_layout, scale=scale,
        causal=causal, window=window, q_segment_ids=q_seg,
        kv_segment_ids=kv_seg, q_positions=q_pos, kv_positions=kv_pos,
        block_q=block_q, variant=variant, interpret=interpret)
    return o


def _paged_core_fwd(q, k_pool, v_pool, page_list, q_seg, kv_seg, q_pos,
                    kv_pos, block_layout, scale, causal, window, block_q,
                    variant, interpret):
    o, m, l = fa.flash_prefill_paged_forward(
        q, k_pool, v_pool, page_list, block_layout, scale=scale,
        causal=causal, window=window, q_segment_ids=q_seg,
        kv_segment_ids=kv_seg, q_positions=q_pos, kv_positions=kv_pos,
        block_q=block_q, variant=variant, interpret=interpret)
    return o, (q, k_pool, v_pool, page_list, q_seg, kv_seg, q_pos, kv_pos,
               block_layout, o, m, l)


def _paged_core_bwd(scale, causal, window, block_q, variant, interpret,
                    res, do):
    (q, k_pool, v_pool, page_list, q_seg, kv_seg, q_pos, kv_pos,
     block_layout, o, m, l) = res
    dq, dk_pool, dv_pool = fa.flash_prefill_paged_backward(
        q, k_pool, v_pool, page_list, o, do, m, l, block_layout,
        scale=scale, causal=causal, window=window,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        q_positions=q_pos, kv_positions=kv_pos,
        block_q=block_q, interpret=interpret)

    def _zero_tangent(x):
        return None if x is None else np.zeros(x.shape, jax.dtypes.float0)

    return (dq, dk_pool, dv_pool, _zero_tangent(page_list),
            _zero_tangent(q_seg), _zero_tangent(kv_seg),
            _zero_tangent(q_pos), _zero_tangent(kv_pos),
            _zero_tangent(block_layout))


_paged_core.defvjp(_paged_core_fwd, _paged_core_bwd)


def flash_prefill_paged(
    q: jax.Array,             # (b, hq, sq, d)
    k_pool: jax.Array,        # (hkv, num_pages, page_size, d) shared pool
    v_pool: jax.Array,
    page_list: jax.Array,     # (b, T) int32; negative = dead slot (SKIP)
    *,
    q_positions: jax.Array,   # (b, sq) logical positions (DESIGN.md §10)
    kv_positions: jax.Array,  # (b, T*page_size); POS_PAD on dead rows
    q_segment_ids: jax.Array | None = None,   # (b, sq)
    kv_segment_ids: jax.Array | None = None,  # (b, T*page_size)
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,        # None = resolve via kernels.tuning
    variant: str = "fa2",
    kv_major: bool | None = None,      # None = loop order resolved via tuning
    interpret: bool | None = None,
    shards: int = 1,                   # tensor-parallel shard count of the
                                       # calling step (per-shard tuning key)
) -> jax.Array:
    """Differentiable FlashAttention over a PAGED kv prefix, read in place.

    The kv side is the page-aligned packed view of ``page_list``: logical
    row ``t*page_size + r`` is row ``r`` of physical page ``page_list[b, t]``
    — no gather ever materializes it. Causal/window masking compares the
    caller's LOGICAL positions (per-segment chunked prefill: chunk queries
    at ``hist + i`` against prefix keys at ``0..hist+C``), so positions are
    REQUIRED; dead kv rows (unallocated slots, alignment tails) must carry
    ``masks.POS_PAD`` (and ``SEG_PAD_KV`` when segment ids are used), which
    the layout compiler turns into SKIP pages the kernel never DMAs.
    Differentiable in (q, k_pool, v_pool); pool gradients come back
    pool-shaped with zeros on untouched pages."""
    b, hq, sq, d = q.shape
    hkv, num_pages, ps, _ = k_pool.shape
    if hq % hkv != 0:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    page_list = jnp.asarray(page_list, jnp.int32)
    if page_list.ndim != 2 or page_list.shape[0] != b:
        raise ValueError(f"page_list must be (batch, T), got "
                         f"{page_list.shape}")
    T = page_list.shape[1]
    sk = T * ps
    if kv_positions.shape != (b, sk):
        raise ValueError(
            f"kv_positions must be (batch, T*page_size)=({b}, {sk}), got "
            f"{kv_positions.shape}")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be passed together")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = default_interpret()
    has_seg = q_segment_ids is not None

    explicit_kvm = kv_major
    if block_q is None:
        tiles = tuning.resolve_tiles(
            block_q, ps, sq=sq, sk=sk, head_dim=d, dtype=q.dtype,
            heads_q=hq, heads_kv=hkv, shards=shards,
            mask_class=tuning.mask_class_of(
                causal=causal, window=window, has_kv_mask=False,
                has_segments=has_seg, has_sparse=False, has_positions=True))
        block_q = tiles.block_q
        if kv_major is None:
            kv_major = tiles.kv_major
    block_q = tuning.round_block(block_q, sq)
    use_kvm = bool(kv_major)

    qp, qpad = _pad_to(q, 2, block_q)
    q_positions = jnp.pad(jnp.asarray(q_positions, jnp.int32),
                          ((0, 0), (0, qpad)), constant_values=POS_PAD)
    kv_positions = jnp.asarray(kv_positions, jnp.int32)
    if has_seg:
        q_segment_ids = jnp.pad(jnp.asarray(q_segment_ids, jnp.int32),
                                ((0, 0), (0, qpad)),
                                constant_values=SEG_PAD_Q)
        kv_segment_ids = jnp.asarray(kv_segment_ids, jnp.int32)

    spec = MaskSpec(causal=causal, window=window, q_offset=0,
                    q_segment_ids=q_segment_ids,
                    kv_segment_ids=kv_segment_ids,
                    q_positions=q_positions, kv_positions=kv_positions)
    layout = compile_block_layout(spec, qp.shape[2], sk,
                                  block_q, ps).as_array()
    layout = paged_prefill_block_layout(layout, page_list)

    if use_kvm:
        # same resident-group re-layout as the contiguous kv-major path
        sq_p = qp.shape[2]
        n_rep = hq // hkv
        r_rows = n_rep * sq_p

        def _tile_rows(x):
            return None if x is None else jnp.tile(x, (1, n_rep))

        o = _paged_core(qp.reshape(b, hkv, r_rows, d), k_pool, v_pool,
                        page_list, _tile_rows(q_segment_ids), kv_segment_ids,
                        _tile_rows(q_positions), kv_positions,
                        fa.kv_major_column_layout(layout),
                        scale, causal, window, r_rows, variant, interpret)
        return o.reshape(b, hq, sq_p, d)[:, :, :sq]
    o = _paged_core(qp, k_pool, v_pool, page_list, q_segment_ids,
                    kv_segment_ids, q_positions, kv_positions, layout,
                    scale, causal, window, block_q, variant, interpret)
    return o[:, :, :sq]


# Convenience: reference entry points re-exported so benchmarks/tests import
# everything from ops.
standard_attention = ref_mod.standard_attention
chunked_attention = ref_mod.chunked_attention
