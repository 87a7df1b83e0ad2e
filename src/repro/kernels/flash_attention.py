"""FlashAttention forward + backward Pallas TPU kernels (paper Alg. 1/2/4).

TPU adaptation of the paper's CUDA kernel (see DESIGN.md §2/§3/§6):
  * grid = (batch, q_heads, num_q_blocks, num_kv_blocks) — the kv axis is the
    innermost (sequential on TPU), and the running softmax state (m, l, acc)
    lives in VMEM scratch that persists across kv steps. This is Algorithm 1
    with the loops exchanged; `variant="paper"` reproduces the exact
    per-block rescaling of Alg. 1 line 12, `variant="fa2"` keeps the
    accumulator unnormalized and divides once at the end (beyond-paper
    optimization, recorded separately in EXPERIMENTS.md §Perf).
  * Q/K/V tiles are staged HBM→VMEM by BlockSpecs; S/P tiles never leave
    VMEM — the IO behaviour the paper proves Θ(N²d²M⁻¹) about.
  * masks arrive COMPILED: every call carries a block layout lowered from a
    `core.masks.MaskSpec` (static (nq, nk) for trace-time masks, traced
    (b, nq, nk) when kv_mask / segment ids participate). The layout is the
    single source of block-run truth: SKIP tiles never run (pl.when — the
    TPU analogue of not launching the tile; Alg. 5's skip applied to causal/
    window geometry, kv padding tails, and cross-document tiles alike),
    FULL tiles run with NO element-level masking at all (not even the
    packed-segment compare — the compiler only emits FULL when every term
    is provably true or sparse-overridden), PARTIAL tiles apply the one
    fused element mask (`core.masks.element_mask`), and PARTIAL_DATA tiles
    apply only its validity/isolation terms. No geometric or segment
    predicate is re-derived per grid step in-kernel.
  * dropout uses a counter-based hash of the GLOBAL element coordinates
    (seed, b, h, q_pos, k_pos) — a pure function, so the backward pass
    regenerates the identical mask with zero HBM traffic. This replaces the
    paper's "save the Philox state ℛ" (Alg. 2 line 1) TPU-idiomatically.
  * GQA: kv BlockSpec index_map divides the head index by the group size, so
    grouped heads re-read the same kv tile from HBM (matches production TPU
    kernels; the tile is VMEM-resident across the group on real hardware).
  * backward = two kernels, as the paper's Alg. 4 + no-atomics constraint
    demands on TPU: a dq kernel (grid over q blocks, kv innermost) and a
    dkv kernel (grid over kv blocks, q innermost). Both recompute S and P
    from (q, k, m, l) tiles (the paper's recomputation trick), regenerate
    the dropout mask, and consume the SAME compiled layout as the forward
    (it rides the custom_vjp residuals in ops.py).

Validated in interpret mode against kernels/ref.py oracles (exact math,
fp32 accumulation) — see tests/test_kernels_flash.py; lowered for a TPU v5e
at real widths in tests/test_tpu_compile.py, and run on the chip against
the same oracles by chip_smoke.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import masks as M
from repro.core.io_model import LANES  # noqa: F401 — one source of truth:
# the tuner's working-set model (io_model.attention_working_set_bytes)
# accounts the lane-replicated m/l scratch with the SAME constant the
# kernels allocate it with; flash_decode re-imports it from here.
from repro.core.masks import NEG_INF


# ---------------------------------------------------------------------------
# shared in-kernel helpers
# ---------------------------------------------------------------------------

def _mix32(x):
    """murmur3 finalizer on uint32 (same math as ref.dropout_keep_mask)."""
    x = x.astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    x *= jnp.uint32(0x846CA68B)
    x ^= x >> 16
    return x


def _dropout_keep(seed, b, h, q0, k0, bq, bk, num_heads, q_len, k_len, p_drop):
    """(bq, bk) keep mask for the tile whose global origin is (q0, k0)."""
    q_pos = (q0 + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0))
    k_pos = (k0 + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1))
    idx = ((b.astype(jnp.uint32) * jnp.uint32(num_heads) + h.astype(jnp.uint32))
           * jnp.uint32(q_len) + q_pos)
    idx = idx * jnp.uint32(k_len) + k_pos
    r = _mix32(idx ^ _mix32(jnp.uint32(seed)))
    threshold = jnp.uint32(int(p_drop * float(2**32 - 1)))
    return r >= threshold


def _layout_at(lay_ref, layout_shape, b, qi, ki):
    """This tile's compiled layout class. The layout (static ``(nq, nk)``
    or traced ``(b, nq, nk)``) is scalar-prefetched into SMEM flattened,
    so one scalar load serves each grid step."""
    nq, nk = layout_shape[-2:]
    idx = qi * nk + ki
    if len(layout_shape) == 3:
        idx = b * (nq * nk) + idx
    return lay_ref[idx]


def _tile_mask(qi, ki, bq, bk, q_offset, *, causal, window, kv_valid_len,
               kvm_ref, qseg_ref, kseg_ref, qpos_ref=None, kpos_ref=None,
               geometry=True):
    """The fused element mask (core.masks.element_mask) for tile (qi, ki).

    ``geometry=False`` drops the causal/window terms (PARTIAL_DATA blocks:
    the compiler proved them all-true, or an Alg. 5 sparse layout overrides
    them); validity/isolation terms always apply. With ``qpos_ref`` /
    ``kpos_ref`` (traced logical positions, the per-segment-q_offset path)
    the causal/window compare reads the loaded position rows instead of the
    tile iotas (``kv_valid_len`` — a buffer-index term — is excluded by
    the MaskSpec). Returns None if no term is active. q-side rows arrive as
    ``(bq, 1)`` columns and kv-side rows as ``(1, bk)`` rows
    (``_mask_rows``), already in the orientation the mask broadcasts in.
    """
    if qpos_ref is not None:
        q_pos = qpos_ref[0]
        k_pos = kpos_ref[0, 0]
    else:
        q_pos = qi * bq + q_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return M.element_mask(
        q_pos, k_pos,
        causal=causal if geometry else False,
        window=window if geometry else None,
        kv_valid_len=kv_valid_len,
        kv_valid=kvm_ref[0, 0] != 0 if kvm_ref is not None else None,
        q_seg=qseg_ref[0] if qseg_ref is not None else None,
        kv_seg=kseg_ref[0, 0] if kseg_ref is not None else None)


def _layout_branches(blk, step, *, causal, window, kv_valid_len,
                     kvm_ref, qseg_ref):
    """Instantiate the per-class compute branches for one grid step.

    ``step(mode)`` runs the tile body with mode in {"none", "geo_data",
    "data"} controlling which element-mask terms apply. Exactly one branch
    executes per tile; SKIP tiles execute none (the block-level skip).
    Branches a call can never reach (e.g. PARTIAL_DATA without data terms)
    are not instantiated.
    """
    has_geo = causal or window is not None
    has_data = (kv_valid_len is not None or kvm_ref is not None
                or qseg_ref is not None)
    if not (has_geo or has_data):
        # maskless call (or a pure sparse pattern): any non-skip tile runs
        # unmasked — PARTIAL without active terms is element-wise FULL.
        pl.when(blk != M.BLOCK_SKIP)(lambda: step("none"))
        return
    pl.when(blk == M.BLOCK_PARTIAL)(lambda: step("geo_data"))
    pl.when(blk == M.BLOCK_FULL)(lambda: step("none"))
    if has_data:
        pl.when(blk == M.BLOCK_PARTIAL_DATA)(lambda: step("data"))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(lay_ref, seed_ref, q_ref, k_ref, v_ref, kvm_ref, qseg_ref,
                kseg_ref, qpos_ref, kpos_ref, o_ref, m_ref, l_ref,
                acc_sc, m_sc, l_sc, *, layout_shape,
                causal, window, q_offset, kv_valid_len, dropout_p,
                num_heads, q_len, k_len, variant):
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _step(mode):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        # scale is folded into q ONCE before the grid (FA-2 non-matmul
        # hoist) — no per-tile multiply on the S tile here.
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        q0 = qi * bq + q_offset
        k0 = ki * bk
        if mode != "none":
            ok = _tile_mask(qi, ki, bq, bk, q_offset, causal=causal,
                            window=window, kv_valid_len=kv_valid_len,
                            kvm_ref=kvm_ref, qseg_ref=qseg_ref,
                            kseg_ref=kseg_ref, qpos_ref=qpos_ref,
                            kpos_ref=kpos_ref, geometry=(mode == "geo_data"))
            if ok is not None:
                s = jnp.where(ok, s, NEG_INF)

        m_prev = m_sc[:, 0]
        l_prev = l_sc[:, 0]
        m_tile = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_tile)
        # NaN-free: masked elements / empty history handled with where-guards.
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, None]))
        correction = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * correction + jnp.sum(p, axis=-1)

        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref[0], b, h, q0 - q_offset, k0, bq, bk,
                                 num_heads, q_len, k_len, dropout_p)
            p_acc = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        else:
            p_acc = p
        pv = jax.lax.dot_general(p_acc, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        if variant == "paper":
            # Alg. 1 line 12: O_i <- diag(l_new)^-1 (diag(l_old) e^{...} O_i + e^{...} P~ V)
            l_safe = jnp.where(l_new == 0.0, 1.0, l_new)
            acc_sc[...] = (acc_sc[...] * (l_prev * correction)[:, None] + pv) / l_safe[:, None]
        else:  # fa2: unnormalized accumulator, single rescale by the max shift
            acc_sc[...] = acc_sc[...] * correction[:, None] + pv

        m_sc[...] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new[:, None], l_sc.shape)

    _layout_branches(_layout_at(lay_ref, layout_shape, b, qi, ki), _step,
                     causal=causal, window=window, kv_valid_len=kv_valid_len,
                     kvm_ref=kvm_ref, qseg_ref=qseg_ref)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_sc[:, 0]
        if variant == "paper":
            o = acc_sc[...]  # already normalized every step
        else:
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o = acc_sc[...] / l_safe[:, None]
        o_ref[0, 0] = o.astype(o_ref.dtype)
        # m/l leave lane-replicated, exactly as the scratch holds them
        m_ref[0, 0] = m_sc[...]
        l_ref[0, 0] = l_sc[...]


# ---------------------------------------------------------------------------
# pallas_call assembly shared by the contiguous and paged kernels
# ---------------------------------------------------------------------------
#
# Every call scalar-prefetches (layout, seed[, page table]) into SMEM: the
# layout is read one scalar per grid step, the seed only under dropout, and
# the page table by the kv index_maps. Vector operands keep the Mosaic
# tiling rule (last two block dims divisible by (8, 128) or equal to the
# array's): the per-row softmax statistics m/l/delta travel lane-replicated
# as (b, h, s, LANES), q-side mask rows as (b, s, 1) columns and kv-side
# mask rows as (b, nk, 1, block_k) rows.

def _spec(block_shape, index, q_inner=False):
    """BlockSpec over a (b, h, q-block, kv-block) grid. ``index`` always
    sees ``(b, h, qi, ki, *prefetch_refs)``; ``q_inner`` adapts it to the
    dkv kernels' (b, h, ki, qi) grid order."""
    if q_inner:
        return pl.BlockSpec(
            block_shape, lambda b, h, ki, qi, *pre: index(b, h, qi, ki, *pre))
    return pl.BlockSpec(block_shape, index)


def _kv_spec(block_k, d, n_rep, paged, q_inner=False):
    """K/V tile spec: a contiguous (b, hkv, sk, d) slice, or — ``paged`` —
    one pool page resolved through the scalar-prefetched page table."""
    if paged:
        return _spec((1, 1, block_k, d),
                     lambda b, h, qi, ki, lay, seed, tab:
                     (h // n_rep, tab[b, ki], 0, 0), q_inner)
    return _spec((1, 1, block_k, d),
                 lambda b, h, qi, ki, *_: (b, h // n_rep, ki, 0), q_inner)


def _q_row_spec(block_q, width, q_inner=False):
    """(1, 1, block_q, width) blocks of a (b, h, sq, width) array: q/o/do
    tiles (width d) and the lane-replicated m/l/delta rows (width LANES)."""
    return _spec((1, 1, block_q, width),
                 lambda b, h, qi, ki, *_: (b, h, qi, 0), q_inner)


def _mask_rows(b, block_q, block_k, q_inner=False, *, kv_mask=None,
               q_seg=None, kv_seg=None, q_pos=None, kv_pos=None):
    """(specs, operands) for the optional per-row mask inputs, in
    ``_split_opts`` order. q-side rows become (b, sq, 1) columns (a
    (block_q, 1) block per step) and kv-side rows (b, nk, 1, block_k) (a
    (1, block_k) row per step): legal tilings for any block size, the page
    size included, and no in-kernel transpose."""
    q_col = _spec((1, block_q, 1), lambda b, h, qi, ki, *_: (b, qi, 0),
                  q_inner)
    k_row = _spec((1, 1, 1, block_k), lambda b, h, qi, ki, *_: (b, ki, 0, 0),
                  q_inner)
    specs, args = [], []

    def add(spec, x, shape):
        specs.append(spec)
        args.append(jnp.asarray(x, jnp.int32).reshape(shape))

    if kv_mask is not None:
        add(k_row, kv_mask, (b, -1, 1, block_k))
    for q_side, kv_side in ((q_seg, kv_seg), (q_pos, kv_pos)):
        if q_side is not None:
            add(q_col, q_side, (b, -1, 1))
            add(k_row, kv_side, (b, -1, 1, block_k))
    return specs, args


def _split_opts(rest, has_kvm, has_seg, has_pos=False):
    """Route the optional (kvm, qseg, kseg, qpos, kpos) refs from a flat
    ref tuple."""
    n_opt = int(has_kvm) + 2 * int(has_seg) + 2 * int(has_pos)
    opts, rest = rest[:n_opt], rest[n_opt:]
    kvm_ref = opts[0] if has_kvm else None
    qseg_ref = opts[int(has_kvm)] if has_seg else None
    kseg_ref = opts[int(has_kvm) + 1] if has_seg else None
    base = int(has_kvm) + 2 * int(has_seg)
    qpos_ref = opts[base] if has_pos else None
    kpos_ref = opts[base + 1] if has_pos else None
    return kvm_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref, rest


def _flash_call(kernel, *, n_fixed, grid, block_layout, dropout_seed,
                table, in_specs, args, rows, out_specs, out_shape, scratch,
                has_kvm, has_seg, has_pos, interpret):
    """One fwd/dq/dkv pallas_call. ``kernel`` takes (lay_ref, seed_ref,
    *n_fixed refs, kvm, qseg, kseg, qpos, kpos, *outputs, *scratch), with
    None for the absent mask rows; ``table`` (b, T) is the page table of
    a paged call, else None."""
    prefetch = [jnp.asarray(block_layout, jnp.int32).reshape(-1),
                jnp.asarray(dropout_seed, jnp.uint32).reshape(1)]
    if table is not None:
        prefetch.append(table)
    n_pre = len(prefetch)

    def wrapped(*refs):
        lay_ref, seed_ref = refs[0], refs[1]
        refs = refs[n_pre:]
        fixed = refs[:n_fixed]
        kvm_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref, rest = _split_opts(
            refs[n_fixed:], has_kvm, has_seg, has_pos)
        return kernel(lay_ref, seed_ref, *fixed, kvm_ref, qseg_ref, kseg_ref,
                      qpos_ref, kpos_ref, *rest)

    return pl.pallas_call(
        wrapped,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, grid=grid,
            in_specs=in_specs + rows[0], out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, *args, *rows[1])


def _forward(q, k, v, block_layout, table, *, block_q, block_k, sk,
             kv_mask, q_segment_ids, kv_segment_ids, q_positions,
             kv_positions, dropout_seed, interpret, **static):
    """(o, m, l) of the forward kernel over a contiguous (``table`` None)
    or paged kv source; ``q`` arrives pre-scaled."""
    b, hq, sq, d = q.shape
    n_rep = hq // k.shape[0 if table is not None else 1]
    has_seg, has_pos = q_segment_ids is not None, q_positions is not None
    q_spec = _q_row_spec(block_q, d)
    stat = _q_row_spec(block_q, LANES)
    kv_spec = _kv_spec(block_k, d, n_rep, table is not None)
    return _flash_call(
        functools.partial(_fwd_kernel, layout_shape=block_layout.shape,
                          num_heads=hq, **static),
        n_fixed=3, grid=(b, hq, sq // block_q, sk // block_k),
        block_layout=block_layout, dropout_seed=dropout_seed, table=table,
        in_specs=[q_spec, kv_spec, kv_spec], args=[q, k, v],
        rows=_mask_rows(b, block_q, block_k, kv_mask=kv_mask,
                        q_seg=q_segment_ids, kv_seg=kv_segment_ids,
                        q_pos=q_positions, kv_pos=kv_positions),
        out_specs=[q_spec, stat, stat],
        out_shape=[jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, sq, LANES), jnp.float32)],
        scratch=[pltpu.VMEM((block_q, d), jnp.float32),
                 pltpu.VMEM((block_q, LANES), jnp.float32),
                 pltpu.VMEM((block_q, LANES), jnp.float32)],
        has_kvm=kv_mask is not None, has_seg=has_seg, has_pos=has_pos,
        interpret=interpret)


def flash_attention_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    kv_mask: jax.Array | None,
    block_layout: jax.Array,
    *,
    scale: float, causal: bool, window: int | None, q_offset: int,
    kv_valid_len: int | None = None,
    dropout_p: float, dropout_seed=0,
    block_q: int, block_k: int, variant: str = "fa2",
    dropout_dims: tuple[int, int] | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (o, m, l): o (b,hq,sq,d), m/l (b,hq,sq,LANES) lane-replicated
    row statistics. Shapes: q (b,hq,sq,d), k/v (b,hkv,sk,d),
    kv_mask (b, sk) or None. sq % block_q == 0 and sk % block_k == 0
    (ops.py pads). ``block_layout`` is the COMPILED layout from
    ``core.masks.compile_block_layout`` — (nq, nk) int32 static or
    (b, nq, nk) traced — and is the single source of block-run truth.
    ``kv_valid_len`` statically marks the kv padding tail (keys >= it are
    invalid); ``q/kv_segment_ids`` ((b, sq) / (b, sk) int32, both or
    neither) feed the PARTIAL-block element compare; ``q/kv_positions``
    ((b, sq) / (b, sk) int32, both or neither) make the causal/window
    compare position-based (per-segment q_offset; excludes kv_valid_len).
    dropout_seed may be a traced scalar (no retrace per step);
    dropout_dims = (orig_q_len, orig_k_len) keeps the counter-based
    dropout hash independent of padding."""
    sq, sk = q.shape[2], k.shape[2]
    if q_positions is not None and kv_valid_len is not None:
        raise ValueError("kv_valid_len cannot combine with q/kv_positions")
    dq_len, dk_len = dropout_dims if dropout_dims is not None else (sq, sk)
    # FA-2 hoist: one multiply at the XLA level, not per tile
    return _forward(
        q * scale, k, v, block_layout, None, block_q=block_q,
        block_k=block_k, sk=sk, kv_mask=kv_mask,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        q_positions=q_positions, kv_positions=kv_positions,
        dropout_seed=dropout_seed, interpret=interpret, causal=causal,
        window=window, q_offset=q_offset, kv_valid_len=kv_valid_len,
        dropout_p=dropout_p, q_len=dq_len, k_len=dk_len, variant=variant)


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks, kv innermost)
# ---------------------------------------------------------------------------

def _recompute_p(q, k, m_row, l_row, ok):
    """Recompute P tile = diag(l)^-1 exp(S - m) (Alg. 4 line 13) from the
    PRE-SCALED q (scale is folded into q by the wrappers, matching the
    forward — no per-tile multiply). ``ok`` is the tile's fused element
    mask (None on FULL blocks — no masking)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    m_safe = jnp.where(l_row == 0.0, 0.0, m_row)
    l_safe = jnp.where(l_row == 0.0, 1.0, l_row)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_safe[:, None])) / l_safe[:, None]
    return p


def _dq_kernel(lay_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
               dd_ref, kvm_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
               dq_ref, dq_sc, *, layout_shape,
               scale, causal, window, q_offset, kv_valid_len, dropout_p,
               num_heads, q_len, k_len):
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _step(mode):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        # lane-replicated row statistics: column 0 carries the value
        m_row, l_row = m_ref[0, 0][:, 0], l_ref[0, 0][:, 0]
        dd = dd_ref[0, 0][:, 0]
        ok = None
        if mode != "none":
            ok = _tile_mask(qi, ki, bq, bk, q_offset, causal=causal,
                            window=window, kv_valid_len=kv_valid_len,
                            kvm_ref=kvm_ref, qseg_ref=qseg_ref,
                            kseg_ref=kseg_ref, qpos_ref=qpos_ref,
                            kpos_ref=kpos_ref, geometry=(mode == "geo_data"))
        p = _recompute_p(q, k, m_row, l_row, ok)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref[0], b, h, qi * bq, ki * bk, bq, bk,
                                 num_heads, q_len, k_len, dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = p * (dp - dd[:, None])
        dq_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _layout_branches(_layout_at(lay_ref, layout_shape, b, qi, ki), _step,
                     causal=causal, window=window, kv_valid_len=kv_valid_len,
                     kvm_ref=kvm_ref, qseg_ref=qseg_ref)

    @pl.when(ki == nk - 1)
    def _finalize():
        # chain rule for the folded scale: the kernel consumed q' = scale·q,
        # so dq = scale · dq' — ONE multiply at finalize, not per kv step.
        dq_ref[0, 0] = (scale * dq_sc[...]).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dkv kernel (grid over kv blocks, q innermost)
# ---------------------------------------------------------------------------

def _dkv_kernel(lay_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                dd_ref, kvm_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, layout_shape,
                causal, window, q_offset, kv_valid_len, dropout_p,
                num_heads, q_len, k_len):
    b, h = pl.program_id(0), pl.program_id(1)
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _step(mode):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        # lane-replicated row statistics: column 0 carries the value
        m_row, l_row = m_ref[0, 0][:, 0], l_ref[0, 0][:, 0]
        dd = dd_ref[0, 0][:, 0]
        ok = None
        if mode != "none":
            ok = _tile_mask(qi, ki, bq, bk, q_offset, causal=causal,
                            window=window, kv_valid_len=kv_valid_len,
                            kvm_ref=kvm_ref, qseg_ref=qseg_ref,
                            kseg_ref=kseg_ref, qpos_ref=qpos_ref,
                            kpos_ref=kpos_ref, geometry=(mode == "geo_data"))
        p = _recompute_p(q, k, m_row, l_row, ok)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref[0], b, h, qi * bq, ki * bk, bq, bk,
                                 num_heads, q_len, k_len, dropout_p)
            z = jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
            p_dropped = p * z
        else:
            z = None
            p_dropped = p
        # dV += P_dropped^T dO   (Alg. 4 line 16)
        dv_sc[...] += jax.lax.dot_general(
            p_dropped, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        # dP = (dO V^T) ∘ Z ; dS = P ∘ (dP - D) ; dK += scale * dS^T Q
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if z is not None:
            dp = dp * z
        # q arrives PRE-SCALED (q' = scale·q), so dS^T q' == scale·dS^T q —
        # the Alg. 4 line-18 scale is already inside the operand.
        ds = p * (dp - dd[:, None])
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _layout_branches(_layout_at(lay_ref, layout_shape, b, qi, ki), _step,
                     causal=causal, window=window, kv_valid_len=kv_valid_len,
                     kvm_ref=kvm_ref, qseg_ref=qseg_ref)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, do, m, l, block_layout, table, *, scale, block_q,
              block_k, sk, kv_mask, q_segment_ids, kv_segment_ids,
              q_positions, kv_positions, dropout_seed, interpret, **static):
    """dq and the PER-Q-HEAD (dk, dv) — (b, hq, sk, d) float32, sk being
    the packed page-aligned length for a paged call — from the dq and dkv
    kernels over a contiguous (``table`` None) or paged kv source."""
    b, hq, sq, d = q.shape
    n_rep = hq // k.shape[0 if table is not None else 1]
    nq, nk = sq // block_q, sk // block_k
    paged = table is not None
    flags = dict(has_kvm=kv_mask is not None,
                 has_seg=q_segment_ids is not None,
                 has_pos=q_positions is not None)
    rows = dict(kv_mask=kv_mask, q_seg=q_segment_ids, kv_seg=kv_segment_ids,
                q_pos=q_positions, kv_pos=kv_positions)

    # D_i = rowsum(dO ∘ O) (paper Eq. 4 / Alg. 4 line 19). O(Nd) IO, done at
    # the XLA level (fuses with surrounding ops); lane-replicated like m/l.
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dd = jnp.broadcast_to(dd[..., None], (b, hq, sq, LANES))

    # Same folded scale as the forward: both kernels recompute P from the
    # pre-scaled q; the dq kernel applies the chain-rule scale at finalize
    # and the dkv kernel needs none (dK = dS^T q' is already scaled).
    q = q * scale
    args = [q, k, v, do, m, l, dd]
    common = dict(block_layout=block_layout, dropout_seed=dropout_seed,
                  table=table, args=args, interpret=interpret, **flags)
    kernel_static = dict(layout_shape=block_layout.shape, num_heads=hq,
                         **static)

    def in_specs(q_inner):
        q_spec = _q_row_spec(block_q, d, q_inner)
        stat = _q_row_spec(block_q, LANES, q_inner)
        kv_spec = _kv_spec(block_k, d, n_rep, paged, q_inner)
        return [q_spec, kv_spec, kv_spec, q_spec, stat, stat, stat]

    # ---- dq kernel: grid over q blocks, kv innermost ----
    dq = _flash_call(
        functools.partial(_dq_kernel, scale=scale, **kernel_static),
        n_fixed=7, grid=(b, hq, nq, nk), in_specs=in_specs(False),
        rows=_mask_rows(b, block_q, block_k, **rows),
        out_specs=_q_row_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch=[pltpu.VMEM((block_q, d), jnp.float32)], **common)

    # ---- dkv kernel: grid over kv blocks, q innermost ----
    kv_out = _spec((1, 1, block_k, d),
                   lambda b, h, qi, ki, *_: (b, h, ki, 0), q_inner=True)
    dk_p, dv_p = _flash_call(
        functools.partial(_dkv_kernel, **kernel_static),
        n_fixed=7, grid=(b, hq, nk, nq), in_specs=in_specs(True),
        rows=_mask_rows(b, block_q, block_k, q_inner=True, **rows),
        out_specs=[kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32)],
        scratch=[pltpu.VMEM((block_k, d), jnp.float32),
                 pltpu.VMEM((block_k, d), jnp.float32)], **common)
    return dq, dk_p, dv_p


def flash_attention_backward(
    q, k, v, o, do, m, l, kv_mask, block_layout,
    *,
    scale, causal, window, q_offset, kv_valid_len=None,
    dropout_p, dropout_seed,
    block_q, block_k, dropout_dims: tuple[int, int] | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    interpret: bool,
):
    """Returns (dq, dk, dv) with dk/dv already group-summed for GQA.
    ``block_layout`` is the same compiled layout the forward ran with."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    n_rep = hq // hkv
    dq_len, dk_len = dropout_dims if dropout_dims is not None else (sq, sk)
    dq, dk_p, dv_p = _backward(
        q, k, v, o, do, m, l, block_layout, None, scale=scale,
        block_q=block_q, block_k=block_k, sk=sk, kv_mask=kv_mask,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        q_positions=q_positions, kv_positions=kv_positions,
        dropout_seed=dropout_seed, interpret=interpret, causal=causal,
        window=window, q_offset=q_offset, kv_valid_len=kv_valid_len,
        dropout_p=dropout_p, q_len=dq_len, k_len=dk_len)

    if n_rep > 1:  # GQA: sum gradients over the query-head group
        dk = dk_p.reshape(b, hkv, n_rep, sk, d).sum(axis=2)
        dv = dv_p.reshape(b, hkv, n_rep, sk, d).sum(axis=2)
    else:
        dk = dk_p
        dv = dv_p
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# kv-major loop order: the resident-q transposed grid (FA-2 repartitioning)
# ---------------------------------------------------------------------------

def kv_major_column_layout(block_layout):
    """Reduce a ``(.., nq, nk)`` block layout over the q-block axis to the
    per-kv-COLUMN classes the resident-q kv-major grid consumes.

    The kv-major forward keeps the entire (grouped) query block in VMEM and
    walks kv blocks in the innermost grid axis, so each grid step sees one
    kv column spanning every q row at once. A column is SKIP only if every
    q block skipped it (no row attends → never DMA'd), FULL only if every
    q block was FULL (no element term can fire anywhere in the column), and
    PARTIAL otherwise — the fused element mask re-establishes exactness on
    the mixed columns. PARTIAL_DATA folds into PARTIAL: the kv-major path
    is only dispatched without a sparse override, so its geometry terms are
    provably true wherever the compiler had relaxed them.
    """
    skip = block_layout == M.BLOCK_SKIP
    full = block_layout == M.BLOCK_FULL
    col = jnp.where(jnp.all(skip, axis=-2), M.BLOCK_SKIP,
                    jnp.where(jnp.all(full, axis=-2), M.BLOCK_FULL,
                              M.BLOCK_PARTIAL)).astype(jnp.int32)
    return col[None, :] if block_layout.ndim == 2 else col[:, None, :]


# ---------------------------------------------------------------------------
# paged prefill: attend the paged KV prefix IN PLACE (no gather)
# ---------------------------------------------------------------------------
#
# The kv BlockSpec index_map resolves the physical page from a
# scalar-prefetched page list — `tab[b, ki]` — so each grid step DMAs
# exactly ONE pool page, and SKIP columns (unallocated slots, pages wholly
# behind the causal frontier of every query row) are never read at all.
# Masking is position-based (DESIGN.md §10): the serving layer provides
# per-row logical positions/segment ids for the page-aligned packed kv
# view, with POS_PAD/SEG_PAD sentinels on dead rows, so causal masking
# against the paged prefix is exact without any q_offset arithmetic.

def flash_prefill_paged_forward(
    q: jax.Array,             # (b, hq, sq, d) — sq % block_q == 0
    k_pool: jax.Array,        # (hkv, num_pages, page_size, d) shared pool
    v_pool: jax.Array,
    page_list: jax.Array,     # (b, T) int32 physical pages; negative = dead
    block_layout: jax.Array,  # (b, nq, T) compiled classes (paged-aware)
    *,
    scale: float, causal: bool, window: int | None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    block_q: int, variant: str = "fa2",
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (o, m, l) — the same residuals as the contiguous forward,
    computed directly against pool pages. Reuses ``_fwd_kernel`` verbatim:
    only the kv BlockSpecs change (page indirection instead of a
    contiguous slice), which is the whole point — the loop body, the
    online-softmax state, and the layout-branch dispatch are untouched."""
    ps = k_pool.shape[2]
    sk = page_list.shape[1] * ps
    table = jnp.maximum(page_list, 0).astype(jnp.int32)
    # folded scale, as in the contiguous forward; serving path: no dropout
    return _forward(
        q * scale, k_pool, v_pool, block_layout, table, block_q=block_q,
        block_k=ps, sk=sk, kv_mask=None, q_segment_ids=q_segment_ids,
        kv_segment_ids=kv_segment_ids, q_positions=q_positions,
        kv_positions=kv_positions, dropout_seed=0, interpret=interpret,
        causal=causal, window=window, q_offset=0, kv_valid_len=None,
        dropout_p=0.0, q_len=q.shape[2], k_len=sk, variant=variant)


def flash_prefill_paged_backward(
    q, k_pool, v_pool, page_list, o, do, m, l, block_layout,
    *,
    scale: float, causal: bool, window: int | None,
    q_segment_ids=None, kv_segment_ids=None,
    q_positions=None, kv_positions=None,
    block_q: int, interpret: bool,
):
    """dq/dkv pair for the paged prefill (trainable use). The dq kernel
    reads pool pages through the same scalar-prefetched indirection as the
    forward; the dkv kernel cannot scatter through BlockSpecs without
    atomics, so it emits gradients in the PACKED page-aligned layout
    (grid (b, hq, T, nq), out block = one page worth of rows), which one
    XLA scatter-add folds back into pool coordinates — dead slots
    (negative pages) are dropped."""
    b, hq, sq, d = q.shape
    hkv, num_pages, ps, _ = k_pool.shape
    n_rep = hq // hkv
    T = page_list.shape[1]
    table = jnp.maximum(page_list, 0).astype(jnp.int32)
    dq, dk_pk, dv_pk = _backward(
        q, k_pool, v_pool, o, do, m, l, block_layout, table, scale=scale,
        block_q=block_q, block_k=ps, sk=T * ps, kv_mask=None,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        q_positions=q_positions, kv_positions=kv_positions, dropout_seed=0,
        interpret=interpret, causal=causal, window=window, q_offset=0,
        kv_valid_len=None, dropout_p=0.0, q_len=sq, k_len=T * ps)

    if n_rep > 1:  # GQA group-sum in the packed layout
        dk_pk = dk_pk.reshape(b, hkv, n_rep, T * ps, d).sum(axis=2)
        dv_pk = dv_pk.reshape(b, hkv, n_rep, T * ps, d).sum(axis=2)
    # packed -> pool: one scatter-add; dead slots route to page index
    # num_pages, dropped. Duplicate pages across batch rows accumulate.
    pages = jnp.where(page_list >= 0, page_list,
                      num_pages).astype(jnp.int32)             # (b, T)
    src_k = dk_pk.reshape(b, hkv, T, ps, d).transpose(1, 0, 2, 3, 4)
    src_v = dv_pk.reshape(b, hkv, T, ps, d).transpose(1, 0, 2, 3, 4)
    dk_pool = jnp.zeros(k_pool.shape, jnp.float32).at[:, pages].add(
        src_k, mode="drop")
    dv_pool = jnp.zeros(v_pool.shape, jnp.float32).at[:, pages].add(
        src_v, mode="drop")
    return dq, dk_pool.astype(k_pool.dtype), dv_pool.astype(v_pool.dtype)
