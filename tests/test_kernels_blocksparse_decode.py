"""Block-sparse FlashAttention (Alg. 5) + split-KV decode kernel tests
(contiguous and paged cache geometries)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks as M
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.ops import flash_attention
from repro.kernels.ref import standard_attention

TOL = dict(rtol=2e-3, atol=2e-5)


def _qkv(seed, b, hq, hkv, sq, sk, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, sq, d)),
            jax.random.normal(ks[1], (b, hkv, sk, d)),
            jax.random.normal(ks[2], (b, hkv, sk, d)))


# ---------------------------------------------------------------------------
# block-sparse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("builder,causal", [
    (M.butterfly_block_layout, False),
    (lambda *a: M.butterfly_block_layout(*a, causal=True), True),
    (M.causal_block_layout, True),
])
def test_blocksparse_fwd(builder, causal):
    s, bq, bk = 512, 128, 128
    q, k, v = _qkv(0, 2, 2, 2, s, s, 32)
    layout = builder(s, s, bq, bk)
    o = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                        block_layout=layout)
    base = M.causal_mask(s, s) if causal else None
    emask = M.layout_to_element_mask(layout, bq, bk, s, s, base_mask=base)
    o_ref = standard_attention(q, k, v, mask=emask)
    np.testing.assert_allclose(o, o_ref, **TOL)


def test_blocksparse_grads():
    s, bq, bk = 256, 64, 64
    q, k, v = _qkv(1, 1, 2, 2, s, s, 32)
    layout = M.butterfly_block_layout(s, s, bq, bk, causal=True)
    emask = M.layout_to_element_mask(layout, bq, bk, s, s,
                                     base_mask=M.causal_mask(s, s))
    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk,
        block_layout=layout) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (standard_attention(
        q, k, v, mask=emask) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a / scale, b / scale, rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name}")


def test_sliding_window_layout_density():
    """Prop. 4 structure: window layout density ~ window/(seq) for seq >> w."""
    s, w, b = 4096, 256, 128
    layout = M.sliding_window_block_layout(s, s, b, b, w)
    dens = M.layout_density(layout)
    assert dens < 0.15, dens
    full = M.causal_block_layout(s, s, b, b)
    assert dens < M.layout_density(full)


def test_blocksparse_skips_zero_blocks_output():
    """Rows whose layout row is all-skip produce zeros, not NaNs."""
    s, bq = 256, 64
    q, k, v = _qkv(2, 1, 1, 1, s, s, 16)
    layout = np.zeros((4, 4), np.uint8)
    layout[0, 0] = 1  # only the first block attends
    o = flash_attention(q, k, v, block_q=bq, block_k=bq, block_layout=layout)
    assert not bool(jnp.any(jnp.isnan(o)))
    np.testing.assert_allclose(o[:, :, bq:], 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# split-KV decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("splits,block_k", [(1, 128), (4, 64), (8, 128)])
def test_decode_matches_standard(splits, block_k):
    b, hq, hkv, cap, d = 3, 4, 2, 512, 64
    q, k, v = _qkv(3, b, hq, hkv, 1, cap, d)
    kv_len = jnp.array([100, 512, 257], jnp.int32)
    o = flash_decode(q, k, v, kv_len, num_splits=splits, block_k=block_k)
    kvm = jnp.arange(cap)[None, :] < kv_len[:, None]
    o_ref = standard_attention(q, k, v, kv_mask=kvm)
    np.testing.assert_allclose(o, o_ref, **TOL)


def test_decode_empty_splits_no_nan():
    """kv_len much shorter than capacity: trailing splits fully masked."""
    b, h, cap, d = 2, 2, 1024, 32
    q, k, v = _qkv(4, b, h, h, 1, cap, d)
    kv_len = jnp.array([3, 65], jnp.int32)
    o = flash_decode(q, k, v, kv_len, num_splits=8, block_k=128)
    assert not bool(jnp.any(jnp.isnan(o)))
    kvm = jnp.arange(cap)[None, :] < kv_len[:, None]
    np.testing.assert_allclose(o, standard_attention(q, k, v, kv_mask=kvm),
                               **TOL)


def test_decode_gqa():
    b, hq, hkv, cap, d = 2, 8, 2, 256, 32
    q, k, v = _qkv(5, b, hq, hkv, 1, cap, d)
    kv_len = jnp.array([256, 128], jnp.int32)
    o = flash_decode(q, k, v, kv_len, num_splits=4, block_k=64)
    kvm = jnp.arange(cap)[None, :] < kv_len[:, None]
    np.testing.assert_allclose(o, standard_attention(q, k, v, kv_mask=kvm),
                               **TOL)


@pytest.mark.parametrize("window,splits,block_k", [
    (32, 4, 64), (100, 8, 128), (1000, 4, 64),  # window > kv_len -> full
])
def test_decode_sliding_window_matches_xla_path(window, splits, block_k):
    """flash_decode(window=w) == the XLA decode path's sliding-window
    semantics: only the last w valid cache positions are attended."""
    from repro.core.attention import AttentionSpec, decode_attention
    b, hq, hkv, cap, d = 3, 4, 2, 512, 32
    q, k, v = _qkv(6, b, hq, hkv, 1, cap, d)
    kv_len = jnp.array([100, 512, 257], jnp.int32)
    o = flash_decode(q, k, v, kv_len, num_splits=splits, block_k=block_k,
                     window=window)
    spec_xla = AttentionSpec(window=window, use_decode_kernel=False)
    o_xla = decode_attention(q, k, v, kv_len, spec_xla)
    np.testing.assert_allclose(o, o_xla, **TOL)
    # dispatch routes the kernel the same way
    spec_kern = AttentionSpec(window=window, use_decode_kernel=True,
                              num_decode_splits=splits, block_k=block_k)
    o_disp = decode_attention(q, k, v, kv_len, spec_kern)
    np.testing.assert_allclose(o_disp, o_xla, **TOL)


def test_decode_kv_mask_matches_standard():
    """Per-slot cache masks (mask IR: kv_mask folds into the decode block
    layout) agree with the oracle and with the XLA decode path."""
    from repro.core.attention import AttentionSpec, decode_attention
    b, hq, hkv, cap, d = 2, 4, 2, 256, 32
    q, k, v = _qkv(8, b, hq, hkv, 1, cap, d)
    kv_len = jnp.array([200, 256], jnp.int32)
    kvm = jax.random.bernoulli(jax.random.PRNGKey(3), 0.8, (b, cap))
    o = flash_decode(q, k, v, kv_len, num_splits=4, block_k=64, kv_mask=kvm)
    full = kvm & (jnp.arange(cap)[None, :] < kv_len[:, None])
    o_ref = standard_attention(q, k, v, kv_mask=full)
    np.testing.assert_allclose(o, o_ref, **TOL)
    spec = AttentionSpec(use_decode_kernel=False)
    o_xla = decode_attention(q, k, v, kv_len, spec, kv_mask=kvm)
    np.testing.assert_allclose(o, o_xla, **TOL)


def test_decode_capacity_validation():
    """Misaligned cache geometry raises up front instead of silently
    padding (which changed the grid and HBM traffic behind the caller)."""
    q, k, v = _qkv(9, 1, 2, 2, 1, 384, 32)
    kv_len = jnp.array([100], jnp.int32)
    with pytest.raises(ValueError, match="multiple of block_k"):
        flash_decode(q, k, v, kv_len, block_k=256, num_splits=1)
    with pytest.raises(ValueError, match="num_splits"):
        flash_decode(q, k, v, kv_len, block_k=128, num_splits=2)  # 3 blocks
    # shape-derived clamps still apply: block bigger than the cache and
    # more splits than blocks are deterministic no-ops, not errors.
    q2, k2, v2 = _qkv(9, 1, 2, 2, 1, 64, 32)
    o = flash_decode(q2, k2, v2, jnp.array([64], jnp.int32),
                     block_k=128, num_splits=8)
    np.testing.assert_allclose(o, standard_attention(q2, k2, v2), **TOL)


# ---------------------------------------------------------------------------
# paged split-KV decode (page-table indirection)
# ---------------------------------------------------------------------------

def _paged_case(seed, b, hq, hkv, d, ps, T, num_pages, kv_len):
    """Random pool + per-sequence tables whose allocated pages are
    deliberately scattered (and interleaved across sequences)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d))
    k_pool = jax.random.normal(ks[1], (hkv, num_pages, ps, d))
    v_pool = jax.random.normal(ks[2], (hkv, num_pages, ps, d))
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_pages)
    table = np.full((b, T), -1, np.int32)
    used = 0
    for i, n in enumerate(-(-np.asarray(kv_len) // ps)):
        table[i, :n] = perm[used:used + n]
        used += n
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(kv_len, jnp.int32)


def _paged_oracle(q, k_pool, v_pool, table, kv_len, window=None):
    """Gather the logical view with numpy indexing and run the standard
    oracle over the shared validity band."""
    hkv, num_pages, ps, d = k_pool.shape
    b, T = table.shape
    safe = np.clip(np.asarray(table), 0, num_pages - 1)

    def gather(pool):
        return jnp.transpose(pool[:, safe], (1, 0, 2, 3, 4)).reshape(
            b, hkv, T * ps, d)

    kvm = M.decode_kv_valid(kv_len, T * ps, window=window)
    o = standard_attention(q, gather(k_pool), gather(v_pool), kv_mask=kvm)
    return jnp.where((kv_len == 0)[:, None, None, None], 0.0, o)


@pytest.mark.parametrize("splits,window", [(1, None), (3, None), (6, 20)])
def test_paged_decode_matches_oracle(splits, window):
    b, hq, hkv, d, ps, T, P = 3, 4, 2, 32, 8, 6, 24
    kv_len = [13, 48, 0]
    q, kp, vp, table, kvl = _paged_case(0, b, hq, hkv, d, ps, T, P, kv_len)
    o = flash_decode_paged(q, kp, vp, table, kvl, num_splits=splits,
                           window=window)
    np.testing.assert_allclose(o, _paged_oracle(q, kp, vp, table, kvl,
                                                window=window), **TOL)


def test_paged_decode_xla_parity_and_dispatch():
    """Kernel and XLA gather paths agree through paged_decode_attention."""
    from repro.core.attention import AttentionSpec, paged_decode_attention
    b, hq, hkv, d, ps, T, P = 2, 4, 2, 16, 8, 4, 16
    q, kp, vp, table, kvl = _paged_case(1, b, hq, hkv, d, ps, T, P, [19, 32])
    # the entry point reads the lane-dense (L, P, ps, hkv*d) pool
    kl, vl = (x.transpose(1, 2, 0, 3).reshape(1, P, ps, hkv * d)
              for x in (kp, vp))
    o_xla = paged_decode_attention(q, kl, vl, 0, table, kvl,
                                   AttentionSpec(use_decode_kernel=False))
    o_ker = paged_decode_attention(
        q, kl, vl, 0, table, kvl,
        AttentionSpec(use_decode_kernel=True, num_decode_splits=2))
    np.testing.assert_allclose(o_ker, o_xla, **TOL)
    np.testing.assert_allclose(o_xla, _paged_oracle(q, kp, vp, table, kvl),
                               **TOL)


def test_paged_decode_gqa_matches_contiguous():
    """Chopping a contiguous cache into (permuted) pages changes nothing."""
    b, hq, hkv, cap, d, ps = 2, 8, 2, 256, 32, 32
    q, k, v = _qkv(5, b, hq, hkv, 1, cap, d)
    kv_len = jnp.array([256, 128], jnp.int32)
    o_contig = flash_decode(q, k, v, kv_len, num_splits=4, block_k=64)

    T = cap // ps
    rng = np.random.default_rng(5)
    perm = rng.permutation(b * T)
    pool_k = np.zeros((hkv, b * T, ps, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    table = np.zeros((b, T), np.int32)
    for i in range(b):
        for t in range(T):
            pg = int(perm[i * T + t])
            pool_k[:, pg] = np.asarray(k)[i, :, t * ps:(t + 1) * ps]
            pool_v[:, pg] = np.asarray(v)[i, :, t * ps:(t + 1) * ps]
            table[i, t] = pg
    o_paged = flash_decode_paged(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                                 jnp.asarray(table), kv_len, num_splits=4)
    np.testing.assert_allclose(o_paged, o_contig, **TOL)


def test_paged_skip_pages_provably_never_read():
    """NaN-poison every page NOT named by an (allocated, valid) table entry:
    the kernel must still produce the exact oracle answer — SKIP and
    unallocated pages are never touched by the compute."""
    b, hq, hkv, d, ps, T, P = 2, 2, 2, 16, 8, 4, 16
    q, kp, vp, table, kvl = _paged_case(2, b, hq, hkv, d, ps, T, P, [11, 26])
    ref = _paged_oracle(q, kp, vp, table, kvl)
    live = {int(p) for row, n in zip(np.asarray(table),
                                     -(-np.asarray(kvl) // ps))
            for p in row[:n]}
    dead = jnp.asarray([p for p in range(P) if p not in live])
    kp = kp.at[:, dead].set(jnp.nan)
    vp = vp.at[:, dead].set(jnp.nan)
    o = flash_decode_paged(q, kp, vp, table, kvl, num_splits=2)
    assert not bool(jnp.any(jnp.isnan(o)))
    np.testing.assert_allclose(o, ref, **TOL)


def test_paged_num_splits_validation():
    b, hq, hkv, d, ps, T, P = 1, 2, 2, 16, 8, 6, 8
    q, kp, vp, table, kvl = _paged_case(3, b, hq, hkv, d, ps, T, P, [20])
    with pytest.raises(ValueError, match="num_splits"):
        flash_decode_paged(q, kp, vp, table, kvl, num_splits=4)  # 6 % 4


def test_decode_window_masks_old_positions():
    """With a tiny window the answer must differ from full attention and
    equal attention over only the window slice."""
    b, h, cap, d = 1, 2, 256, 16
    q, k, v = _qkv(7, b, h, h, 1, cap, d)
    kv_len = jnp.array([200], jnp.int32)
    w = 16
    o = flash_decode(q, k, v, kv_len, num_splits=4, block_k=32, window=w)
    o_full = flash_decode(q, k, v, kv_len, num_splits=4, block_k=32)
    assert float(jnp.max(jnp.abs(o - o_full))) > 1e-4
    o_ref = standard_attention(q[:, :, :], k[:, :, 184:200], v[:, :, 184:200])
    np.testing.assert_allclose(o, o_ref, **TOL)
