"""Config-invariance: NO tuner decision may change numerics.

The tuning subsystem (kernels/tuning.py) makes tile sizes a resolved,
shape-dependent choice — so this suite proves the choice is observationally
pure: forward outputs AND gradients agree across every valid
``(block_q, block_k)`` pair and decode ``(block_k, num_splits)`` geometry,
including the packed-segment and paged-decode paths, up to fp32
accumulator-order effects (the online-softmax merge reassociates sums)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import (AttentionSpec, decode_attention,
                                  paged_decode_attention,
                                  paged_prefill_attention)
from repro.core import masks
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.ops import flash_attention
from repro.serve import kv_cache as kvc

# accumulator-order tolerance only: measured max deviation across block
# configs is ~1e-6 on O(1) values; anything past 1e-4 is a real bug.
INV = dict(rtol=1e-4, atol=1e-5)


def _lane_dense(pool):
    """A head-major (hkv, P, ps, d) kernel pool as the one-layer lane-dense
    (1, P, ps, hkv*d) pool the paged attention entry points read."""
    hkv, num_pages, ps, d = pool.shape
    return pool.transpose(1, 2, 0, 3).reshape(1, num_pages, ps, hkv * d)

BLOCKS = [(64, 64), (32, 128), (128, 32), (128, 128), (256, 256),
          (64, 256), (None, None)]


def _qkv(seed, b, hq, hkv, s, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, s, d)),
            jax.random.normal(ks[1], (b, hkv, s, d)),
            jax.random.normal(ks[2], (b, hkv, s, d)))


def _fwd_and_grads(fn, q, k, v):
    o = fn(q, k, v)
    gq, gk, gv = jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2))(q, k, v)
    return o, gq, gk, gv


class TestTrainingTileInvariance:
    @pytest.mark.parametrize("bq,bk", BLOCKS)
    def test_causal_fwd_and_grads(self, bq, bk):
        q, k, v = _qkv(0, 2, 4, 2, 256, 32)
        fn = functools.partial(flash_attention, causal=True,
                               block_q=bq, block_k=bk)
        ref = functools.partial(flash_attention, causal=True,
                                block_q=128, block_k=128)
        for got, want in zip(_fwd_and_grads(fn, q, k, v),
                             _fwd_and_grads(ref, q, k, v)):
            np.testing.assert_allclose(got, want, **INV)

    @pytest.mark.parametrize("bq,bk", [(32, 64), (128, 128), (None, None)])
    def test_window_fwd(self, bq, bk):
        q, k, v = _qkv(1, 1, 2, 2, 192, 32)
        o = flash_attention(q, k, v, window=48, block_q=bq, block_k=bk)
        ref = flash_attention(q, k, v, window=48, block_q=64, block_k=64)
        np.testing.assert_allclose(o, ref, **INV)

    @pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64),
                                       (None, None)])
    def test_packed_segments_fwd_and_grads(self, bq, bk):
        """Packed (varlen) path: segment isolation must not depend on how
        tiles cut across document boundaries."""
        q, k, v = _qkv(2, 2, 2, 2, 128, 16)
        seg = jnp.asarray(
            np.repeat([[0, 1, 2, 3], [0, 0, 1, 1]], 32, axis=1))
        fn = functools.partial(flash_attention, causal=True,
                               segment_ids=seg, block_q=bq, block_k=bk)
        ref = functools.partial(flash_attention, causal=True,
                                segment_ids=seg, block_q=128, block_k=128)
        for got, want in zip(_fwd_and_grads(fn, q, k, v),
                             _fwd_and_grads(ref, q, k, v)):
            np.testing.assert_allclose(got, want, **INV)

    def test_spec_auto_equals_pinned(self):
        """AttentionSpec with auto block fields dispatches to the same
        numerics as any pinned spec (models resolve through the tuner)."""
        from repro.core.attention import attention
        q, k, v = _qkv(3, 1, 2, 2, 128, 16)
        auto = AttentionSpec(impl="pallas", causal=True)
        assert auto.block_q is None and auto.block_k is None
        pinned = dataclasses.replace(auto, block_q=32, block_k=64)
        np.testing.assert_allclose(attention(q, k, v, auto),
                                   attention(q, k, v, pinned), **INV)


class TestLoopOrderInvariance:
    """The forward LOOP ORDER (q-major vs kv-major) and the kv ADDRESSING
    (gather-based vs paged-in-place prefill) are tuner/engine decisions —
    so, like tile sizes, they must be observationally pure: outputs AND
    gradients agree to fp32 accumulator tolerance."""

    @pytest.mark.parametrize("kvm", [False, True])
    def test_kv_major_fwd_and_grads(self, kvm):
        """Short-q / long-k causal GQA suffix — the shape kv-major exists
        for (K/V read once per kv head instead of once per q tile row)."""
        q, k, v = _qkv(7, 2, 4, 2, 256, 32)
        q = q[:, :, :64]
        fn = functools.partial(flash_attention, causal=True, kv_major=kvm)
        ref = functools.partial(flash_attention, causal=True, kv_major=False)
        for got, want in zip(_fwd_and_grads(fn, q, k, v),
                             _fwd_and_grads(ref, q, k, v)):
            np.testing.assert_allclose(got, want, **INV)

    def test_kv_major_packed_segments(self):
        """Packed multi-segment call: the kv-major column layout collapse
        (any-PARTIAL column -> PARTIAL) must preserve segment isolation."""
        q, k, v = _qkv(8, 2, 2, 2, 128, 16)
        seg = jnp.asarray(
            np.repeat([[0, 1, 2, 3], [0, 0, 1, 1]], 32, axis=1))
        fn = functools.partial(flash_attention, causal=True,
                               segment_ids=seg, kv_major=True)
        ref = functools.partial(flash_attention, causal=True,
                                segment_ids=seg, kv_major=False)
        for got, want in zip(_fwd_and_grads(fn, q, k, v),
                             _fwd_and_grads(ref, q, k, v)):
            np.testing.assert_allclose(got, want, **INV)

    def _paged_chunk_case(self):
        """A packed 2-segment suffix chunk against a fragmented page pool,
        with per-segment HISTORY (nonzero chunk starts) — the shape of a
        forced-preemption resume, where a chunk re-enters mid-prompt and
        must attend history written by a previous life of the sequence."""
        hq, hkv, d, ps = 4, 2, 16, 16
        spans = [48, 40]            # logical prefix per segment (history+chunk)
        starts = [16, 24]           # chunk q rows resume at these positions
        lengths = [sp - st for sp, st in zip(spans, starts)]
        num_pages = 12
        rng = np.random.default_rng(3)
        n_pages = [kvc.pages_for(sp, ps) for sp in spans]
        perm = rng.permutation(num_pages)
        tables = [perm[:n_pages[0]].tolist(),
                  perm[n_pages[0]:n_pages[0] + n_pages[1]].tolist()]
        total_pages = 8             # bucketed past the 6 live page slots
        page_list, kseg, kpos = kvc.paged_prefix_lists(
            tables, spans, ps, total_pages)

        sq = sum(lengths)
        qseg = np.full((sq,), masks.SEG_PAD_Q, np.int32)
        qpos = np.full((sq,), masks.POS_PAD, np.int32)
        off = 0
        for i, (st, n) in enumerate(zip(starts, lengths)):
            qseg[off:off + n] = i
            qpos[off:off + n] = np.arange(st, st + n)
            off += n

        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (1, hq, sq, d))
        k_pool = jax.random.normal(ks[1], (hkv, num_pages, ps, d))
        v_pool = jax.random.normal(ks[2], (hkv, num_pages, ps, d))
        arrs = dict(page_list=jnp.asarray(page_list[None]),
                    q_segment_ids=jnp.asarray(qseg[None]),
                    kv_segment_ids=jnp.asarray(kseg[None]),
                    q_positions=jnp.asarray(qpos[None]),
                    kv_positions=jnp.asarray(kpos[None]))
        return q, k_pool, v_pool, arrs

    @pytest.mark.parametrize("kvm", [False, True])
    def test_paged_in_place_matches_gather_fwd_and_grads(self, kvm):
        """The Pallas in-place paged prefill (page-table BlockSpec
        indirection) against the XLA gather oracle — same fused mask, two
        addressing schemes, one function. Grads flow to q AND the pool."""
        from repro.kernels import ops
        q, k_pool, v_pool, arrs = self._paged_chunk_case()
        common = dict(q_segment_ids=arrs["q_segment_ids"],
                      kv_segment_ids=arrs["kv_segment_ids"],
                      q_positions=arrs["q_positions"],
                      kv_positions=arrs["kv_positions"])

        def in_place(q, kp, vp):
            return ops.flash_prefill_paged(q, kp, vp, arrs["page_list"],
                                           causal=True, kv_major=kvm,
                                           **common)

        oracle_spec = AttentionSpec(impl="chunked", causal=True)

        def oracle(q, kp, vp):
            return paged_prefill_attention(q, _lane_dense(kp), _lane_dense(vp),
                                           0, arrs["page_list"],
                                           oracle_spec, **common)

        for got, want in zip(_fwd_and_grads(in_place, q, k_pool, v_pool),
                             _fwd_and_grads(oracle, q, k_pool, v_pool)):
            np.testing.assert_allclose(got, want, **INV)


class TestDecodeGeometryInvariance:
    CAP = 256

    def _case(self, seed=4, b=3, hq=4, hkv=2, d=32):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, hq, 1, d))
        kc = jax.random.normal(ks[1], (b, hkv, self.CAP, d))
        vc = jax.random.normal(ks[2], (b, hkv, self.CAP, d))
        kv_len = jnp.asarray([self.CAP, 100, 17], jnp.int32)
        return q, kc, vc, kv_len

    @pytest.mark.parametrize("blk,splits", [
        (256, 1), (128, 2), (64, 4), (32, 8), (None, None)])
    def test_contiguous_split_invariance(self, blk, splits):
        q, kc, vc, kv_len = self._case()
        o = flash_decode(q, kc, vc, kv_len, block_k=blk, num_splits=splits)
        xla = decode_attention(q, kc, vc, kv_len,
                               AttentionSpec(use_decode_kernel=False))
        np.testing.assert_allclose(o, xla, **INV)

    @pytest.mark.parametrize("splits", [1, 2, 4, 8, None])
    def test_paged_split_invariance(self, splits):
        hkv, d, ps, T, num_pages = 2, 32, 32, 8, 24
        q, kc, vc, kv_len = self._case(seed=5)
        rng = np.random.default_rng(0)
        perm = rng.permutation(num_pages)[: 3 * T].reshape(3, T)
        table = jnp.asarray(perm, jnp.int32)
        kp = jnp.zeros((hkv, num_pages, ps, d))
        vp = jnp.zeros((hkv, num_pages, ps, d))
        kp = kp.at[:, perm].set(
            np.asarray(kc).reshape(3, hkv, T, ps, d).transpose(1, 0, 2, 3, 4))
        vp = vp.at[:, perm].set(
            np.asarray(vc).reshape(3, hkv, T, ps, d).transpose(1, 0, 2, 3, 4))
        o = flash_decode_paged(q, kp, vp, table, kv_len, num_splits=splits)
        xla = paged_decode_attention(
            q, _lane_dense(kp), _lane_dense(vp), 0, table, kv_len,
            AttentionSpec(use_decode_kernel=False))
        np.testing.assert_allclose(o, xla, **INV)

    def test_auto_geometry_matches_every_pinned_geometry(self):
        """All pairwise: the merge operator is associative, so ANY split
        of the KV axis is the same function."""
        q, kc, vc, kv_len = self._case(seed=6)
        outs = [flash_decode(q, kc, vc, kv_len, block_k=blk,
                             num_splits=splits)
                for blk, splits in [(None, None), (256, 1), (64, 4)]]
        for other in outs[1:]:
            np.testing.assert_allclose(outs[0], other, **INV)
