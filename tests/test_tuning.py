"""Kernel tuning subsystem (kernels/tuning.py + core/io_model.py):
analytic chooser properties, the lane-aligned block clamp, decode-geometry
resolution (contiguous + paged invariant), and the autotune cache
write+read roundtrip."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import io_model
from repro.kernels import tuning
from repro.kernels.ops import flash_attention
from repro.kernels.ref import standard_attention

TOL = dict(rtol=2e-3, atol=2e-5)


# ---------------------------------------------------------------------------
# analytic chooser
# ---------------------------------------------------------------------------

class TestAnalyticChooser:
    @pytest.mark.parametrize("n", [128, 256, 512, 1024, 2048, 4096, 32768])
    @pytest.mark.parametrize("d", [64, 128])
    def test_sweep_shapes_lane_aligned_and_fit(self, n, d):
        """PR-4 acceptance: for every attention-sweep shape the auto tiles
        are lane-aligned and their fwd+bwd working set fits the budget."""
        cfg = tuning.choose_tile_config(n, n, d)
        assert cfg.block_q % io_model.LANES == 0
        assert cfg.block_k % io_model.LANES == 0
        assert io_model.attention_working_set_bytes(
            cfg.block_q, cfg.block_k, d) <= tuning.sram_budget()

    @pytest.mark.parametrize("n", [1024, 4096, 32768])
    def test_chosen_hbm_never_worse_than_fixed_128(self, n):
        """The chooser's objective IS the Theorem-2 byte count, so the old
        fixed 128/128 default can never beat it (long-seq acceptance)."""
        d = 64
        cfg = tuning.choose_tile_config(n, n, d)
        chosen = io_model.flash_hbm_bytes_tiled(
            n, n, d, 1, 1, cfg.block_q, cfg.block_k)
        fixed = io_model.flash_hbm_bytes_tiled(n, n, d, 1, 1, 128, 128)
        assert chosen <= fixed

    def test_budget_shrinks_tiles(self):
        big = tuning.choose_tile_config(4096, 4096, 64,
                                        sram_budget_bytes=8 << 20)
        small = tuning.choose_tile_config(4096, 4096, 64,
                                          sram_budget_bytes=1 << 20)
        assert (small.block_q, small.block_k) <= (big.block_q, big.block_k)
        assert io_model.attention_working_set_bytes(
            small.block_q, small.block_k, 64) <= (1 << 20)

    def test_pinned_axis_respected(self):
        cfg = tuning.choose_tile_config(2048, 2048, 64, block_q=128)
        assert cfg.block_q == 128
        assert cfg.block_k % io_model.LANES == 0

    def test_working_set_monotone_in_tiles(self):
        ws = io_model.attention_working_set_bytes
        assert ws(128, 128, 64) < ws(256, 128, 64) < ws(256, 256, 64)
        assert ws(128, 128, 64, backward=False) < ws(128, 128, 64)

    def test_hbm_model_prefers_bigger_q_blocks(self):
        """q-major grid: K/V are re-streamed once per q block, so doubling
        block_q nearly halves the dominant term."""
        h = io_model.flash_hbm_bytes_tiled
        assert h(4096, 4096, 64, 1, 1, 256, 128) \
            < h(4096, 4096, 64, 1, 1, 128, 128)


# ---------------------------------------------------------------------------
# block clamp (lane-alignment regression for tiny/ragged seq lens)
# ---------------------------------------------------------------------------

class TestRoundBlock:
    @pytest.mark.parametrize("req,seq,expect", [
        (128, 96, 96),     # old behavior kept: 96 is already aligned
        (128, 100, 104),   # OLD clamp gave 100 (unaligned); now 104 + pad
        (128, 3, 8),       # tiny seq -> one minimal aligned tile
        (64, 96, 64),      # no clamp needed
        (256, 512, 256),   # explicit choice passes through
        (60, 1000, 56),    # unaligned request rounded down
    ])
    def test_values(self, req, seq, expect):
        assert tuning.round_block(req, seq) == expect

    def test_always_sublane_multiple(self):
        for req in [8, 60, 128, 250, 1024]:
            for seq in [1, 3, 7, 100, 130, 999]:
                blk = tuning.round_block(req, seq)
                assert blk % io_model.SUBLANES == 0
                assert blk >= io_model.SUBLANES

    @pytest.mark.parametrize("sq,sk", [(100, 100), (3, 130), (130, 100),
                                       (5, 5), (100, 260)])
    def test_ragged_seq_numerics(self, sq, sk):
        """flash_attention on ragged lengths (auto blocks): the padded
        aligned tiles must be numerically invisible."""
        ks = jax.random.split(jax.random.PRNGKey(sq * 1000 + sk), 3)
        q = jax.random.normal(ks[0], (2, 2, sq, 32))
        k = jax.random.normal(ks[1], (2, 2, sk, 32))
        v = jax.random.normal(ks[2], (2, 2, sk, 32))
        causal = sq <= sk
        o = flash_attention(q, k, v, causal=causal)
        o_ref = standard_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(o, o_ref, **TOL)


# ---------------------------------------------------------------------------
# decode geometry resolution
# ---------------------------------------------------------------------------

class TestDecodeGeometry:
    @pytest.mark.parametrize("capacity", [16, 64, 128, 384, 2048, 4096])
    def test_auto_always_divisor_valid(self, capacity):
        blk, splits = tuning.resolve_decode_geometry(
            capacity, None, None, head_dim=64)
        assert capacity % blk == 0
        assert (capacity // blk) % splits == 0
        assert splits <= tuning.TARGET_DECODE_SPLITS

    def test_explicit_still_validates(self):
        with pytest.raises(ValueError, match="multiple of block_k"):
            tuning.resolve_decode_geometry(384, 256, 1, head_dim=64)

    @pytest.mark.parametrize("capacity,splits", [(768, 3), (4096, 16),
                                                 (256, 2)])
    def test_pinned_splits_constrain_auto_block(self, capacity, splits):
        """An explicit num_splits with an auto block is a CONSTRAINT on the
        block search — honored exactly, never clamped or rejected when a
        valid aligned block exists (regression: the chooser used to pick
        its block for its own split target first)."""
        blk, got = tuning.resolve_decode_geometry(
            capacity, None, splits, head_dim=64)
        assert got == splits
        assert capacity % blk == 0
        assert (capacity // blk) % splits == 0

    def test_pinned_splits_impossible_raises(self):
        with pytest.raises(ValueError, match="num_splits"):
            tuning.resolve_decode_geometry(128, None, 7, head_dim=64)

    def test_paged_block_is_the_page(self):
        blk, splits = tuning.resolve_decode_geometry(
            192, None, None, head_dim=64, page_size=16)
        assert blk == 16
        assert 12 % splits == 0

    def test_paged_conflicting_block_rejected(self):
        with pytest.raises(ValueError, match="page_size"):
            tuning.resolve_decode_geometry(192, 128, None, head_dim=64,
                                           page_size=16)

    def test_paged_explicit_splits_validated(self):
        with pytest.raises(ValueError, match="num_splits"):
            tuning.resolve_decode_geometry(192, None, 8, head_dim=64,
                                           page_size=16)


# ---------------------------------------------------------------------------
# autotune cache roundtrip
# ---------------------------------------------------------------------------

class TestAutotuneCache:
    def test_write_then_hit(self, tmp_path):
        path = str(tmp_path / "autotune.json")
        tuning.configure_tuning(cache_path=path)
        try:
            first = tuning.autotune_tiles(128, 128, 16, dtype=jnp.float32,
                                          mask_class="causal",
                                          backward=False, max_candidates=2)
            assert first.source == "autotuned"
            with open(path) as f:
                blob = json.load(f)
            assert len(blob["entries"]) == 1
            (entry,) = blob["entries"].values()
            assert entry["block_q"] == first.block_q
            assert entry["timed_us"] > 0
            second = tuning.autotune_tiles(128, 128, 16, dtype=jnp.float32,
                                           mask_class="causal",
                                           backward=False, max_candidates=2)
            assert second.source == "cache"
            assert (second.block_q, second.block_k) \
                == (first.block_q, first.block_k)
            # a different workload class misses (key includes mask class)
            assert tuning.autotune_cache().get(
                tuning.cache_key("x", "f32", 16, 128, "dense")) is None
        finally:
            tuning.configure_tuning(cache_path=tuning._DEFAULT_CACHE)

    def test_partial_pin_constrains_candidates(self, tmp_path):
        """A pinned axis is honored by the empirical tuner (only pinned
        combinations are timed) and keyed separately from unpinned runs."""
        tuning.configure_tuning(cache_path=str(tmp_path / "p.json"),
                                autotune=True)
        try:
            cfg = tuning.resolve_tiles(64, None, sq=128, sk=128,
                                       head_dim=16, dtype=jnp.float32,
                                       mask_class="causal")
            assert cfg.block_q == 64
            assert cfg.source == "autotuned"
            again = tuning.resolve_tiles(64, None, sq=128, sk=128,
                                         head_dim=16, dtype=jnp.float32,
                                         mask_class="causal")
            assert again.source == "cache" and again.block_q == 64
        finally:
            tuning.configure_tuning(cache_path=tuning._DEFAULT_CACHE,
                                    autotune=False)

    def test_backward_timed_and_keyed_separately(self, tmp_path):
        """backward=True times the fwd+grad pipeline (split dq/dkv kernels)
        and persists under its own |bwd key — the forward-only entry never
        serves a trainable call site, and vice versa."""
        path = str(tmp_path / "b.json")
        tuning.configure_tuning(cache_path=path)
        try:
            fwd = tuning.autotune_tiles(128, 128, 16, dtype=jnp.float32,
                                        mask_class="causal",
                                        backward=False, max_candidates=2)
            bwd = tuning.autotune_tiles(128, 128, 16, dtype=jnp.float32,
                                        mask_class="causal",
                                        backward=True, max_candidates=2)
            assert fwd.source == "autotuned" and bwd.source == "autotuned"
            with open(path) as f:
                entries = json.load(f)["entries"]
            assert len(entries) == 2
            bwd_keys = [k for k in entries if k.endswith("|bwd")]
            assert len(bwd_keys) == 1
            assert entries[bwd_keys[0]]["timed_us"] > 0
            # both namespaces hit on re-resolution
            assert tuning.autotune_tiles(
                128, 128, 16, dtype=jnp.float32, mask_class="causal",
                backward=True, max_candidates=2).source == "cache"
            assert tuning.autotune_tiles(
                128, 128, 16, dtype=jnp.float32, mask_class="causal",
                backward=False, max_candidates=2).source == "cache"
        finally:
            tuning.configure_tuning(cache_path=tuning._DEFAULT_CACHE)

    def test_resolve_tiles_explicit_skips_cache(self, tmp_path):
        tuning.configure_tuning(cache_path=str(tmp_path / "a.json"),
                                autotune=True)
        try:
            cfg = tuning.resolve_tiles(64, 32, sq=128, sk=128, head_dim=16,
                                       dtype=jnp.float32)
            assert (cfg.block_q, cfg.block_k, cfg.source) \
                == (64, 32, "explicit")
        finally:
            tuning.configure_tuning(cache_path=tuning._DEFAULT_CACHE,
                                    autotune=False)


@pytest.mark.parametrize("kind,bw", [("TPU v5 lite", io_model.V5E_HBM_BW),
                                     ("TPU v5e", io_model.V5E_HBM_BW),
                                     ("cpu", None), ("TPU v4", None)])
def test_nominal_hbm_bw_only_for_known_kinds(kind, bw):
    """A device kind with no row gets no nominal bandwidth (and so no
    calibration ratio), never a guessed default."""
    assert tuning.nominal_hbm_bw(kind) == bw


def test_calibration_ratio_not_measured_for_unknown_kind(tmp_path):
    cache = tuning.AutotuneCache(str(tmp_path / "c.json"))
    cfg = tuning.TileConfig(block_q=64, block_k=64, source="autotuned")
    for kind in ("cpu", "TPU v5 lite"):
        cache.put(f"{kind}|k", cfg, 10.0, model_hbm_bytes=8.19e6,
                  device_kind=kind)
    assert cache.calibration("cpu")["vs_nominal"] is None
    assert cache.calibration("TPU v5 lite")["vs_nominal"] == pytest.approx(1.0)
