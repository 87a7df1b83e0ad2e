"""FLOP and byte counts against hand arithmetic at the cells' widths."""

import pytest

import bench_tiny  # noqa: F401
from chipbench import flops

GRANITE = flops.Dims(layers=40, d_model=2048, heads=32, kv_heads=8,
                     head_dim=64, d_ff=8192, vocab=49155, mlp_mats=3,
                     elt_bytes=2)
GPT2 = flops.Dims(layers=12, d_model=768, heads=12, kv_heads=12, head_dim=64,
                  d_ff=3072, vocab=50257, mlp_mats=2, elt_bytes=4)


def test_granite_sizes():
    # per layer: q 2048x2048, k and v 2048x512, o 2048x2048, 3 x 2048x8192
    per_layer = 2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192
    assert GRANITE.block_params == 40 * per_layer == 2_432_696_320
    assert GRANITE.head_params == 2048 * 49155
    assert GRANITE.kv_bytes_per_token == 81_920          # 40 x 8 x 64 x 2 x 2 B
    assert GRANITE.weight_bytes == 2 * (2_432_696_320 + 2048 * 49155)


def test_gpt2_sizes():
    per_layer = 4 * 768 * 768 + 2 * 768 * 3072
    assert GPT2.block_params == 12 * per_layer == 84_934_656
    assert GPT2.head_params == 768 * 50257


def test_causal_keys():
    assert flops.causal_keys(0, 4) == 1 + 2 + 3 + 4
    assert flops.causal_keys(10, 2) == 11 + 12


def test_decode_step():
    # 3 lanes at 100, 200 and 300 keys (new rows included)
    f = flops.decode_flops(GRANITE, 3, 600)
    dense = 2 * (GRANITE.block_params + GRANITE.head_params) * 3
    attn = 4 * 40 * 32 * 64 * 600
    assert f == dense + attn
    b = flops.decode_bytes(GRANITE, 3, 600)
    assert b == GRANITE.weight_bytes + (600 + 3) * 81_920


def test_prefill_chunk():
    # a 512-row chunk from position 1024, its last row's logits used
    f = flops.prefill_flops(GRANITE, 1024, 512, head_rows=1)
    keys = sum(range(1025, 1537))
    assert f == (2 * GRANITE.block_params * 512 + 4 * 40 * 32 * 64 * keys
                 + 2 * GRANITE.head_params)
    b = flops.prefill_bytes(GRANITE, [(1024, 512), (0, 100)])
    assert b == GRANITE.weight_bytes + (1536 + 512 + 100 + 100) * 81_920


def test_train_step_counts_documents_not_rows():
    # one row of 4096 tokens in two documents of 1000 and 3096
    f = flops.train_flops(GPT2, 4096, [1000, 3096])
    keys = 1000 * 1001 // 2 + 3096 * 3097 // 2
    fwd = 2 * (GPT2.block_params + GPT2.head_params) * 4096 + 4 * 12 * 12 * 64 * keys
    assert f == 3 * fwd
    # per token about 0.74 GFLOP of 6N plus attention
    assert f / 4096 == pytest.approx(6 * 123_532_032 + 3 * 36864 * keys / 4096)


def test_roofline_takes_the_larger_bound():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_s(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops.roofline_s(1.0, 819e9, peaks) == pytest.approx(1.0)
