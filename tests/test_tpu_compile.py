"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers a kernel with ``interpret=False`` for one chip of a
described (not attached) ``v5e:2x2`` topology and asserts that the compiled
program holds the Mosaic kernel (``tpu_custom_call``). Interpret mode
accepts block shapes the chip's compiler refuses; these compiles are what
guard the kernels' TPU lowering without a chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports every test file. All such tests live in this one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.ops import flash_attention, flash_prefill_paged

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def test_flash_forward_granite_widths(one_chip):
    """granite-3-2b attention: 32 q / 8 kv heads, d=64, s=2048, causal."""
    args = _shapes(one_chip, ((1, 32, 2048, 64), BF16),
                   ((1, 8, 2048, 64), BF16), ((1, 8, 2048, 64), BF16))
    text = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
def test_flash_fwd_bwd_gpt2_small_widths(one_chip, dropout_p):
    """jax.grad at gpt2-small widths (12 heads, d=64, s=1024): the forward,
    dq and dkv kernels, with and without attention dropout."""
    args = _shapes(one_chip, *[((2, 12, 1024, 64), BF16)] * 3)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, dropout_p=dropout_p,
                            dropout_seed=3, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("train", [False, True])
def test_flash_prefill_paged_16_row_pages(one_chip, train):
    """Chunked prefill against a 16-row page pool (granite heads), forward
    and — for trainable use — its dq/dkv backward."""
    b, sq, pages, T = 2, 512, 256, 64
    args = _shapes(one_chip, ((b, 32, sq, 64), BF16),
                   ((8, pages, 16, 64), BF16), ((8, pages, 16, 64), BF16),
                   ((b, T), I32), ((b, sq), I32), ((b, T * 16), I32))

    def prefill(q, kp, vp, page_list, q_pos, kv_pos):
        return flash_prefill_paged(q, kp, vp, page_list, q_positions=q_pos,
                                   kv_positions=kv_pos, interpret=False)

    fn = prefill
    if train:
        fn = jax.grad(lambda *a: jnp.sum(prefill(*a).astype(jnp.float32)),
                      argnums=(0, 1, 2))
    assert "tpu_custom_call" in _compile_text(fn, *args)


@pytest.mark.parametrize("with_kv_mask", [False, True])
def test_flash_decode_contiguous_cache(one_chip, with_kv_mask):
    """Split-KV decode: 8 lanes, granite heads, a 2048-slot cache."""
    specs = [((8, 32, 1, 64), BF16), ((8, 8, 2048, 64), BF16),
             ((8, 8, 2048, 64), BF16), ((8,), I32)]
    if with_kv_mask:
        specs.append(((8, 2048), jnp.bool_))
    args = _shapes(one_chip, *specs)
    text = _compile_text(
        lambda q, k, v, kv_len, *m: flash_decode(
            q, k, v, kv_len, kv_mask=m[0] if m else None, interpret=False),
        *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size", [16, 8])
def test_flash_decode_paged_pool(one_chip, page_size):
    """Paged split-KV decode: 8 kv heads, 4 lanes, 2048-slot page tables;
    bf16 pages of 16 rows (served) and of 8 rows (half a bf16 tile)."""
    T = 2048 // page_size
    args = _shapes(one_chip, ((4, 32, 1, 64), BF16),
                   ((8, 4 * T, page_size, 64), BF16),
                   ((8, 4 * T, page_size, 64), BF16),
                   ((4, T), I32), ((4,), I32))
    text = _compile_text(
        lambda q, kp, vp, table, kv_len: flash_decode_paged(
            q, kp, vp, table, kv_len, interpret=False), *args)
    assert "tpu_custom_call" in text
