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

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.ops import flash_attention, flash_prefill_paged
from repro.models import build_model

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


# ---------------------------------------------------------------------------
# paged step programs: the page pool is updated in place, never copied
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\((%[\w.-]+)?(.*)")
_FUSED = re.compile(r" fusion\(.*calls=%([\w.-]+)")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _materialized_moves(hlo: str) -> list[tuple[str, str, int]]:
    """(name, opcode, bytes) of every copy, dynamic-slice and
    dynamic-update-slice in an optimised HLO module that writes a buffer
    of its own: an instruction outside every fusion body, or a fusion
    whose root (through bitcasts) is one. Inside a fusion they only
    address the operands of the fused computation."""
    comps: dict[str, dict] = {}
    cur: dict = {}
    for line in hlo.splitlines():
        if m := _COMPUTATION.match(line):
            cur = comps.setdefault(m.group(1), {})
        elif m := _INSTRUCTION.match(line):
            root, name, dtype, dims, op, arg, rest = m.groups()
            bits = re.sub(r"\D", "", dtype)         # bf16 -> 16; pred -> 8
            nbytes = max(1, int(bits or 8) // 8)
            for d in filter(None, dims.split(",")):
                nbytes *= int(d)
            cur[name] = (op, nbytes, (arg or "")[1:], rest)
            if root:
                cur[None] = name
    fused = set(_FUSED.findall(hlo))

    def root_op(comp):
        instrs = comps[comp]
        op, _, arg, _ = instrs[instrs[None]]
        while op == "bitcast" and arg in instrs:
            op, _, arg, _ = instrs[arg]
        return op

    moves = []
    for cname, instrs in comps.items():
        if cname in fused:
            continue
        for name, (op, nbytes, _, rest) in (
                (k, v) for k, v in instrs.items() if k is not None):
            if op == "fusion":
                op = root_op(re.search(r"calls=%([\w.-]+)", rest).group(1))
            if op in _MOVES:
                moves.append((name, op, nbytes))
    return moves


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_paged"])
def test_paged_steps_update_the_pool_in_place(one_chip, program):
    """granite-3-2b at its published widths (4 layers), 8 lanes over 2048
    pages of 16 rows, the pool donated as the engine donates it: the
    decode step, and a 512-row chunk over a 4096-row prefix. No copy,
    dynamic-slice or dynamic-update-slice writes a tensor of one layer's
    K pool or more, and the program's temporaries stay under one layer's
    K pool — the layer loop writes and reads the lane-dense pool in place
    with one scatter and one gather."""
    lanes, pages, page_size, capacity, chunk = 8, 2048, 16, 4096, 512
    cfg = dataclasses.replace(get_config("granite-3-2b"), num_layers=4)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_paged_decode_state(
        lanes, pages, page_size, capacity // page_size)))
    if program == "decode_step":
        args = (params, state, *_shapes(one_chip, ((lanes,), I32)))
        fn = jax.jit(model.decode_step, donate_argnums=(1,))
    else:
        rows = {"tokens": (1, chunk), "q_segment_ids": (1, chunk),
                "q_positions": (1, chunk), "kv_segment_ids": (1, capacity),
                "kv_positions": (1, capacity), "dest_page": (chunk,),
                "dest_off": (chunk,), "page_list": (1, capacity // page_size)}
        batch = {k: jax.ShapeDtypeStruct(v, I32, sharding=one_chip)
                 for k, v in rows.items()}
        args = (params, batch, state["caches"])
        fn = jax.jit(model.prefill_chunk_paged, donate_argnums=(2,))
    compiled = fn.lower(*args).compile()
    k_pool = state["caches"]["kv"]["k"]
    layer_pool = math.prod(k_pool.shape[1:]) * k_pool.dtype.itemsize
    big = [m for m in _materialized_moves(compiled.as_text())
           if m[2] >= layer_pool]
    assert big == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool
