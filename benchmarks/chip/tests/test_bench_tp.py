"""A serving cell on four chips, at a tiny size on four host-platform
devices: the mix's ``tp`` reaches the engine, the weights are made in the
engine's own layout and are the engine's arrays, and a whole run through
``run_cell`` comes out correct, and not correct with a fault planted."""

import jax
import numpy as np
import pytest

import bench_tiny
from chipbench import faults
from chipbench.common import program_model
from chipbench.serve_cell import serve_shardings
from chipbench.weights import make_params

CELL = "internlm2-20b.chat-tp4"
SEED = 2**31 + 11

bench = bench_tiny.runner()


def model_and_shapes():
    cell = bench_tiny.tiny_cell(CELL)
    model = program_model(cell.config)
    return cell, model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


def test_tiny_cell_keeps_the_mesh():
    cell = bench_tiny.tiny_cell(CELL)
    assert cell.chips == 4 and cell.mix["engine"]["tp"] == 4
    assert jax.device_count() >= 4


def test_sharded_weights_equal_unsharded():
    cell, model, shapes = model_and_shapes()
    shardings = serve_shardings(model, 4)
    a = make_params(shapes, SEED)
    b = make_params(shapes, SEED, shardings)
    for x, y, sh in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                        jax.tree.leaves(shardings)):
        assert y.sharding == sh
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert serve_shardings(model, 1) is None


def test_engine_serves_the_harness_arrays():
    from repro.serve import ServingEngine
    cell, model, shapes = model_and_shapes()
    params = make_params(shapes, SEED, serve_shardings(model, 4))
    E = cell.mix["engine"]
    eng = ServingEngine(model, params, num_slots=E["lanes"],
                        capacity=E["capacity"], page_size=E["page_size"],
                        chunk_size=E["chunk_size"],
                        prefill_bucket=E["prefill_bucket"], tp=4)
    for mine, served in zip(jax.tree.leaves(params), jax.tree.leaves(eng.params)):
        assert served is mine


def test_tp_that_is_not_the_cells_chips_fails_before_any_work():
    cell = bench_tiny.tiny_cell(CELL)
    cell.mix["engine"]["tp"] = 2
    with pytest.raises(ValueError, match="tp=2"):
        bench.run_cell(cell, SEED, 0.5, False, bench_tiny.FAKE_PEAKS)


def test_tiny_four_chip_run_is_correct():
    out = bench.run_cell(bench_tiny.tiny_cell(CELL), SEED, 0.5, False,
                         bench_tiny.FAKE_PEAKS)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", sorted(faults.planted(bench_tiny.tiny_cell(CELL))))
def test_planted_fault_on_four_chips_is_not_correct(name):
    cell = bench_tiny.tiny_cell(CELL)
    with faults.planted(cell)[name]():
        out = bench.run_cell(cell, SEED, 0.5, False, bench_tiny.FAKE_PEAKS)
    assert not out["correct"], out["check"]
