"""A run with the timed path broken underneath comes out not correct, and a
sound run at the same tiny size comes out correct (CPU; the limits are the
cells' own)."""

import pytest

import bench_tiny
from chipbench import check, faults

bench = bench_tiny.runner()

SEED = 2**31 + 3


def run(name, fault=None, control=None):
    cell = bench_tiny.tiny_cell(name)
    if fault is None:
        return bench.run_cell(cell, SEED, 0.5, False, bench_tiny.FAKE_PEAKS,
                              control=control)
    with fault():
        return bench.run_cell(cell, SEED, 0.5, False, bench_tiny.FAKE_PEAKS)


@pytest.mark.parametrize("name", ["granite-3-2b.chat", "gpt2-small.train-4k"])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("name,fault", [
    ("granite-3-2b.chat", faults.altered_token),
    ("gpt2-small.train-4k", faults.unchanged_state),
    ("gpt2-small.train-4k", faults.half_batch),
])
def test_planted_fault_is_not_correct(name, fault):
    out = run(name, fault)
    assert not out["correct"], out["check"]


def test_lower_precision_control_is_not_correct():
    """The bfloat16 reference in the program's place fails the training
    cell's limits (the chat cell's fp8 control is read on the chip only:
    at this size it ranks the same tokens first)."""
    name = "gpt2-small.train-4k"
    out = run(name, control=bench_tiny.tiny_cell(name).config["control_precision"])
    assert out["correct"]
    ok, _ = check.judge(out["check_low"], bench_tiny.tiny_cell(name).limits)
    assert not ok, out["check_low"]
