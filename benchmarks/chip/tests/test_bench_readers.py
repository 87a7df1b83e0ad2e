"""Metric readers on a hand-built run record: tails over the window's
requests, rates over the window, model calls read from engine spans."""

import types

import pytest

import bench_tiny  # noqa: F401
from chipbench import flops, manifest
from chipbench.common import Run
from chipbench.engine_calls import model_calls
from chipbench.serve_cell import ReqRecord
from chipbench.traffic import ServeRequest
from chipbench.windows import prefill_gap_share, window_gaps
from test_bench_flops import GRANITE

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def rec(due, times, submit=None, segment="window", rid=0):
    r = ReqRecord(ServeRequest(rid, due, [1, 2], 4, segment), due)
    r.submit, r.times, r.rid, r.done = (due if submit is None else submit), times, rid, True
    return r


def serve_run():
    run = Run(cell=None, seed=0, seconds=10.0, peaks=PEAKS, window=(100.0, 110.0))
    run.dims = GRANITE
    run.requests = [
        rec(101.0, [101.5, 101.6, 101.9], submit=101.01, rid=0),
        rec(105.0, [106.0, 106.1], submit=105.02, rid=1),
        rec(99.0, [99.1, 99.2], rid=2),                       # due before t0
        rec(104.0, [104.2, 104.3], segment="tail", rid=3),
    ]
    #        start   end  prefill gen active
    run.steps = [(99.9, 100.5, 512, 1, 2), (100.5, 109.0, 0, 3, 4),
                 (109.0, 110.5, 512, 2, 4)]
    run.engine_events = [
        {"kind": "step", "name": "prefill_chunk", "t": 100.6, "dur": 0.1,
         "chunks": [[512, 100], [0, 64]]},
        {"kind": "req", "name": "chunk", "t": 100.7, "start": 512, "length": 100, "last": True},
        {"kind": "req", "name": "chunk", "t": 100.7, "start": 0, "length": 64, "last": False},
        {"kind": "step", "name": "decode", "t": 101.0, "dur": 0.05, "tokens": 3, "kv_rows": 300},
        {"kind": "req", "name": "admit", "t": 101.2, "rid": 0},
        {"kind": "req", "name": "admit", "t": 105.5, "rid": 1},
    ]
    return run


def read(name, run):
    return manifest.metric_reader(name)(run)


def test_tails_over_window_requests_only():
    run = serve_run()
    # gaps of requests 0 and 1 only: 100, 300, 100 ms
    assert read("itl_p50_ms", run) == pytest.approx(100)
    assert read("itl_p99_ms", run) == pytest.approx(300 - 0.02 * 200)


def test_gaps_that_carried_prefill():
    run = serve_run()
    gaps, carried = window_gaps(run)
    assert gaps == pytest.approx([100, 300, 100])
    # only the first gap, (101.5, 101.6], holds the end of a step that
    # prefilled
    run.steps = [(101.4, 101.5, 0, 1, 1), (101.5, 101.6, 64, 1, 1),
                 (101.6, 101.9, 0, 1, 1), (105.9, 106.0, 512, 1, 1),
                 (106.0, 106.1, 0, 1, 1)]
    gaps, carried = window_gaps(run)
    assert carried.tolist() == [True, False, False]
    assert prefill_gap_share(run) == pytest.approx(1 / 3)


def test_rates_over_the_window():
    # every step started in the window counts; the window closes at the
    # last one's end (12.0), not at t0 + seconds
    run = Run(cell=None, seed=0, seconds=10.0, peaks=PEAKS, window=(0.0, 12.0))
    run.train_steps = [(0.0, 4.0, 16384, 1e15, 9.0), (4.0, 8.0, 16384, 1e15, 8.9),
                       (8.0, 12.0, 16384, 1e15, 8.8)]
    assert read("train_tokens_per_s", run) == pytest.approx(3 * 16384 / 12)
    run.window = (0.0, 12.5)
    assert read("train_tokens_per_s", run) == pytest.approx(3 * 16384 / 12.5)


def test_model_calls_from_engine_spans():
    calls = model_calls(serve_run())
    assert [c.kind for c in calls] == ["prefill", "decode"]
    want = (flops.prefill_flops(GRANITE, 512, 100, 1)
            + flops.prefill_flops(GRANITE, 0, 64, 0))
    assert calls[0].flops == pytest.approx(want)
    assert calls[1].flops == pytest.approx(flops.decode_flops(GRANITE, 3, 303))
    mfu = read("mfu.chat", serve_run())
    assert mfu == pytest.approx(100 * (calls[0].flops + calls[1].flops) / (10 * 197e12))


def test_device_readers_need_a_trace():
    run = serve_run()
    for name in ("idle_share.chat", "decode_step_roofline.chat"):
        assert read(name, run) is None
    run.trace = types.SimpleNamespace(window_s=2.0, busy_s=1.5, devices=1,
                                      programs={"decode_step": 0.5})
    run.trace_window = (100.9, 101.1)
    assert read("idle_share.chat", run) == pytest.approx(25.0)
    need = flops.roofline_s(flops.decode_flops(GRANITE, 3, 303),
                            flops.decode_bytes(GRANITE, 3, 303), PEAKS)
    assert read("decode_step_roofline.chat", run) == pytest.approx(100 * need / 0.5)


def test_training_readers():
    run = Run(cell=None, seed=0, seconds=10.0, peaks=PEAKS, window=(0.0, 12.0))
    run.train_steps = [(0.0, 4.0, 16384, 1e15, 9.0), (4.0, 8.0, 16384, 1e15, 8.9),
                       (8.0, 12.0, 16384, 1e15, 8.8)]
    assert read("train_tokens_per_s", run) == pytest.approx(3 * 16384 / 12)
    assert read("mfu.train", run) == pytest.approx(100 * 3e15 / (12 * 197e12))
