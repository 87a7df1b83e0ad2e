"""The trace reduction on a hand-built event fixture."""

import pytest

import bench_tiny  # noqa: F401
from chipbench.trace_reduce import Event, program_name, summarize, union_length

MS = 1e6   # ns


def test_union_length_merges_overlaps():
    length, merged = union_length([(0, 10), (5, 15), (20, 30), (30, 31)])
    assert length == 26
    assert merged == [[0, 15], [20, 31]]


def test_program_name():
    assert program_name("jit_decode_step(123)") == "decode_step"
    assert program_name("jit_prefill_chunk_paged(7)") == "prefill_chunk_paged"
    assert program_name("fusion.12") == "fusion.12"


def fixture():
    # window 0..100 ms; ops busy 10-30, 25-40 (overlap), 60-70, and 95-120
    # (clipped to 95-100); programs cover the ops; host spans label gaps.
    ops = [Event("fusion.1", 10 * MS, 30 * MS), Event("fusion.2", 25 * MS, 40 * MS),
           Event("dot.3", 60 * MS, 70 * MS), Event("fusion.1", 95 * MS, 120 * MS)]
    mods = [Event("jit_decode_step(1)", 10 * MS, 40 * MS),
            Event("jit_prefill_chunk_paged(2)", 60 * MS, 70 * MS),
            Event("jit_decode_step(1)", 95 * MS, 120 * MS)]
    host = [Event("engine.step", 5 * MS, 45 * MS),
            Event("client.collect", 45 * MS, 50 * MS),
            Event("engine.step", 55 * MS, 90 * MS),
            Event("traffic.submit", 80 * MS, 85 * MS)]
    return (0.0, 100 * MS), [ops], [mods], host


def test_busy_idle_programs():
    t = summarize(*fixture())
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(0.045)          # 30 + 10 + 5 ms
    assert t.programs == pytest.approx({"decode_step": 0.035,
                                        "prefill_chunk_paged": 0.010})
    assert t.program_calls == {"decode_step": 2, "prefill_chunk_paged": 1}
    assert t.ops["fusion.1"] == pytest.approx(0.025)
    # gaps: 0-10 (mid 5: engine.step starts at 5), 40-60 (mid 50: the
    # collect span ends there), 70-95 (mid 82.5: submit inside a step)
    assert sum(t.idle_by_span.values()) == pytest.approx(0.055)
    assert t.idle_by_span == pytest.approx({"engine.step": 0.010,
                                            "client.collect": 0.020,
                                            "traffic.submit": 0.025})


def test_no_device_work_is_no_busy():
    w, _, _, host = fixture()
    t = summarize(w, [[]], [[]], host)
    assert t.devices == 0 and t.busy_s == 0.0
