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


def test_collective_ops_by_kind():
    from chipbench.trace_reduce import is_collective, op_kind
    assert op_kind("f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x), "
                   "replica_groups={{0,1,2,3}}") == "all-reduce"
    assert op_kind("(bf16[8,6144]{1,0:T(8,128)(2,1)}, u32[]) all-reduce-start("
                   "bf16[8,6144]{1,0} %p)") == "all-reduce-start"
    assert op_kind("f32[4]{0:T(256)} fusion(f32[4]{0} %a), kind=kLoop") == "fusion"
    yes = [Event("%all-reduce.7", 0, 1), Event("all-gather-start.2", 0, 1),
           Event("_reduce-scatter.1", 0, 1), Event("collective-permute-done", 0, 1),
           Event("%fusion.12", 0, 1, kind="all-reduce"), Event("all-to-all.3", 0, 1)]
    no = [Event("%fusion.12", 0, 1, kind="fusion"), Event("reduce.4", 0, 1),
          Event("all_reduce_like_name", 0, 1), Event("%copy-start.1", 0, 1)]
    from types import SimpleNamespace
    from chipbench.trace_reduce import op_event
    e = op_event(SimpleNamespace(
        name="%all-reduce.5.clone = bf16[8,6144]{1,0} all-reduce(bf16[8,6144]{1,0} %dot)",
        start_ns=10, end_ns=20))
    assert (e.name, e.start, e.end, e.kind) == ("%all-reduce.5.clone", 10, 20, "all-reduce")
    yes.append(op_event(SimpleNamespace(
        name="%fusion.9 = bf16[8,6144]{1,0} all-reduce-done(%ars)", start_ns=0, end_ns=1)))
    assert all(map(is_collective, yes))
    assert not any(map(is_collective, no))


def test_collective_time_averaged_over_chips():
    # two chips: chip 0 all-reduces 10 ms of its 40 busy, chip 1 20 of 40
    # (a start/done pair, the done half partly outside the window)
    w = (0.0, 100 * MS)
    chip0 = [Event("fusion.1", 0, 30 * MS), Event("%all-reduce.3", 30 * MS, 40 * MS)]
    chip1 = [Event("fusion.1", 0, 20 * MS),
             Event("all-reduce-start.3", 20 * MS, 25 * MS, kind="all-reduce-start"),
             Event("all-reduce-done.3", 85 * MS, 115 * MS, kind="all-reduce-done")]
    t = summarize(w, [chip0, chip1], [[], []], [])
    assert t.busy_s == pytest.approx(0.040)
    assert t.collective_s == pytest.approx(0.015)
    assert t.collectives == pytest.approx({"%all-reduce.3": 0.005,
                                           "all-reduce-start.3": 0.0025,
                                           "all-reduce-done.3": 0.0075})
    from chipbench import manifest
    read = manifest.metric_reader("collective_share.chat-tp4")
    assert read(type("R", (), {"trace": t})) == pytest.approx(37.5)
    assert summarize(*fixture()).collective_s == 0.0
    assert read(type("R", (), {"trace": summarize(*fixture())})) is None
