"""The scope split of a trace on a hand-built fixture, the host-work reader
on the engine's scope events, and the engine calls the harness reads, with
the engine's host scopes on and off."""

import contextlib
import types

import jax
import pytest

import bench_tiny  # noqa: F401
from chipbench import manifest, scope_reduce, trace_reduce
from chipbench.common import Run, dims_of
from chipbench.engine_calls import model_calls
from chipbench.serve_cell import _engine_events
from chipbench.trace_reduce import Event

MS = 1e6   # ns
SCOPES = ("attention", "kv_write", "mlp", "logits", "sample")
DEC = "jit(decode_step)/while/body"


def fixture():
    # window 0..100 ms. decode_step 10-50 ms is one layer-scan while op
    # enclosing its body ops; sample_tokens 60-70 ms; one op runs past the
    # window's end.
    ops = [("while.13", 10, 50, "jit(decode_step)/while"),
           ("fusion.1", 10, 20, f"{DEC}/attention/dot_general"),
           ("fusion.2", 15, 25, f"{DEC}/attention/exp"),
           ("fusion.3", 25, 30, f"{DEC}/kv_write/scatter"),
           ("fusion.4", 30, 45, f"{DEC}/mlp/dot_general"),
           ("copy.5", 45, 50, ""),
           ("fusion.6", 60, 70, "jit(sample_tokens)/sample/argmax"),
           ("fusion.7", 95, 120,
            "jit(train_step)/transpose(jvp(attention))/dot_general")]
    mods = [Event("jit_decode_step(1)", 10 * MS, 50 * MS),
            Event("jit_sample_tokens(2)", 60 * MS, 70 * MS),
            Event("jit_train_step(3)", 95 * MS, 120 * MS)]
    program = {"fusion.6": 2, "fusion.7": 3}
    paths = {(program.get(n, 1), n): p for n, _, _, p in ops if p}
    host = [Event("engine.step", 5 * MS, 90 * MS),          # the harness's
            Event("engine.step", 6 * MS, 89 * MS),          # the program's
            Event("engine.decode.launch", 8 * MS, 9 * MS),
            Event("engine.sync", 50 * MS, 58 * MS),
            Event("engine.account", 72 * MS, 80 * MS),
            Event("client.collect", 90 * MS, 93 * MS)]
    events = [Event(n, a * MS, b * MS) for n, a, b, _ in ops]
    return (0.0, 100 * MS), [events], [mods], host, [paths]


def test_scopes_count_nested_ops_once():
    w, ops, mods, host, paths = fixture()
    s = scope_reduce.summarize(w, ops, mods, host, paths, SCOPES)
    dec = s.scopes["decode_step"]
    assert dec["attention"] == pytest.approx(0.015)    # 10-25, not 20
    assert dec["kv_write"] == pytest.approx(0.005)
    assert dec["mlp"] == pytest.approx(0.015)
    assert "while" not in dec and "sample" not in dec
    assert s.scopes["sample_tokens"] == {"sample": pytest.approx(0.010)}
    # the backward pass's transpose(jvp(attention)) is attention, clipped
    assert s.scopes["train_step"] == {"attention": pytest.approx(0.005)}
    # the scan's while op has a path but no scope; copy.5 has no path
    assert s.outside == {"decode_step": {"while": pytest.approx(0.040)}}
    assert s.unscoped_ops == 1


def test_old_fields_unchanged_and_gaps_get_program_spans():
    w, ops, mods, host, paths = fixture()
    harness = [h for h in host if h.name in trace_reduce.HARNESS_SPANS]
    old = trace_reduce.summarize(w, ops, mods, harness)
    new = scope_reduce.summarize(w, ops, mods, host, paths, SCOPES).trace
    assert (new.busy_s, new.programs, new.ops) == (old.busy_s, old.programs,
                                                   old.ops)
    assert sum(new.idle_by_span.values()) == pytest.approx(
        sum(old.idle_by_span.values()))
    assert sum(new.idle_by_span.values()) == pytest.approx(
        old.window_s - old.busy_s)
    # 0-10: mid 5 (the harness's step starts at 5); 50-60: in the sync;
    # 70-95: mid 82.5, the program's engine.step
    assert new.idle_by_span == pytest.approx(
        {"engine.step": 0.010 + 0.025, "engine.sync": 0.010})
    assert old.idle_by_span == pytest.approx({"engine.step": 0.045})
    s = scope_reduce.summarize(w, ops, mods, host, paths, SCOPES)
    assert s.host_s == pytest.approx({"engine.decode.launch": 0.001,
                                      "engine.sync": 0.008,
                                      "engine.account": 0.008})


def test_scope_parts_strip_wrappers():
    parts = scope_reduce.scope_parts(
        "jit(train_step)/while/body/checkpoint/transpose(jvp(mlp))/dot")
    assert {"train_step", "while", "mlp", "dot"} <= parts
    assert scope_reduce.scope_parts(
        "jit(train_step)/transpose(jvp())/while/body/attention/exp") >= {
            "attention", "while"}
    assert scope_reduce.scope_parts("") == set()


def scope_ev(i, name, t, dur, parent=-1):
    return {"kind": "scope", "name": name, "t": t, "ts": t, "dur": dur,
            "id": i, "parent": parent, "step": 1}


def test_step_host_ms_reads_scope_events_only():
    read = manifest.metric_reader("step_host_ms.chat")
    run = Run(cell=None, seed=0, seconds=1.0, peaks={}, window=(10.0, 20.0))
    assert read(run) is None                     # untraced: no events
    run.engine_events = [{"kind": "step", "name": "decode", "t": 11.0,
                          "dur": 0.05, "tokens": 2, "kv_rows": 10}]
    assert read(run) is None                     # a program without scopes
    run.engine_events += [
        scope_ev(1, "engine.sync", 11.01, 0.06, parent=0),
        scope_ev(3, "engine.sync", 11.11, 0.002, parent=2),
        scope_ev(2, "engine.account", 11.1, 0.005, parent=0),
        scope_ev(0, "engine.step", 11.0, 0.08),
        scope_ev(4, "engine.step", 12.0, 0.03),
        scope_ev(5, "engine.step", 9.0, 0.5),     # before the window
    ]
    # host work 0.08 - 0.06 - 0.002 = 18 ms and 30 ms
    assert read(run) == pytest.approx(24.0)


def _served_calls(monkeypatch, scoped: bool):
    from repro.serve import ServingEngine
    from repro.telemetry import trace

    if not scoped:     # a scope that records nothing and annotates nothing
        monkeypatch.setattr(
            trace.Tracer, "scope", lambda self, name, **args:
            contextlib.nullcontext(types.SimpleNamespace(args={})))
    cell = bench_tiny.tiny_cell("granite-3-2b.chat")
    from chipbench.common import program_model

    model = program_model(cell.config)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        page_size=8, chunk_size=16, prefill_bucket=16,
                        trace=True)
    for n, m in ((40, 5), (7, 6), (23, 3)):
        eng.submit(list(range(1, n + 1)), max_new_tokens=m)
    eng.run()
    run = types.SimpleNamespace(dims=dims_of(model.cfg),
                                engine_events=_engine_events(eng))
    kinds = {e["kind"] for e in run.engine_events}
    assert ("scope" in kinds) == scoped
    return [(c.kind, c.flops, c.nbytes) for c in model_calls(run)]


def test_engine_calls_unchanged_by_host_scopes(monkeypatch):
    with_scopes = _served_calls(monkeypatch, scoped=True)
    without = _served_calls(monkeypatch, scoped=False)
    assert with_scopes == without
    assert {k for k, _, _ in with_scopes} == {"decode", "prefill"}


def _pb(*fields) -> bytes:
    """Protobuf encoding of (field number, int | bytes | str) pairs."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_paths_from_event_metadata(tmp_path):
    # a TPU trace's op event names the op's HLO instruction; its event
    # metadata carries the program id and the op_name as tf_op
    stat_meta = [(5, _pb((1, k), (2, _pb((1, k), (2, name)))))
                 for k, name in ((3, "tf_op"), (4, "program_id"),
                                 (5, "flops"))]

    def op(mid, name, tf_op, program):
        meta = _pb((1, mid), (2, name),
                   (5, _pb((1, 3), (5, tf_op))), (5, _pb((1, 4), (3, program))),
                   (5, _pb((1, 5), (4, 1024))))
        return (4, _pb((1, mid), (2, meta)))

    device = _pb((1, 7), (2, "/device:TPU:0"), *stat_meta,
                 op(1, "%fusion.162 = s32[8]{0} fusion(s32[8]{0} %copy.91)",
                    "jit(decode_step)/while/body/attention/gather:", 977),
                 op(2, "%copy.91 = s32[8]{0} copy(s32[8]{0} %p)",
                    "jit(decode_step)/while/body/dynamic_slice:", 977),
                 (4, _pb((1, 3), (2, _pb((1, 3), (2, "%no_stats"))))))
    host = _pb((2, "/host:CPU"), *stat_meta,
               op(1, "%fusion.1", "jit(f)/x:", 5))
    (tmp_path / "t.xplane.pb").write_bytes(_pb((1, host), (1, device)))
    assert scope_reduce.op_paths(str(tmp_path / "t.xplane.pb")) == {
        "/device:TPU:0": {
            (977, "%fusion.162"): "jit(decode_step)/while/body/attention/gather",
            (977, "%copy.91"): "jit(decode_step)/while/body/dynamic_slice"}}
