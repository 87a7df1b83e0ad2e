"""Telemetry subsystem tests (DESIGN.md §15): metrics-registry semantics,
trace schema + lifecycle reconstruction (including a forced
preemption→resume under page pressure and prefix-cache hits), IO-ledger
pricing, disabled-mode zero-allocation, and back-compat of the engine's
pre-existing counter attributes (now registry views)."""

import json
import tracemalloc

import jax
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import ServingEngine
from repro.telemetry import (IOLedger, MetricsRegistry, ServePriceModel,
                             Tracer, chrome_trace_doc, percentile)
from repro.telemetry.validate import validate_chrome_trace


# --------------------------------------------------------------- registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(3)
    assert c.value() == 4
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("active")
    assert g.value() == 0.0
    g.set(2)
    g.max_update(1)          # lower: no-op
    assert g.value() == 2
    g.max_update(5)
    assert g.value() == 5

    h = reg.histogram("lat_s")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(0.111)
    assert h.samples() == [0.001, 0.01, 0.1]


def test_labeled_counter_series_and_total():
    reg = MetricsRegistry()
    c = reg.counter("preempt", labels=("reason",))
    c.inc(reason="starvation")
    c.inc(2, reason="pool-exhaustion")
    assert c.value(reason="starvation") == 1
    assert c.total() == 3
    with pytest.raises(ValueError):
        c.inc()              # labelled metric requires its labels
    with pytest.raises(ValueError):
        c.inc(cause="x")     # wrong label name


def test_registry_rejects_kind_and_label_conflicts():
    reg = MetricsRegistry()
    reg.counter("n", labels=("k",))
    with pytest.raises(ValueError):
        reg.gauge("n")
    with pytest.raises(ValueError):
        reg.counter("n", labels=("other",))
    assert reg.get("n") is not None and reg.get("missing") is None


def test_snapshot_and_delta():
    reg = MetricsRegistry()
    c = reg.counter("toks")
    g = reg.gauge("occ")
    h = reg.histogram("t_s")
    c.inc(10)
    g.set(0.5)
    h.observe(0.2)
    snap = reg.snapshot()
    assert snap["toks"]["series"][""] == 10
    c.inc(5)
    g.set(0.9)
    h.observe(0.3)
    d = reg.delta(snap)
    assert d["toks"]["series"][""] == 5          # counters diff
    assert d["occ"]["series"][""] == 0.9         # gauges pass through
    assert d["t_s"]["series"][""]["count"] == 1
    assert "toks" in reg.table()


def test_histogram_buckets_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("d", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    bc = h.bucket_counts()                   # cumulative, Prometheus-style
    assert bc["le=1"] == 1 and bc["le=2"] == 2
    assert bc["le=4"] == 3 and bc["le=+Inf"] == 4
    assert h.percentile(50) == pytest.approx(2.25)


def test_percentile_edge_cases():
    # the single shared implementation behind engine.latency_stats()
    assert percentile([], 50) == 0.0
    assert percentile([], 95) == 0.0
    assert percentile([0.7], 50) == pytest.approx(0.7)
    assert percentile([0.7], 95) == pytest.approx(0.7)
    reg = MetricsRegistry()
    h = reg.histogram("x")
    assert h.percentile(95) == 0.0               # empty histogram
    h.observe(1.25)
    assert h.percentile(50) == pytest.approx(1.25)


# ------------------------------------------------------------------ trace
def test_tracer_disabled_records_nothing_and_allocates_nothing():
    tr = Tracer(enabled=False)
    # the call-site contract guards with `if tr.enabled:` — but even the
    # unguarded call must early-return without touching the buffer; a
    # disabled scope is one shared no-op context.
    assert tr.scope("engine.step") is tr.scope("engine.sync", step=1)
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    for i in range(10_000):
        if tr.enabled:
            tr.event("req", "submit", rid=i)
            tr.span("step", "decode", 0.0, 1.0, step=i)
        with tr.scope("engine.step"):
            with tr.scope("engine.sync"):
                pass
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert tr.events == []
    assert after - before < 16_384          # no per-emit retention


def test_tracer_scopes_nest_and_append_on_exit():
    tr = Tracer(enabled=True)
    tr.step = 7
    with tr.scope("engine.step") as outer:
        with tr.scope("engine.plan"):
            pass
        with tr.scope("engine.sync", rows=3):
            pass
        outer.args["active"] = 2
    inner, sync, step = tr.events            # appended as each one exits
    assert [e["name"] for e in tr.events] == [
        "engine.plan", "engine.sync", "engine.step"]
    assert all(e["kind"] == "scope" and e["step"] == 7 for e in tr.events)
    assert step["parent"] == -1 and step["active"] == 2
    assert inner["parent"] == sync["parent"] == step["id"]
    assert sync["rows"] == 3
    for child in (inner, sync):
        assert step["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= step["ts"] + step["dur"]
    doc = chrome_trace_doc(tr.events)
    host = [e for e in doc["traceEvents"] if e.get("cat") == "scope"]
    assert len(host) == 3 and {e["tid"] for e in host} == {6}
    assert all(e["ph"] == "X" and e["pid"] == 1 for e in host)


def test_tracer_event_and_span_shapes():
    tr = Tracer(enabled=True)
    tr.event("req", "submit", rid=0, prompt_len=3)
    tr.span("step", "decode", 0.0, 0.5, step=1, hbm_bytes=64)
    assert len(tr.events) == 2
    ev, sp = tr.events
    assert ev["kind"] == "req" and ev["rid"] == 0 and "ts" in ev
    assert sp["dur"] == 0.5 and sp["hbm_bytes"] == 64


def test_chrome_trace_doc_roundtrips_and_validates(tmp_path):
    tr = Tracer(enabled=True)
    tr.event("req", "submit", rid=0, prompt_len=4)
    tr.event("req", "admit", rid=0, lane=0, cached=0)
    tr.span("step", "prefill_zero", 0.001, 0.01, step=1, lanes=1,
            tokens=4, hbm_bytes=1024)
    tr.event("req", "first_token", rid=0, ttft_s=0.02)
    tr.span("step", "decode", 0.02, 0.005, step=2, lanes=1, tokens=1,
            hbm_bytes=512)
    tr.event("req", "finish", rid=0, reason="eos", n_output=1)
    doc = chrome_trace_doc(tr.events)
    assert validate_chrome_trace(doc) == []
    p = tmp_path / "t.json"
    n = tr.to_chrome_trace(str(p))
    assert json.loads(p.read_text())["traceEvents"] and n > 0


def test_validator_flags_broken_traces():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    # a step span without its hbm_bytes prediction must be flagged
    doc = {"traceEvents": [
        {"name": "prefill_zero", "ph": "X", "cat": "step", "pid": 1,
         "tid": 0, "ts": 0, "dur": 5, "args": {}},
    ]}
    probs = validate_chrome_trace(doc)
    assert any("hbm_bytes" in p for p in probs)


# -------------------------------------------------------------- io ledger
def _price():
    return ServePriceModel(d=32, heads_q=4, heads_kv=1, d_model=128,
                           layers=2, elt=4, block_q=64, block_k=64,
                           kv_major=True)


def test_price_model_prefill_and_decode_bytes():
    pm = _price()
    b1 = pm.prefill_bytes([(0, 64)])
    b2 = pm.prefill_bytes([(0, 128)])
    assert 0 < b1 < b2                       # monotone in prefill length
    d1 = pm.decode_bytes([16])
    d2 = pm.decode_bytes([16, 64])
    assert 0 < d1 < d2                       # per-lane KV stream dominates
    assert pm.decode_bytes(iter([16])) == d1  # generator input is safe


def test_ledger_accounting_and_prefix_credit():
    led = IOLedger(price=_price())
    led.account("decode", hbm_bytes=1000, wall_s=0.1, tokens=4)
    led.account("prefill_zero", hbm_bytes=3000, wall_s=0.2, tokens=16)
    led.account("prefix_saved", hbm_bytes=500, tokens=8)
    assert led.total_bytes() == 4000         # credits excluded
    assert led.total_tokens() == 20
    assert led.bytes_per_token() == pytest.approx(200.0)
    s = led.summary()
    assert s["decode"]["implied_gb_per_s"] == pytest.approx(1e-5, rel=1e-3)
    assert "prefill_zero" in led.table()


# --------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_preemption_resume_lifecycle_in_trace(setup):
    """Page pressure forces a preemption; the exported trace must
    reconstruct the full lifecycle of every request, including the
    preempted→resumed prefill of the victim (the §15 acceptance
    scenario)."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=32,
                        paged=True, page_size=8, chunk_size=8,
                        token_budget=18, num_pages=4, trace=True)
    eng.submit(list(range(1, 25)), max_new_tokens=5)
    eng.submit(list(range(30, 54)), max_new_tokens=5)
    done = eng.run()
    assert len(done) == 2 and eng.preemptions >= 1

    names = [(e["kind"], e["name"]) for e in eng.tm.tracer.events]
    assert ("req", "preempt") in names
    assert names.count(("req", "finish")) == 2
    resumed = [e for e in eng.tm.tracer.events
               if e["kind"] == "req" and e["name"] == "resume"]
    assert resumed, "preempted request never re-admitted as a resume"

    doc = chrome_trace_doc(eng.tm.tracer.events)
    assert validate_chrome_trace(doc) == []
    # every executed step span carries its io_model byte prediction
    steps = [e for e in doc["traceEvents"]
             if e.get("cat") == "step" and e.get("ph") == "X"]
    assert steps
    assert all(e["args"]["hbm_bytes"] >= 0 for e in steps)
    # scheduler recorded WHY: reasons live on the labelled counters
    snap = eng.tm.registry.snapshot()
    assert sum(snap["sched_preemptions"]["series"].values()) >= 1
    assert eng.tm.ledger.total_bytes() > 0
    assert eng.tm.ledger.by_kind["decode"]["tokens"] > 0


def test_engine_scopes_nest_and_sync_inside_step(setup):
    """A traced engine's host spans: every scope lies inside its parent,
    every engine.sync inside an engine.step, which carries the step's
    stats; constant arguments are one meta event, not per span."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        paged=True, page_size=8, chunk_size=8,
                        token_budget=12, trace=True)
    eng.submit(list(range(1, 20)), max_new_tokens=4)
    eng.submit(list(range(30, 41)), max_new_tokens=3)
    eng.run()
    evs = eng.tm.tracer.events
    scopes = {e["id"]: e for e in evs if e["kind"] == "scope"}
    names = {e["name"] for e in scopes.values()}
    assert {"engine.step", "engine.plan", "engine.prefill.build",
            "engine.prefill.launch", "engine.decode.upload",
            "engine.decode.launch", "engine.sync",
            "engine.account"} <= names
    eps = 1e-9
    for e in scopes.values():
        if e["parent"] < 0:
            continue
        p = scopes[e["parent"]]
        assert p["ts"] - eps <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + eps
        assert e["step"] == p["step"]

    def root(e):
        while e["parent"] >= 0:
            e = scopes[e["parent"]]
        return e

    syncs = [e for e in scopes.values() if e["name"] == "engine.sync"]
    assert syncs and all(root(e)["name"] == "engine.step" for e in syncs)
    steps = [e for e in scopes.values() if e["name"] == "engine.step"]
    assert [e["step"] for e in steps] == list(range(1, len(steps) + 1))
    assert all({"active", "prefill_tokens", "decode_tokens", "queued"}
               <= set(e) for e in steps)
    assert all("census" not in e and "tiles" not in e
               for e in evs if e["kind"] == "step")
    meta = [e for e in evs if e["kind"] == "meta"]
    assert len(meta) == 1 and set(meta[0]) >= {"census", "tiles"}
    assert not any(e["kind"] == "stepsum" for e in evs)
    assert validate_chrome_trace(chrome_trace_doc(evs)) == []


def test_tok_latency_is_the_gap_a_stream_sees(setup):
    """serve_tok_latency_s observes one gap per token after a request's
    first, from one token's arrival to the next, prefill steps between
    them included."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        paged=True, page_size=8, chunk_size=8,
                        token_budget=10)
    eng.submit(list(range(1, 6)), max_new_tokens=6)
    eng.submit(list(range(30, 60)), max_new_tokens=2)   # 4 chunks, later
    done = eng.run()
    gaps = eng.tok_latencies
    assert len(gaps) == sum(len(r.output) - 1 for r in done)
    first = done[0] if done[0].rid == 0 else done[1]
    assert sum(gaps) == pytest.approx(
        sum(r.t_last - r.t_first for r in done))
    assert first.t_last > first.t_first


def test_prefix_hit_annotated_and_credited(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        paged=True, page_size=8, prefix_cache=True,
                        trace=True)
    prompt = list(range(1, 17))              # two full pages
    eng.submit(prompt, max_new_tokens=3)
    eng.run()                                # publishes the prefix pages
    eng.submit(prompt, max_new_tokens=3)
    done = eng.run()
    assert eng.prefix_hits >= 1
    hits = [e for e in eng.tm.tracer.events
            if e["kind"] == "req" and e["name"] == "prefix_hit"]
    assert hits and hits[0]["cached_tokens"] > 0
    saved = eng.tm.ledger.by_kind.get("prefix_saved")
    assert saved and saved["hbm_bytes"] > 0
    # the credit never inflates the moved-bytes total
    assert all(r.output == done[0].output for r in done)


def test_engine_counter_backcompat_views(setup):
    """Every pre-existing ad-hoc counter attribute survives as a
    registry-backed read-only view with unchanged types/semantics."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        paged=True, page_size=16)
    assert eng.last_step_stats == {}         # before any step
    for p in ([1, 2, 3], [4, 5, 6, 7], [8, 9]):
        eng.submit(p, max_new_tokens=3)
    eng.run()
    assert eng.prefill_calls >= 1 and isinstance(eng.prefill_calls, int)
    assert eng.decode_calls >= 3
    assert eng.preemptions == 0
    assert eng.peak_active >= 2
    assert eng.blocks_total >= 0 and eng.blocks_skipped >= 0
    assert 0.0 < eng.last_prefill_layout_density <= 1.0
    assert len(eng.ttfts) == 3               # one per request
    assert len(eng.tok_latencies) >= 6
    stats = eng.latency_stats()
    for k in ("ttft_p50", "ttft_p95", "tok_latency_p50",
              "tok_latency_p95"):
        assert stats[k] > 0
    s = eng.last_step_stats
    assert set(s) >= {"active", "occupancy", "pool_utilization",
                      "prefill_tokens", "decode_tokens", "queued"}
    # kv pool counters are registry views too
    assert eng.kv.alloc_events >= 1 and eng.kv.peak_in_use >= 1
    # tracing stayed off: no event buffer, no step spans
    assert eng.tm.tracer.events == []


def test_scheduler_defer_reasons_recorded(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=32,
                        paged=True, page_size=8, chunk_size=8,
                        token_budget=10, num_pages=16)
    eng.submit(list(range(1, 25)), max_new_tokens=3)
    eng.submit(list(range(30, 54)), max_new_tokens=3)
    eng.run()
    c = eng.tm.registry.get("sched_deferred_chunks")
    assert c is not None and c.total() >= 1
    assert c.value(reason="budget-exhausted") >= 1
