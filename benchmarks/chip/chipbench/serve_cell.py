"""Serving cells: an open-loop client in front of
``ServingEngine``, timed from the client's side.

Set-up makes the weights from the seed, builds the engine with the mix's
settings, compiles every step shape the mix can reach, and runs the mix's
warm-up traffic. The window then submits requests as they fall due, steps
the engine, and stamps every token when the host receives it. After the
window the requests due in it are followed to completion (under a drain
cap) while the tail traffic keeps the load on. Once the engine is freed,
a sample of the finished requests, drawn from the seed and holding the
longest one, is checked against the plain float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, traffic
from chipbench.common import (Run, annotate, device_peak_bytes, dims_of,
                              program_model, profile_window)
from chipbench.weights import make_params
from chipbench.windows import prefill_gap_share, window_requests


@dataclasses.dataclass
class ReqRecord:
    req: traffic.ServeRequest
    due: float                  # host clock
    submit: float = 0.0
    rid: int = -1
    times: list = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False


def warm_step_shapes(eng, mix: dict) -> int:
    """Compile every program shape the window can reach: the packed and
    chunked prefill calls at each query width the token budget allows and
    each kv width up to ``warm_max_kv``, the pool scatter, the layout
    statistics, and the sampling of 1..``warm_max_rows`` prefill rows and
    of a full decode batch. Pool writes all go to an out-of-range page and
    are dropped. Returns the number of shapes driven."""
    from repro.core.masks import POS_PAD, SEG_PAD_KV, SEG_PAD_Q

    E = mix["engine"]
    bucket, ps = eng.prefill_bucket, eng.page_size
    budget = eng.scheduler.cfg.effective_budget
    widths = range(bucket, budget + (-budget) % bucket + 1, bucket)
    P = eng.kv.num_pages
    n = 0
    for sq in widths:
        toks = jnp.zeros((1, sq), jnp.int32)
        qseg = np.full((1, sq), SEG_PAD_Q, np.int32)
        qseg[0, 0] = 0
        drop = jnp.full((sq,), P, jnp.int32)
        zero = jnp.zeros((sq,), jnp.int32)
        caches, logits = eng._prefill_packed(
            eng.params, {"tokens": toks, "segment_ids": jnp.asarray(qseg)})
        eng.state["caches"] = eng._scatter(eng.state["caches"], caches, drop, zero)
        eng._record_layout_stats(qseg)
        for k in range(1, int(E["warm_max_rows"]) + 1):
            rows = jnp.stack([logits[0, 0]] * k)
            eng._sample_rows(rows, [None] * k)
        n += 2
        for sk in range(eng.chunk_kv_bucket, int(E["warm_max_kv"]) + 1,
                        eng.chunk_kv_bucket):
            batch = {"tokens": toks, "q_segment_ids": jnp.asarray(qseg),
                     "q_positions": jnp.asarray(np.where(qseg == 0, 0, POS_PAD)),
                     "kv_segment_ids": jnp.full((1, sk), SEG_PAD_KV, jnp.int32),
                     "kv_positions": jnp.full((1, sk), POS_PAD, jnp.int32),
                     "dest_page": drop, "dest_off": zero,
                     "page_list": jnp.full((1, sk // ps), -1, jnp.int32)}
            caches, logits = eng._prefill_chunk(eng.params, batch,
                                                eng.state["caches"])
            eng.state["caches"] = caches
            logits[0, 0].block_until_ready()
            n += 1
    eng._sample_rows(jnp.zeros((eng.B, eng.model.cfg.vocab_size), jnp.float32),
                     [None] * eng.B)
    return n


def run_serve(cell, seed: int, seconds: float, trace: bool, run: Run,
              trace_dir: str | None = None) -> None:
    """Drive one serving run; fills ``run`` (records, device, check)."""
    from repro.serve import ServingEngine

    mix, E = cell.mix, cell.mix["engine"]
    model = program_model(cell.config)
    cfg = model.cfg
    run.dims = dims_of(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_params(shapes, seed)
    eng = ServingEngine(model, params, num_slots=E["lanes"],
                        capacity=E["capacity"], page_size=E["page_size"],
                        chunk_size=E["chunk_size"],
                        prefill_bucket=E["prefill_bucket"],
                        num_pages=E.get("num_pages"), trace=trace)
    run.notes["num_pages"] = eng.kv.num_pages
    run.notes["warm_shapes"] = warm_step_shapes(eng, mix)
    sched = traffic.serve_schedule(mix, seed, seconds, cfg.vocab_size)
    warm_s = float(mix["arrivals"]["warmup_s"])
    clock = time.perf_counter
    t0 = clock() + warm_s
    t1 = t0 + seconds
    drain_end = t1 + float(mix["drain_cap_s"])
    pending = list(reversed(sched))          # pop() takes the next due
    recs: list[ReqRecord] = []
    live: dict[int, ReqRecord] = {}
    steps = []
    prof = profile_window(trace_dir, t0, seconds, float(mix["trace_s"])) \
        if trace else None

    def submit(r: traffic.ServeRequest, due: float) -> None:
        rec = ReqRecord(r, due)
        rec.rid = eng.submit(r.prompt, max_new_tokens=r.max_new)
        rec.submit = clock()
        recs.append(rec)
        live[rec.rid] = rec

    def window_left() -> bool:
        """Requests due in the window that are not submitted or not
        finished."""
        return (any(not r.done for r in recs if r.req.segment == "window")
                or any(p.segment == "window" for p in pending))

    while True:
        now = clock()
        if prof is not None:
            prof.tick(now)
        if now >= t1 and (not window_left() or now >= drain_end):
            break
        with annotate("traffic.submit"):
            while pending and t0 + pending[-1].due <= now:
                r = pending.pop()
                submit(r, t0 + r.due)
        if eng.scheduler.idle():
            if not pending:
                break
            time.sleep(max(0.0, min(t0 + pending[-1].due - clock(), 0.01)))
            continue
        ts = clock()
        with annotate("engine.step"):
            eng.step()
        te = clock()
        with annotate("client.collect"):
            gen = 0
            for rid in list(live):
                rec = live[rid]
                req = eng.requests[rid]
                k = len(req.output) - len(rec.times)
                if k > 0:
                    rec.times += [te] * k
                    gen += k
                if req.done:
                    rec.done = True
                    rec.failed = len(req.output) < rec.req.max_new
                    del live[rid]
            st = eng.last_step_stats
            steps.append((ts, te, st["prefill_tokens"], gen, st["active"]))
            if te >= t1 and "queued_at_window_end" not in run.notes:
                run.notes["queued_at_window_end"] = st["queued"]
    if prof is not None:
        prof.close()
        run.trace_window = tuple(prof.host) if prof.host else None
    for rec in recs:
        if not rec.done:
            rec.failed = True
    run.window = (t0, t1)
    run.requests = recs
    run.steps = steps
    run.notes["gap_prefill_share"] = prefill_gap_share(run)
    run.engine_events = _engine_events(eng) if trace else None
    in_window = window_requests(run)
    run.attempted = len(in_window)
    run.failed = sum(r.failed for r in in_window)
    run.device["memory_peak_bytes"] = device_peak_bytes()

    sample = _check_sample(in_window, eng, seed, mix["check"])
    del eng, live
    gc.collect()
    run.check_inputs = sample
    run.params = params


def _engine_events(eng) -> list[dict]:
    """The engine tracer's events with ``t`` on the harness's clock."""
    tr = eng.tm.tracer
    base = getattr(tr, "_t0", 0.0)
    return [dict(ev, t=base + ev["ts"]) for ev in tr.events]


def _check_sample(window_recs, eng, seed: int, spec: dict):
    """(prompt, served tokens) of finished requests, drawn from the seed:
    the longest first, then others until ``served_tokens`` are in or
    ``max_requests`` are taken."""
    ok = [r for r in window_recs if r.done and not r.failed]
    if not ok:
        return []
    ok.sort(key=lambda r: len(r.req.prompt) + r.req.max_new, reverse=True)
    rest = ok[1:]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    pick = [ok[0]] + [rest[i] for i in order]
    out, served = [], 0
    for r in pick:
        if served >= spec["served_tokens"] or len(out) >= spec["max_requests"]:
            break
        toks = list(eng.requests[r.rid].output)
        out.append((list(r.req.prompt), toks))
        served += len(toks)
    return out


def logit_gap_readings(params, sample, arch, control: str | None = None):
    """Per served token, how far its float32-reference logit lies below the
    reference's best. With ``control``, the same at the tokens that the
    reference computed in that lower precision ranks first."""
    prog, ctrl = [], []
    for prompt, served in sample:
        seq = prompt + served[:-1]
        rows = jnp.arange(len(prompt) - 1, len(seq), dtype=jnp.int32)
        ref = reference.serve_logits(params, seq, arch)
        prog.append(np.asarray(reference.logit_gaps(
            ref, rows, jnp.asarray(served, jnp.int32))))
        if control is not None:
            low = reference.serve_logits(params, seq, arch, precision=control)
            pick = reference.row_argmax(low, rows)
            del low
            ctrl.append(np.asarray(reference.logit_gaps(ref, rows, pick)))
        del ref
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,))
    return cat(prog), cat(ctrl)
