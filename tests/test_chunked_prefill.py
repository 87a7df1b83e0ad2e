"""Chunked prefill end-to-end (DESIGN.md §10): position-based masking
(per-segment q_offset) at the kernel/oracle level, engine-level
token-identity across chunk sizes, decode/prefill interleaving, preemption
at chunk boundaries (greedy AND seeded sampling), and sampling-key
persistence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import masks as M
from repro.kernels import ops
from repro.kernels.ref import chunked_attention, standard_attention
from repro.models import build_model
from repro.serve import SamplingParams, ServingEngine


# ---------------------------------------------------------------------------
# mask IR: traced positions (per-segment q_offset)
# ---------------------------------------------------------------------------

def _packed_chunk_case(hists, chunks, d=16, hq=4, hkv=2, seed=0):
    """Packed suffix-chunk attention fixture: q = the chunks, kv = each
    segment's full prefix; returns arrays + the per-segment brute force."""
    rng = np.random.default_rng(seed)
    Sq = sum(chunks)
    Sk = sum(h + c for h, c in zip(hists, chunks))
    q = rng.standard_normal((1, hq, Sq, d)).astype(np.float32)
    k = rng.standard_normal((1, hkv, Sk, d)).astype(np.float32)
    v = rng.standard_normal((1, hkv, Sk, d)).astype(np.float32)
    qseg = np.concatenate([[i] * c for i, c in enumerate(chunks)])[None]
    kseg = np.concatenate([[i] * (h + c)
                           for i, (h, c) in enumerate(zip(hists, chunks))])[None]
    qpos = np.concatenate([np.arange(h, h + c)
                           for h, c in zip(hists, chunks)])[None]
    kpos = np.concatenate([np.arange(h + c)
                           for h, c in zip(hists, chunks)])[None]

    outs, qo, ko = [], 0, 0
    for h, c in zip(hists, chunks):
        o = standard_attention(jnp.asarray(q[:, :, qo:qo + c]),
                               jnp.asarray(k[:, :, ko:ko + h + c]),
                               jnp.asarray(v[:, :, ko:ko + h + c]),
                               causal=True)      # scalar q_offset = h
        outs.append(np.asarray(o))
        qo += c
        ko += h + c
    ref = np.concatenate(outs, axis=2)
    arrs = dict(q_segment_ids=jnp.asarray(qseg, jnp.int32),
                kv_segment_ids=jnp.asarray(kseg, jnp.int32),
                q_positions=jnp.asarray(qpos, jnp.int32),
                kv_positions=jnp.asarray(kpos, jnp.int32))
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), arrs, ref


def test_positions_match_per_segment_offsets_all_impls():
    """One packed call with traced positions == per-segment scalar-q_offset
    calls, for the oracle, the chunked XLA path, and the Pallas kernel."""
    q, k, v, arrs, ref = _packed_chunk_case([5, 2], [3, 4])
    o_std = standard_attention(q, k, v, causal=True, **arrs)
    o_chk = chunked_attention(q, k, v, causal=True, chunk_size=4, **arrs)
    o_fa = ops.flash_attention(q, k, v, causal=True, block_q=4, block_k=4,
                               **arrs)
    np.testing.assert_allclose(np.asarray(o_std), ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_chk), ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_fa), ref, atol=1e-5)


def test_positions_kernel_grads_match_oracle():
    q, k, v, arrs, _ = _packed_chunk_case([4, 1], [4, 3])

    def f(fn):
        return jax.grad(lambda a, b, c: fn(a, b, c).sum(), argnums=(0, 1, 2))

    g_fa = f(lambda a, b, c: ops.flash_attention(
        a, b, c, causal=True, block_q=4, block_k=4, **arrs))(q, k, v)
    g_std = f(lambda a, b, c: standard_attention(
        a, b, c, causal=True, **arrs))(q, k, v)
    for a, b in zip(g_fa, g_std):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_position_block_layout_classes():
    """Range-based classes are sound and POS_PAD tails classify SKIP."""
    qpos = jnp.asarray([[8, 9, 10, 11]], jnp.int32)         # one chunk block
    kpos = jnp.asarray([[0, 1, 2, 3, 8, 9, 10, 11,
                         M.POS_PAD, M.POS_PAD, M.POS_PAD, M.POS_PAD]],
                       jnp.int32)
    lay = M.position_block_layout(qpos, kpos, 4, 4, causal=True)
    # history block: provably fully attended; diagonal block: partial;
    # padding block: provably skipped.
    assert lay.shape == (1, 1, 3)
    assert int(lay[0, 0, 0]) == M.BLOCK_FULL
    assert int(lay[0, 0, 1]) == M.BLOCK_PARTIAL
    assert int(lay[0, 0, 2]) == M.BLOCK_SKIP


def test_positions_validation():
    q = jnp.zeros((1, 2, 4, 8))
    k = jnp.zeros((1, 2, 8, 8))
    with pytest.raises(ValueError, match="together"):
        ops.flash_attention(q, k, k, causal=True,
                            q_positions=jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="kv_valid_len|positions"):
        M.MaskSpec(causal=True, kv_valid_len=8,
                   q_positions=jnp.zeros((1, 4), jnp.int32),
                   kv_positions=jnp.zeros((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# engine: chunked == atomic, token-identical (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


PROMPTS = [[5, 9, 2], [7, 7, 1, 4], [3], [11, 2], [8, 6, 5, 1, 9],
           list(range(1, 20))]           # includes a multi-chunk prompt


def _run(model, params, *, chunk=None, budget=None, slots=3, n_new=6,
         **kw):
    eng = ServingEngine(model, params, num_slots=slots, capacity=64,
                        paged=True, page_size=8, chunk_size=chunk,
                        token_budget=budget, **kw)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=n_new)
    done = eng.run()
    assert len(done) == len(PROMPTS)
    return {r.rid: r.output for r in done}, eng


@pytest.mark.parametrize("chunk", [None, 8])
def test_unrolled_layers_match_scanned_stack(setup, chunk):
    """``scan_layers=False`` runs the paged decode and chunk-prefill
    blocks with a Python layer index over the same carried pool: the same
    tokens and the same pool rows as the scanned stack."""
    cfg, model, params = setup
    unrolled = build_model(dataclasses.replace(cfg, scan_layers=False))
    blocks = [jax.tree.map(lambda p: p[l], params["blocks"])
              for l in range(cfg.num_layers)]
    out_scan, e_scan = _run(model, params, chunk=chunk)
    out_unr, e_unr = _run(unrolled, {**params, "blocks": blocks}, chunk=chunk)
    assert out_unr == out_scan
    for got, want in zip(jax.tree.leaves(e_unr.state["caches"]),
                         jax.tree.leaves(e_scan.state["caches"])):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_chunked_token_identical_across_chunk_sizes(setup):
    """Greedy outputs are identical for EVERY chunk size — including chunk
    sizes that divide prompts unevenly and a token budget that forces
    chunk deferral — because every chunk is exact attention over the same
    logical prefix the atomic prefill sees."""
    cfg, model, params = setup
    ref, e0 = _run(model, params, chunk=None)
    for chunk, budget in [(4, None), (7, None), (64, None), (5, 11)]:
        out, eng = _run(model, params, chunk=chunk, budget=budget)
        assert out == ref, f"chunk={chunk} budget={budget} diverged"
        assert eng.scheduler.chunks_emitted >= len(PROMPTS)
    # multi-chunk prompts mean strictly more prefill invocations
    _, e4 = _run(model, params, chunk=4)
    assert e4.prefill_calls > e0.prefill_calls


def test_decode_interleaves_with_long_prefill(setup):
    """Short requests decode while the long prompt is still mid-prefill —
    the no-head-of-line-blocking property, observed at the engine level."""
    cfg, model, params = setup
    long_p = list(range(1, 49))
    eng = ServingEngine(model, params, num_slots=3, capacity=64, paged=True,
                        page_size=8, chunk_size=8, token_budget=16)
    rid_long = eng.submit(long_p, max_new_tokens=4)
    eng.submit([5, 9, 2], max_new_tokens=6)
    eng.submit([7, 7, 1, 4], max_new_tokens=6)
    interleaved = 0

    def watch(e):
        long_mid_prefill = any(r is not None and r.rid == rid_long
                               and not r.output for r in e.slot_req)
        if long_mid_prefill and e.last_step_stats["decode_tokens"] > 0:
            nonlocal_count[0] += 1

    nonlocal_count = [0]
    done = eng.run(on_step=watch)
    assert len(done) == 3
    assert nonlocal_count[0] > 0, \
        "no decode step ran while the long prompt was mid-prefill"
    # and the outputs still match the unchunked engine
    ref = ServingEngine(model, params, num_slots=3, capacity=64, paged=True,
                        page_size=8)
    ref.submit(long_p, max_new_tokens=4)
    ref.submit([5, 9, 2], max_new_tokens=6)
    ref.submit([7, 7, 1, 4], max_new_tokens=6)
    assert {r.rid: r.output for r in ref.run()} == \
        {r.rid: r.output for r in done}


def test_chunked_requires_paged(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="dense|atomic"):
        ServingEngine(model, params, num_slots=2, capacity=64, paged=False,
                      chunk_size=8)
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(model, params, num_slots=2, capacity=64, paged=False,
                      token_budget=16)


# ---------------------------------------------------------------------------
# preemption at a chunk boundary -> identical resume (greedy and sampled)
# ---------------------------------------------------------------------------

P0 = list(range(1, 25))
P1 = list(range(30, 54))


def _pressure_engine(model, params, **kw):
    """Pool sized so two 24-token prompts cannot both finish prefill: the
    younger is evicted MID-PREFILL at a chunk boundary and re-prefills."""
    eng = ServingEngine(model, params, num_slots=2, capacity=32, paged=True,
                        page_size=8, chunk_size=8, token_budget=18,
                        num_pages=4, **kw)
    return eng


def test_mid_prefill_preemption_resumes_token_identical(setup):
    cfg, model, params = setup
    eng = _pressure_engine(model, params)
    eng.submit(P0, max_new_tokens=5)
    eng.submit(P1, max_new_tokens=5)
    done = {r.rid: r.output for r in eng.run()}
    assert eng.preemptions >= 1, "scenario no longer forces preemption"
    for rid, p in enumerate([P0, P1]):
        solo = ServingEngine(model, params, num_slots=1, capacity=32,
                             paged=True, page_size=8)
        solo.submit(p, max_new_tokens=5)
        assert done[rid] == solo.run()[0].output, f"rid {rid} diverged"


def test_mid_prefill_preemption_sampled_token_identical(setup):
    """The satellite invariant: preemption->resume stays token-identical
    UNDER SAMPLING, because the i-th token's key is fold_in(seed, i) —
    position-indexed, not state-carried."""
    cfg, model, params = setup

    def run(num_pages):
        eng = ServingEngine(model, params, num_slots=2, capacity=32,
                            paged=True, page_size=8, chunk_size=8,
                            token_budget=18, num_pages=num_pages)
        eng.submit(P0[:9], max_new_tokens=12, temperature=0.8, top_p=0.9,
                   seed=7)
        eng.submit(P1[:10], max_new_tokens=12, temperature=1.2, top_p=0.8,
                   seed=11)
        return {r.rid: r.output for r in eng.run()}, eng

    calm, _ = run(num_pages=8)          # no pressure: no preemption
    tight, eng = run(num_pages=4)       # forced preemption + resume
    assert eng.preemptions >= 1
    assert calm == tight


def test_sampling_temperature_zero_is_greedy_and_seeds_decorrelate(setup):
    cfg, model, params = setup
    prompt = [5, 9, 2, 4, 1]

    def run(**submit_kw):
        eng = ServingEngine(model, params, num_slots=1, capacity=64,
                            paged=True, page_size=8)
        eng.submit(prompt, max_new_tokens=8, **submit_kw)
        return eng.run()[0].output

    greedy = run()
    assert run(temperature=0.0, top_p=1.0, seed=3) == greedy
    s_a = run(temperature=1.5, top_p=0.9, seed=3)
    s_b = run(temperature=1.5, top_p=0.9, seed=4)
    assert s_a == run(temperature=1.5, top_p=0.9, seed=3)   # deterministic
    assert s_a != s_b                                       # seed matters
    assert s_a != greedy


def test_same_plan_admit_then_evict_executes_cleanly(setup):
    """The starvation victim can be a request admitted in the SAME plan
    (youngest by arrival, holding no pages yet); the engine must place and
    evict it without losing it, and every request still completes with
    greedy-correct output."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=3, capacity=32, paged=True,
                        page_size=4, chunk_size=8, token_budget=24,
                        num_pages=7)
    prompts = {0: list(range(1, 25)), 1: list(range(30, 46)), 2: [5, 9, 2, 4]}
    eng.submit(prompts[0], max_new_tokens=2)
    eng.submit(prompts[1], max_new_tokens=2)
    eng.step()
    eng.step()
    eng.submit(prompts[2], max_new_tokens=2)   # admitted + evicted in one plan
    done = {r.rid: r.output for r in eng.run()}
    assert len(done) == 3
    assert eng.preemptions >= 2
    for rid, p in prompts.items():
        solo = ServingEngine(model, params, num_slots=1, capacity=32,
                             paged=True, page_size=4)
        solo.submit(p, max_new_tokens=2)
        assert done[rid] == solo.run()[0].output, f"rid {rid} diverged"


def test_prepass_evicted_lane_readmitted_same_plan_executes(setup):
    """A decode-boundary eviction frees a lane that the SAME plan hands to
    a queued request; the engine must evict the old tenant and place the
    new one on that lane without confusing them, and all streams stay
    greedy-correct."""
    cfg, model, params = setup
    prompts = {0: list(range(1, 15)), 1: list(range(20, 34)), 2: [5, 9, 2, 4]}
    eng = ServingEngine(model, params, num_slots=2, capacity=32, paged=True,
                        page_size=8, chunk_size=8, token_budget=18,
                        num_pages=4)
    eng.submit(prompts[0], max_new_tokens=6)
    eng.submit(prompts[1], max_new_tokens=6)
    for _ in range(4):                 # prefill + decode to the boundary
        eng.step()
    eng.submit(prompts[2], max_new_tokens=3)
    done = {r.rid: r.output for r in eng.run()}
    assert len(done) == 3
    assert eng.preemptions >= 1
    for rid, p in prompts.items():
        solo = ServingEngine(model, params, num_slots=1, capacity=32,
                             paged=True, page_size=8)
        solo.submit(p, max_new_tokens=len(done[rid]))
        assert done[rid] == solo.run()[0].output, f"rid {rid} diverged"


def test_no_extra_token_at_capacity_boundary(setup):
    """A sequence reaching per-sequence capacity is finished, never decoded
    AT capacity: the input token's KV write would be dropped and the
    emitted token mis-conditioned. Output must be an exact prefix of the
    unconstrained greedy stream, in both atomic and chunked modes."""
    cfg, model, params = setup
    prompt = list(range(1, 16))                # len 15, capacity 16
    ref = ServingEngine(model, params, num_slots=1, capacity=64, paged=True,
                        page_size=8)
    ref.submit(prompt, max_new_tokens=5)
    full = ref.run()[0].output
    for chunk in (None, 8):
        eng = ServingEngine(model, params, num_slots=1, capacity=16,
                            paged=True, page_size=8, chunk_size=chunk)
        eng.submit(prompt, max_new_tokens=5)
        out = eng.run()[0].output
        # prefill emits token 1 (conditioned on rows [0,15)); decode at
        # filled 15 writes row 15 and emits token 2; filled 16 == capacity
        # -> finish. Exactly 2 tokens, both matching the greedy stream.
        assert out == full[:2], f"chunk={chunk}: {out} vs {full[:2]}"


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)
