"""Paged KV-cache tests (DESIGN.md §6): allocator behaviour, paged-engine
token-identity vs the dense engine (including pool exhaustion + preemption
and fragmented pools after churn), and SKIP-page isolation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import PagedKVCache, ServingEngine
from repro.serve.kv_cache import packed_destinations, pages_for


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite-3-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def greedy_ref(model, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = model.forward(params,
                                  {"tokens": jnp.asarray([toks], jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_release_and_fragmentation():
    kv = PagedKVCache(num_pages=8, page_size=4)
    assert kv.free_pages == 8 and kv.utilization() == 0.0
    assert kv.alloc(0, 2) and kv.table(0) == [0, 1]
    assert kv.alloc(1, 2) and kv.table(1) == [2, 3]
    assert kv.alloc(2, 2) and kv.table(2) == [4, 5]
    assert kv.utilization() == 6 / 8
    # all-or-nothing: a failed alloc grabs nothing
    assert not kv.alloc(3, 3)
    assert kv.free_pages == 2 and kv.table(3) == []
    # FIFO reuse: released pages queue behind the still-free tail, so the
    # next multi-page table is non-contiguous — fragmentation is normal
    # operating state for the pool.
    assert kv.release(1) == 2
    assert kv.alloc(4, 3) and kv.table(4) == [6, 7, 2]
    assert np.any(np.diff(kv.table(4)) != 1)
    assert kv.peak_in_use == 7
    # table_array: -1 sentinel for unallocated entries / empty rows
    arr = kv.table_array([4, None, 0], pages_per_seq=4)
    assert arr.shape == (3, 4)
    assert list(arr[0]) == [6, 7, 2, -1]
    assert list(arr[1]) == [-1] * 4
    assert list(arr[2]) == [0, 1, -1, -1]


def test_allocator_validation_and_pages_for():
    with pytest.raises(ValueError):
        PagedKVCache(num_pages=0, page_size=4)
    with pytest.raises(ValueError):
        PagedKVCache(num_pages=4, page_size=0)
    assert pages_for(0, 8) == 0
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


def test_packed_destinations_padding_dropped():
    tables = [[5, 2], [7]]
    offsets = np.array([0, 5, 8])
    dp, do = packed_destinations(tables, offsets[:2], [5, 3], page_size=4,
                                 total=12, num_pages=8)
    assert list(dp[:5]) == [5, 5, 5, 5, 2]
    assert list(do[:5]) == [0, 1, 2, 3, 0]
    assert list(dp[5:8]) == [7, 7, 7]
    assert list(do[5:8]) == [0, 1, 2]
    # bucket-padding tail maps out of bounds (dropped by the scatter)
    assert list(dp[8:]) == [8, 8, 8, 8]


# ---------------------------------------------------------------------------
# engine: paged vs dense token-identity
# ---------------------------------------------------------------------------

PROMPTS = [[5, 9, 2], [7, 7, 1, 4], [3], [11, 2], [8, 6, 5, 1, 9]]


def _run(model, params, *, paged, n_new=6, **kw):
    eng = ServingEngine(model, params, num_slots=3, capacity=64,
                        paged=paged, **kw)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=n_new)
    done = eng.run()
    assert len(done) == len(PROMPTS)
    return eng, {r.rid: r.output for r in done}


def test_paged_engine_token_identical_to_dense(setup):
    cfg, model, params = setup
    e_dense, out_dense = _run(model, params, paged=False)
    e_paged, out_paged = _run(model, params, paged=True)
    assert e_paged.paged and not e_dense.paged
    assert out_paged == out_dense
    for rid, out in out_paged.items():
        assert out == greedy_ref(model, params, PROMPTS[rid], len(out))
    # every page returned to the pool at drain
    assert e_paged.kv.used_pages == 0
    assert e_paged.kv.peak_in_use > 0


def test_paged_sequential_prefill_matches_packed(setup):
    cfg, model, params = setup
    e_seq, out_seq = _run(model, params, paged=True, packed_prefill=False)
    e_pk, out_pk = _run(model, params, paged=True, packed_prefill=True)
    assert out_seq == out_pk
    assert e_pk.prefill_calls < e_seq.prefill_calls


def test_paged_geometry_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="multiple of page_size"):
        ServingEngine(model, params, num_slots=2, capacity=60,
                      paged=True, page_size=16)
    eng = ServingEngine(model, params, num_slots=2, capacity=32,
                        paged=True, page_size=8, num_pages=2)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(1, 12)), max_new_tokens=10)  # needs 3 pages
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(list(range(1, 40)), max_new_tokens=2)
    # dense mode rejects over-capacity prompts at submit too (previously an
    # opaque broadcast error surfaced mid-run)
    dense = ServingEngine(model, params, num_slots=2, capacity=16,
                          paged=False)
    with pytest.raises(ValueError, match="capacity"):
        dense.submit(list(range(1, 40)), max_new_tokens=4)


def test_decode_kernel_geometry_fails_at_construction(setup):
    """With cfg.use_decode_kernel the engine validates the kernel grid at
    __init__ — not at the first jitted decode step. Explicit (pinned) tile
    fields keep their fail-fast misalignment errors; auto (None) fields
    resolve to a divisor-valid geometry through kernels.tuning instead."""
    cfg, model, params = setup
    scfg = reduced_config("granite-3-2b", use_decode_kernel=True,
                          num_decode_splits=8)
    smodel = build_model(scfg)
    with pytest.raises(ValueError, match="num_splits"):
        # pages_per_seq = 12, pinned num_decode_splits = 8
        ServingEngine(smodel, params, num_slots=2, capacity=192,
                      paged=True, page_size=16)
    bcfg = reduced_config("granite-3-2b", use_decode_kernel=True,
                          attn_block_k=128)
    bmodel = build_model(bcfg)
    with pytest.raises(ValueError, match="block_k"):
        # capacity 192 is not a multiple of the pinned block_k 128
        ServingEngine(bmodel, params, num_slots=2, capacity=192,
                      paged=False)
    # paged mode: a pinned block_k that disagrees with the page size breaks
    # the page == kv-block allocation invariant -> rejected, not overridden
    with pytest.raises(ValueError, match="page_size"):
        ServingEngine(bmodel, params, num_slots=2, capacity=192,
                      paged=True, page_size=32)
    # auto fields: the tuner picks a valid grid for the same capacities
    acfg = reduced_config("granite-3-2b", use_decode_kernel=True)
    amodel = build_model(acfg)
    eng = ServingEngine(amodel, params, num_slots=2, capacity=192,
                        paged=True, page_size=16)
    assert eng.pages_per_seq % eng.num_decode_splits == 0
    dense = ServingEngine(amodel, params, num_slots=2, capacity=192,
                          paged=False)
    assert 192 % dense.decode_block_k == 0
    nk = 192 // dense.decode_block_k
    assert nk % dense.num_decode_splits == 0


def test_paged_refuses_recurrent_families():
    """SSM state cannot be paged: auto mode falls back to the dense slot
    cache (and still serves exactly — the unbucketed ``model.prefill`` +
    whole-state insert path), explicit paged=True raises."""
    cfg = reduced_config("mamba2-2.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=2, capacity=32)
    assert not eng.paged
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(model, params, num_slots=2, capacity=32, paged=True)
    prompts = {0: [5, 9, 2], 1: [7, 7, 1, 4]}
    eng.submit(prompts[0], max_new_tokens=4)
    eng.submit(prompts[1], max_new_tokens=3)
    done = eng.run()
    assert len(done) == 2
    for r in done:
        assert r.output == greedy_ref(model, params, prompts[r.rid],
                                      len(r.output))


# ---------------------------------------------------------------------------
# pool exhaustion -> preemption, and fragmented pools after churn
# ---------------------------------------------------------------------------

def test_pool_exhaustion_preemption_token_identical(setup):
    """A pool too small for both sequences' full lengths forces preemption;
    the requeued request must still produce token-identical output."""
    cfg, model, params = setup
    prompts = [[5, 9, 2, 1, 4, 7, 8, 2, 6], [7, 7, 1, 4, 3, 2, 9, 5, 1, 6]]
    n_new = 12
    refs = [greedy_ref(model, params, p, n_new) for p in prompts]

    # each sequence grows to 21/22 tokens = 3 pages of 8; 5 pages cannot
    # hold 6, so the younger sequence is preempted mid-decode.
    eng = ServingEngine(model, params, num_slots=2, capacity=32,
                        paged=True, page_size=8, num_pages=5)
    for p in prompts:
        eng.submit(p, max_new_tokens=n_new)
    done = eng.run()
    assert len(done) == 2
    assert eng.preemptions >= 1
    outs = {r.rid: r.output for r in done}
    assert outs[0] == refs[0]
    assert outs[1] == refs[1]
    assert eng.kv.used_pages == 0


def test_fragmented_pool_decode_token_identical(setup):
    """After churn the free list is scrambled; a sequence whose pages are
    non-contiguous in the pool must decode token-identically."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64,
                        paged=True, page_size=8, num_pages=12)
    # wave 1: churn with different finish times, then drain
    for p, n in [([1, 2, 3], 3), ([4, 5, 6, 7, 8, 9, 10, 11, 12], 5),
                 ([13, 14], 7)]:
        eng.submit(p, max_new_tokens=n)
    eng.run()
    # scramble the free list deterministically on top of the churn order
    eng.kv.free.rotate(5)
    prompt = [8, 6, 5, 1, 9, 3, 2, 7, 4, 11, 2, 5, 9, 1, 6, 3, 8, 2]
    rid = eng.submit(prompt, max_new_tokens=8)
    eng.step()  # admit + prefill: table now materialized
    table = list(eng.kv.table(rid))
    assert len(table) >= 3
    assert np.any(np.diff(table) != 1), table  # provably fragmented
    done = {r.rid: r.output for r in eng.run()}
    assert done[rid] == greedy_ref(model, params, prompt, 8)


def test_paged_engine_mixed_lengths_interleave(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, num_slots=2, capacity=64, paged=True,
                        page_size=16)
    eng.submit([1], max_new_tokens=8)
    eng.submit([2, 3, 4, 5, 6], max_new_tokens=2)
    eng.submit([7, 8], max_new_tokens=4)
    done = eng.run()
    assert sorted(len(r.output) for r in done) == [2, 4, 8]
    for r in done:
        prompt = {0: [1], 1: [2, 3, 4, 5, 6], 2: [7, 8]}[r.rid]
        assert r.output == greedy_ref(model, params, prompt, len(r.output))


def test_paged_engine_with_decode_kernel_token_identical(setup):
    """cfg.use_decode_kernel=True routes every engine decode step through
    the split-KV Pallas kernel's page-table indirection (flash_decode_paged)
    instead of the XLA gather — outputs must stay token-identical."""
    cfg, model, params = setup
    kcfg = reduced_config("granite-3-2b", use_decode_kernel=True)
    kmodel = build_model(kcfg)
    prompts = PROMPTS[:2]
    eng = ServingEngine(kmodel, params, num_slots=2, capacity=64,
                        paged=True, page_size=16)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    done = eng.run()
    assert len(done) == 2
    for r in done:
        assert r.output == greedy_ref(model, params, prompts[r.rid],
                                      len(r.output))


def test_paged_state_specs_match_state_structure(setup):
    """The sharding specs for the paged decode state mirror its pytree
    structure leaf-for-leaf (the sharded-serving contract, DESIGN.md §6.5);
    the pool's page dim carries the dense capacity dim's axis name."""
    cfg, model, params = setup
    state = jax.eval_shape(
        lambda: model.init_paged_decode_state(2, 8, 16, 4))
    specs = model.paged_decode_state_specs()
    assert (jax.tree.structure(state)
            == jax.tree.structure(specs,
                                  is_leaf=lambda x: not isinstance(x, dict)))
    pool_spec = specs["caches"]["kv"]["k"]
    pool_shape = state["caches"]["kv"]["k"].shape  # (L, P, ps, hkv*hd)
    assert len(pool_spec) == len(pool_shape)
    assert pool_spec[1] == "kv_seq"


# ---------------------------------------------------------------------------
# isolation: free pages cannot influence active sequences
# ---------------------------------------------------------------------------

def test_free_page_garbage_cannot_leak_into_outputs(setup):
    """Poison every FREE page with large finite garbage mid-run; outputs
    must be bit-identical to the clean run (the mask IR classifies those
    pages SKIP / the masked softmax zeroes them)."""
    cfg, model, params = setup
    ref = greedy_ref(model, params, PROMPTS[0], 6)

    eng = ServingEngine(model, params, num_slots=2, capacity=64, paged=True,
                        page_size=16, num_pages=8)
    eng.submit(PROMPTS[0], max_new_tokens=6)
    eng.step()  # prefill: pages for the prompt are now allocated
    used = {p for t in eng.kv.tables.values() for p in t}
    free = np.asarray([p for p in range(eng.kv.num_pages) if p not in used])

    def poison(leaf):
        # leaf: (L, num_pages, page_size, hkv*hd)
        return leaf.at[:, jnp.asarray(free)].set(7.7e4)

    caches = eng.state["caches"]
    caches["kv"] = {k: poison(v) for k, v in caches["kv"].items()}
    done = eng.run()
    assert done[0].output == ref
