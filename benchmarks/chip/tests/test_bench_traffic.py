"""The generator: same seed, same inputs; lengths clipped; every seed the
same multiset of sizes; packed documents equal to the program's pipeline."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the harness on the path)
from chipbench import traffic

CHAT = {"kind": "serve",
        "arrivals": {"process": "poisson", "rate_per_s": 2.0, "warmup_s": 5,
                     "tail_s": 10},
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                       "min": 64, "max": 3584},
        "output_len": {"dist": "uniform", "min": 16, "max": 128}}


def test_same_seed_same_schedule():
    a = traffic.serve_schedule(CHAT, 2**31 + 7, 20, 1000)
    b = traffic.serve_schedule(CHAT, 2**31 + 7, 20, 1000)
    assert [(r.due, r.prompt, r.max_new, r.segment) for r in a] == \
           [(r.due, r.prompt, r.max_new, r.segment) for r in b]


def test_other_seed_same_sizes_other_order():
    a = traffic.serve_schedule(CHAT, 1, 20, 1000)
    b = traffic.serve_schedule(CHAT, 2, 20, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("spec", [CHAT["prompt_len"], CHAT["output_len"]])
def test_lengths_clipped(spec):
    xs = traffic.stratified_lengths(spec, 5000, np.random.default_rng(0))
    assert xs.min() >= spec["min"] and xs.max() <= spec["max"]
    assert xs.min() == spec["min"] and xs.max() == spec["max"]


def test_segments_hold_rate_times_length():
    reqs = traffic.serve_schedule(CHAT, 3, 20, 1000)
    count = {s: sum(r.segment == s for r in reqs) for s in ("warm", "window", "tail")}
    assert count == {"warm": 10, "window": 40, "tail": 20}
    win = [r.due for r in reqs if r.segment == "window"]
    assert 0 < min(win) and max(win) < 20


def test_unknown_arrival_process_is_refused():
    mix = dict(CHAT, arrivals={"process": "backlog", "requests": 12})
    with pytest.raises(ValueError, match="backlog"):
        traffic.serve_schedule(mix, 3, 20, 1000)


@pytest.mark.parametrize("seq,doc", [(256, 64), (128, 512)])
def test_packed_docs_match_program_pipeline(seq, doc):
    from repro.data.pipeline import SyntheticLM
    ours = traffic.PackedDocs(50257, seq, 3, seed=2**31 + 11, mean_doc_len=doc)
    theirs = SyntheticLM(50257, seq, 3, seed=2**31 + 11, mean_doc_len=doc)
    for step in (0, 5):
        a, b = ours.batch_at(step), theirs.batch_at(step)
        for k in ("tokens", "loss_mask", "segment_ids"):
            np.testing.assert_array_equal(a[k], b[k])


def test_segment_lengths():
    ids = np.array([[0, 0, 1, 1, 1, 2], [0, 0, 0, 0, 0, 0]])
    assert traffic.segment_lengths(ids) == [2, 3, 1, 6]
