"""The one general generator: serving schedules and training batches, both
drawn from ``--seed`` and the parameters of a mix file.

Serving. Every seed gets the same multiset of prompt lengths, output
lengths and inter-arrival gaps (stratified quantiles of the mix's
distributions), in a seed-dependent order, so seeds change the order of the
work and not its amount. Arrivals come in three segments: warm-up (before
the window, set-up), the window, and a tail that keeps the load on while
the window's requests drain. Each segment holds exactly ``round(rate * its
length)`` arrivals.

Training. ``PackedDocs`` reproduces ``repro.data.pipeline.SyntheticLM``
(noisy affine token recurrence, documents packed back to back with
``segment_ids`` and a loss mask that drops each boundary token and the one
after it), with the per-position loop replaced by the recurrence's closed
form; the output is the same array for the same seed and step.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


# ----------------------------------------------------------------- serving

def stratified_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``'s
    distribution, clipped to ``[min, max]``, in an order drawn from ``rng``.

    ``spec``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive integer bounds)."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif spec["dist"] == "uniform":
        vals = np.floor(lo + (hi - lo + 1) * p)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng.permutation(np.clip(vals, lo, hi).astype(np.int64))


def poisson_times(rate: float, start: float, dur: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Exactly ``round(rate * dur)`` arrival times in ``(start, start+dur)``
    whose gaps are the stratified quantiles of an exponential distribution,
    in an order drawn from ``rng``."""
    n = int(round(rate * dur))
    if n == 0:
        return np.zeros((0,))
    p = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = rng.permutation(-np.log1p(-p))
    c = np.cumsum(gaps)
    return start + dur * c[:-1] / c[-1]


@dataclasses.dataclass
class ServeRequest:
    idx: int
    due: float               # seconds from window start
    prompt: list[int]
    max_new: int
    segment: str             # "warm", "window" or "tail"


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab: int) -> list[ServeRequest]:
    """The requests of one run, in submission order.

    ``mix["arrivals"]``: ``{"process": "poisson", "rate_per_s",
    "warmup_s", "tail_s"}``, open loop at a fixed rate.
    """
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    segs = [("warm", -float(arr["warmup_s"]), float(arr["warmup_s"])),
            ("window", 0.0, float(seconds)),
            ("tail", float(seconds), float(arr["tail_s"]))]
    times, labels = [], []
    for label, start, dur in segs:
        t = poisson_times(float(arr["rate_per_s"]), start, dur, rng)
        times.append(t)
        labels += [label] * len(t)
    due = np.concatenate(times)
    n = len(labels)
    plen = stratified_lengths(mix["prompt_len"], n, rng)
    olen = stratified_lengths(mix["output_len"], n, rng)
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab, size=int(plen[i]))
        out.append(ServeRequest(
            idx=i, due=float(due[i]),
            prompt=[int(t) for t in toks], max_new=int(olen[i]),
            segment=labels[i]))
    return out


# ---------------------------------------------------------------- training

@dataclasses.dataclass
class PackedDocs:
    """Random-access packed synthetic documents (``batch_at(step)``)."""
    vocab_size: int
    seq_len: int
    batch: int
    seed: int
    noise: float = 0.02
    mean_doc_len: int = 512

    def __post_init__(self):
        V, S = self.vocab_size, self.seq_len
        a = 31337 % V or 7
        # t_i = (A_i t_0 + b C_i) mod V with A_i = a^i, C_i = sum_{j<i} a^j
        A = np.empty((S,), np.int64)
        C = np.empty((S,), np.int64)
        A[0], C[0] = 1, 0
        for i in range(1, S):
            A[i] = (A[i - 1] * a) % V
            C[i] = (C[i - 1] * a + 1) % V
        self._A, self._C = A, C

    def batch_at(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[step, 0, 0, 0]))
        B, S, V = self.batch, self.seq_len, self.vocab_size
        b = rng.integers(1, V, size=(B, 1))
        t0 = rng.integers(0, V, size=(B, 1))
        toks = (self._A[None, :] * t0 + b * self._C[None, :]) % V
        flip = rng.random((B, S)) < self.noise
        toks = np.where(flip, rng.integers(0, V, size=(B, S)), toks)
        boundary = rng.random((B, S)) < (1.0 / self.mean_doc_len)
        boundary[:, 0] = False
        toks = np.where(boundary, rng.integers(0, V, size=(B, S)), toks)
        after = np.zeros_like(boundary)
        after[:, 1:] = boundary[:, :-1]
        loss_mask = 1.0 - (boundary | after).astype(np.float32)
        segment_ids = np.cumsum(boundary.astype(np.int64), axis=-1)
        return {"tokens": toks.astype(np.int32),
                "loss_mask": loss_mask,
                "segment_ids": segment_ids.astype(np.int32)}


def segment_lengths(segment_ids: np.ndarray) -> list[int]:
    """Lengths of the runs of equal ids in every row (packed documents)."""
    out: list[int] = []
    for row in np.asarray(segment_ids):
        cuts = np.flatnonzero(np.diff(row)) + 1
        out += np.diff(np.concatenate([[0], cuts, [len(row)]])).tolist()
    return out

