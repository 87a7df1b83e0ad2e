"""Token sampling for the serving engine: temperature / top-p with a
per-request PRNG key that is a pure function of (seed, position).

The preemption-resume invariant (DESIGN.md §10) requires that a request
preempted after generating k tokens and later resumed produces the SAME
continuation. Greedy decoding gets this for free; sampling gets it by
construction here: the key for a request's i-th generated token is
``fold_in(PRNGKey(request.seed), i)`` — no mutable RNG state survives a
preemption because there is no mutable RNG state at all. The engine calls
ONE function (``sample_tokens``) from both the prefill path (first token,
``count = len(output)`` — 0 normally, k after a resume) and the decode
path, so the two paths are bit-identical by sharing the code.

``temperature <= 0`` means greedy (argmax) for that row; ``top_p`` keeps
the smallest prefix of the sorted distribution whose cumulative
probability reaches p (the top token always survives), renormalized by
``jax.random.categorical`` over the filtered logits.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.telemetry.scopes import SAMPLE, scoped

__all__ = ["SamplingParams", "sample_tokens"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode distribution. ``temperature == 0`` is greedy
    (top_p and seed are then inert). ``seed`` defaults to the request id
    at submit time so concurrent requests decorrelate."""
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def _sample_one(logits, seed, count, temperature, top_p):
    """One row. The key depends only on (seed, count): position-indexed
    randomness, so preempt->resume replays identically."""
    lg = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    probs = jax.nn.softmax(lg / jnp.where(greedy, 1.0, temperature))
    order = jnp.argsort(-probs)                      # descending
    sp = jnp.take(probs, order)
    csum = jnp.cumsum(sp)
    # keep rows whose EXCLUSIVE cumulative mass is < p: the top token's is
    # 0, so at least one row always survives.
    keep = (csum - sp) < top_p
    filt = jnp.where(keep, jnp.log(jnp.maximum(sp, 1e-38)), -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), count)
    sampled = jnp.take(order, jax.random.categorical(key, filt))
    return jnp.where(greedy, jnp.argmax(lg), sampled).astype(jnp.int32)


@scoped(SAMPLE)
def sample_tokens(logits, seeds, counts, temperature, top_p):
    """(n, V) logits + per-row (seed, count, temperature, top_p) -> (n,)
    int32 tokens. vmapped over rows, so each row's draw is independent of
    the batch width — the same (seed, count, logits) gives the same token
    whether sampled from a prefill row gather or the full decode batch."""
    return jax.vmap(_sample_one)(logits, seeds, counts, temperature, top_p)
