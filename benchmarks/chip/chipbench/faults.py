"""Faults planted under the timed path, to show that ``correct`` catches
them: each is a context manager that swaps one program function for a
broken one while a run is built and driven."""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def _swap(module, name, fn):
    old = getattr(module, name)
    setattr(module, name, fn(old))
    try:
        yield
    finally:
        setattr(module, name, old)


def altered_token():
    """Serving: every sampled token is replaced by the next id."""
    from repro.serve import sampling

    def broken(orig):
        def sample(logits, *args):
            return (orig(logits, *args) + 1) % logits.shape[-1]
        return sample
    return _swap(sampling, "sample_tokens", broken)


def no_exchange():
    """Sharded serving: the two projection all-reduces of every layer are
    left out, so each chip carries on with its own partial sums."""
    from repro.models import attention_layer

    def broken(orig):
        def reduce(y, cfg):
            return y
        return reduce
    return _swap(attention_layer, "_tp_reduce", broken)


def _train_step_fault(wrap):
    from repro import train

    def broken(orig):
        def make(model, opt, **kw):
            return wrap(orig(model, opt, **kw))
        return make
    return _swap(train, "make_train_step", broken)


def unchanged_state():
    """Training: the step returns its parameters and optimizer state as it
    got them."""
    def wrap(step):
        def f(params, opt_state, batch):
            return params, opt_state, step(params, opt_state, batch)[2]
        return f
    return _train_step_fault(wrap)


def half_batch():
    """Training: the step leaves out the second half of the batch and takes
    the mean over the rest."""
    def wrap(step):
        def f(params, opt_state, batch):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return step(params, opt_state, half)
        return f
    return _train_step_fault(wrap)


SERVE = {"altered_token": altered_token}
TRAIN = {"unchanged_state": unchanged_state, "half_batch": half_batch}
SHARDED = {"no_exchange": no_exchange}      # cells on more than one chip


def planted(cell) -> dict:
    """The faults that ``cell`` can have."""
    out = dict(SERVE if cell.mix["kind"] == "serve" else TRAIN)
    if cell.chips > 1:
        out.update(SHARDED)
    return out
