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
