"""Weights from the seed, made on the device in one jitted call.

The harness, not the program, makes the weights, so the plain reference can
take the same ones without taking anything the program made. The tree's
layout and shapes are the program's (``jax.eval_shape`` of its init); every
leaf is filled here: norm scales ``w`` with ones, biases ``b`` with zeros,
every matrix with a normal truncated at two standard deviations, of
standard deviation ``1 / sqrt(fan_in)`` (``fan_in`` = the second-to-last
dimension; a stacked-layer leaf keeps its leading layer axis).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make_params(shapes, seed: int):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct``. Returns arrays of
    the same shapes and dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, s) in zip(keys, leaves):
            name = _leaf_name(path)
            if name == "w" and len(s.shape) <= 2:      # norm scale
                x = jnp.ones(s.shape, jnp.float32)
            elif name == "b":
                x = jnp.zeros(s.shape, jnp.float32)
            else:
                std = 1.0 / max(1.0, float(s.shape[-2])) ** 0.5
                x = jax.random.truncated_normal(
                    k, -2.0, 2.0, s.shape, jnp.float32) * std
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.key(seed % (1 << 63)))
