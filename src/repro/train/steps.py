"""Train/serve step factories: pjit-sharded, donated, ZeRO-1, grad-accum.

``make_sharded_train_step`` returns (step_fn, shardings) where step_fn is an
AOT-compilable jit with:
  * params sharded by the model's logical specs resolved on the mesh (TP/EP),
  * optimizer state sharded by ZeRO-1 over the data axes,
  * batch sharded over ("pod","data"),
  * donated params/opt-state (in-place update — halves peak param memory),
  * optional gradient accumulation (lax.scan over microbatches — divides
    activation peak by the accumulation factor),
  * dropout seeded by the optimizer step (traced — no retrace per step).

This factory is what both the trainer loop and the multi-pod dry-run lower.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import resolve_tree, rules_for_mesh
from repro.distributed.zero import zero1_state_specs
from repro.models.model_zoo import Model
from repro.optim.optimizers import (Optimizer, apply_updates,
                                    clip_by_global_norm)
from repro.telemetry import scopes


def make_train_step(model: Model, optimizer: Optimizer, *,
                    clip_norm: float = 1.0, grad_accum: int = 1,
                    deterministic: bool = False):
    """Mesh-agnostic train step (sharding applied by the caller's jit)."""

    def loss_fn(params, batch, seed):
        return model.loss(params, batch, deterministic=deterministic,
                          dropout_seed=seed)

    def train_step(params, opt_state, batch):
        seed = opt_state["step"].astype(jnp.uint32)
        if grad_accum == 1:
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, seed)
        else:
            def split(x):
                return x.reshape(grad_accum, x.shape[0] // grad_accum,
                                 *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                g_acc, m_acc = carry
                (_, m), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb, seed)
                g_acc = jax.tree.map(jnp.add, g_acc,
                                     jax.tree.map(lambda x: x / grad_accum, g))
                m_acc = jax.tree.map(jnp.add, m_acc,
                                     jax.tree.map(lambda x: x / grad_accum, m))
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": 0.0, "ce": 0.0, "aux": 0.0, "tokens": 0.0}
            m0 = jax.tree.map(jnp.float32, m0)
            (grads, metrics), _ = jax.lax.scan(body, (g0, m0), micro)

        with scopes.named(scopes.OPTIMIZER):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


def make_sharded_train_step(model: Model, optimizer: Optimizer, mesh, *,
                            rules=None, zero1: bool = True,
                            clip_norm: float = 1.0, grad_accum: int = 1,
                            deterministic: bool = False,
                            batch_specs=None, donate: bool = True):
    """Returns (jitted_step, shardings dict). ``batch_specs``: logical spec
    pytree for the batch (from model.input_specs)."""
    rules = rules or rules_for_mesh(mesh)
    param_specs = model.param_specs()
    param_sh = resolve_tree(param_specs, mesh, rules)

    param_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if zero1:
        opt_spec_phys = zero1_state_specs(param_shapes, param_specs, mesh, rules)
        opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), opt_spec_phys,
                              is_leaf=lambda x: isinstance(x, P))
    else:
        opt_sh = {"step": NamedSharding(mesh, P()),
                  "mu": param_sh, "nu": param_sh}

    if batch_specs is None:
        batch_sh = NamedSharding(mesh, P())
    else:
        batch_sh = resolve_tree(batch_specs, mesh, rules)

    metrics_sh = NamedSharding(mesh, P())
    step = make_train_step(model, optimizer, clip_norm=clip_norm,
                           grad_accum=grad_accum, deterministic=deterministic)
    jitted = jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, {"params": param_sh, "opt": opt_sh, "batch": batch_sh,
                    "metrics": metrics_sh}


def make_sharded_serve_steps(model: Model, mesh, *, rules=None,
                             state_specs=None, donate: bool = True):
    """(prefill_fn, decode_fn) with the decode state sharded + donated."""
    rules = rules or rules_for_mesh(mesh)
    param_sh = resolve_tree(model.param_specs(), mesh, rules)

    def decode(params, state, token):
        return model.decode_step(params, state, token)

    if state_specs is not None:
        state_sh = resolve_tree(state_specs, mesh, rules)
        from repro.distributed.sharding import resolve_spec
        tok_sh = NamedSharding(mesh, resolve_spec(P("data"), rules))
    else:
        state_sh = None
        tok_sh = None

    decode_jit = jax.jit(
        decode,
        in_shardings=(param_sh, state_sh, tok_sh) if state_sh else None,
        out_shardings=(state_sh, None) if state_sh else None,
        donate_argnums=(1,) if donate else (),
    )

    def prefill(params, batch, capacity):
        return model.prefill(params, batch, capacity)

    prefill_jit = jax.jit(prefill, static_argnums=(2,),
                          in_shardings=(param_sh, None) if state_sh else None)
    return prefill_jit, decode_jit
