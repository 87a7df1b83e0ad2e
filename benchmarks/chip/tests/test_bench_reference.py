"""The plain float32 references match the program's model at a tiny size
(float32 weights, CPU)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from chipbench import reference, traffic
from chipbench.common import program_model
from chipbench.reference import arch_from_config
from chipbench.weights import make_params


def tiny(name, **extra):
    cell = bench_tiny.tiny_cell(name)
    cell.config["program"]["overrides"].update(dtype="float32", **extra)
    model = program_model(cell.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return cell, model, make_params(shapes, 2**31 + 5)


def test_serving_logits_match_program_forward():
    cell, model, params = tiny("granite-3-2b.chat")
    toks = np.random.default_rng(0).integers(1, 256, size=37)
    want = model.forward(params, {"tokens": jnp.asarray(toks[None])})[0][0]
    got = reference.serve_logits(params, list(toks), arch_from_config(cell.config),
                                 pad_to=16)[:37]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


def test_training_loss_and_grad_match_program():
    cell, model, params = tiny("gpt2-small.train-4k")
    batch = traffic.PackedDocs(256, 64, 2, seed=9, mean_doc_len=16).batch_at(0)
    assert len(traffic.segment_lengths(batch["segment_ids"])) > 2
    (want_loss, _), want_g = jax.value_and_grad(
        lambda p: model.loss(p, jax.tree.map(jnp.asarray, batch),
                             deterministic=True), has_aux=True)(params)
    loss, g = reference.loss_and_grad(params, batch, arch_from_config(cell.config))
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-3)


def test_reference_optimizer_matches_program_adamw():
    from repro.optim import adamw, apply_updates, warmup_cosine
    from repro.optim.optimizers import clip_by_global_norm
    cell, model, params = tiny("gpt2-small.train-4k")
    o = cell.mix["optimizer"]
    batches = [traffic.PackedDocs(256, 64, 2, seed=3).batch_at(i) for i in range(2)]
    opt = adamw(warmup_cosine(o["peak_lr"], o["warmup_steps"], o["total_steps"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    p, state = params, opt.init(params)
    for b in batches:
        _, g = reference.loss_and_grad(p, b, arch_from_config(cell.config))
        g, _ = clip_by_global_norm(g, o["clip_norm"])
        u, state = opt.update(g, state, p)
        p = apply_updates(p, u)
    _, _, ref_p = reference.train_steps(params, batches,
                                        arch_from_config(cell.config), o)
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-5)


def test_weights_from_seed():
    cell, model, a = tiny("granite-3-2b.chat")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    b = make_params(shapes, 2**31 + 5)
    c = make_params(shapes, 2**31 + 6)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["blocks"]["attn"]["wq"]),
                              np.asarray(c["blocks"]["attn"]["wq"]))
    assert np.all(np.asarray(a["final_norm"]["w"]) == 1.0)
