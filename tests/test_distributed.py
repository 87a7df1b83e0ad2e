"""Distributed tests — run in subprocesses with 8 fake CPU devices (the
XLA host-platform flag must be set before jax init, so each scenario is an
isolated script). Covers: sharded train step (TP+DP), ZeRO-1 state sharding,
pipeline parallelism vs sequential, elastic checkpoint restore (8 -> 4
devices), gradient compression inside shard_map, and the sharding rule
resolver."""

import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import resolve_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(body: str, n_devices: int = 8, timeout: int = 420) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# rule resolver (no subprocess needed)
# ---------------------------------------------------------------------------

def test_resolve_spec_mapping():
    rules = {"heads": "model", "ff": "model", "data": ("pod", "data"),
             "embed": None}
    assert resolve_spec(P("embed", "heads"), rules) == P(None, "model")
    assert resolve_spec(P("data", None), rules) == P(("pod", "data"), None)
    assert resolve_spec(P(None, "unknown"), rules) == P(None, None)
    assert resolve_spec(P(("data",), "ff"), rules) == P(("pod", "data"), "model")


def test_auto_rules_divisibility():
    body = """
    from repro.configs import get_config
    from repro.distributed.sharding import auto_rules
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # granite vocab 49155 % 4 != 0 -> demoted; heads 2048 % 4 == 0 -> kept
    r = auto_rules(get_config("granite-3-2b"), mesh, global_batch=8)
    assert r["vocab"] is None, r
    assert r["heads"] == "model"
    # hymba ssm widths not divisible by 4 -> ssm demotions
    r = auto_rules(get_config("hymba-1.5b"), mesh, global_batch=8)
    assert r["ssm_ff"] is None and r["ssm_heads"] is None
    # batch 1 on data 2 -> data demoted
    r = auto_rules(get_config("olmo-1b"), mesh, global_batch=1)
    assert r["data"] is None
    print("AUTO_RULES_OK")
    """
    assert "AUTO_RULES_OK" in run_devices(body)


# ---------------------------------------------------------------------------
# sharded training
# ---------------------------------------------------------------------------

def test_sharded_train_step_tp_dp_zero1():
    body = """
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train.steps import make_sharded_train_step, make_train_step
    from repro.distributed.sharding import auto_rules, resolve_tree

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = reduced_config("granite-3-2b", d_model=64, d_ff=128, num_heads=4,
                         num_kv_heads=2, head_dim=16, vocab_size=256)
    model = build_model(cfg)
    rules = auto_rules(cfg, mesh, global_batch=8)
    opt = adamw(1e-3)
    step, sh = make_sharded_train_step(
        model, opt, mesh, rules=rules, zero1=True,
        batch_specs={"tokens": P(("data",), None),
                     "loss_mask": P(("data",), None)})

    params = jax.device_put(model.init(jax.random.PRNGKey(0)), sh["params"])
    opt_state = jax.device_put(opt.init(params), sh["opt"])
    # ZeRO-1: moments sharded over MORE devices than params
    mu_leaf = jax.tree.leaves(opt_state["mu"])[0]
    assert len(mu_leaf.sharding.device_set) >= 2, mu_leaf.sharding

    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "loss_mask": jnp.ones((8, 32), jnp.float32)}
    batch = jax.device_put(batch, sh["batch"])
    p1, o1, m1 = step(params, opt_state, batch)
    assert np.isfinite(float(m1["loss"]))

    # parity vs the unsharded step on one device
    params2 = model.init(jax.random.PRNGKey(0))
    opt_state2 = opt.init(params2)
    ref = jax.jit(make_train_step(model, opt))
    p2, o2, m2 = ref(params2, opt_state2,
                     {"tokens": np.ones((8, 32), np.int32),
                      "loss_mask": np.ones((8, 32), np.float32)})
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # float reduction order differs across device layouts; Adam's rsqrt is
    # sensitive where v ~ 0, so compare with an absolute floor well under
    # one LR-sized update (lr=1e-3).
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-5)
    print("SHARDED_STEP_OK")
    """
    assert "SHARDED_STEP_OK" in run_devices(body)


def test_grad_accum_equivalence():
    body = """
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train.steps import make_train_step

    cfg = reduced_config("olmo-1b")
    model = build_model(cfg)
    opt = adamw(1e-3)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256),
             "loss_mask": jnp.ones((8, 32), jnp.float32)}
    s1 = jax.jit(make_train_step(model, opt, deterministic=True))
    s4 = jax.jit(make_train_step(model, opt, grad_accum=4, deterministic=True))
    p1, _, m1 = s1(params, opt.init(params), batch)
    p4, _, m4 = s4(params, opt.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-6)
    print("ACCUM_OK")
    """
    assert "ACCUM_OK" in run_devices(body, n_devices=1)


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    body = """
    from repro.distributed.pipeline import (make_stage_fn, pipeline_apply,
                                            split_stages)
    mesh = jax.make_mesh((4,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    L, D, GB, M = 8, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(0), L)
    params = {"w": jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in keys])}

    def block_fn(p_l, x):
        return jnp.tanh(x @ p_l["w"]) + x

    x = jax.random.normal(jax.random.PRNGKey(1), (GB, D))

    def seq_apply(params, x):
        def body(h, p_l):
            return block_fn(p_l, h), None
        y, _ = jax.lax.scan(body, x, params)
        return y

    stage_fn = make_stage_fn(block_fn)
    stages = split_stages(params, 4)
    y_pipe = pipeline_apply(stage_fn, stages, x, mesh=mesh,
                            num_microbatches=M)
    y_seq = seq_apply(params, x)
    np.testing.assert_allclose(y_pipe, y_seq, rtol=1e-5, atol=1e-6)

    # gradients through the pipeline
    def loss_pipe(params):
        st = split_stages(params, 4)
        return (pipeline_apply(stage_fn, st, x, mesh=mesh,
                               num_microbatches=M) ** 2).sum()

    def loss_seq(params):
        return (seq_apply(params, x) ** 2).sum()

    g1 = jax.grad(loss_pipe)(params)["w"]
    g2 = jax.grad(loss_seq)(params)["w"]
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)
    print("PIPELINE_OK")
    """
    assert "PIPELINE_OK" in run_devices(body)


# ---------------------------------------------------------------------------
# elastic checkpoint restore (8 -> 4 devices)
# ---------------------------------------------------------------------------

def test_elastic_restore_across_meshes(tmp_path):
    save_body = f"""
    from repro.checkpoint import Checkpointer
    from jax.sharding import NamedSharding
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = NamedSharding(mesh, P(None, "model"))
    tree = {{"w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sh)}}
    Checkpointer(r"{tmp_path}").save(5, tree)
    print("SAVED")
    """
    assert "SAVED" in run_devices(save_body, n_devices=8)

    restore_body = f"""
    from repro.checkpoint import Checkpointer
    from jax.sharding import NamedSharding
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = {{"w": NamedSharding(mesh, P("model", None))}}   # different layout too
    target = {{"w": jnp.zeros((8, 8))}}
    tree, step = Checkpointer(r"{tmp_path}").restore(target, shardings=sh)
    assert step == 5
    np.testing.assert_allclose(np.asarray(tree["w"]),
                               np.arange(64.0).reshape(8, 8))
    # placed on the NEW 4-device mesh (model-sharded + data-replicated)
    assert len(tree["w"].sharding.device_set) == 4
    assert tree["w"].addressable_shards[0].data.shape == (4, 8)
    print("ELASTIC_OK")
    """
    assert "ELASTIC_OK" in run_devices(restore_body, n_devices=4)


# ---------------------------------------------------------------------------
# gradient compression in shard_map
# ---------------------------------------------------------------------------

def test_compressed_mean_matches_exact_mean():
    body = """
    from repro.distributed.compression import compressed_mean_tree
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    def body_fn(g):
        out = compressed_mean_tree({"g": g[0]}, "data")
        return out["g"][None]

    fn = jax.shard_map(body_fn, mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None), check_vma=False)
    approx = np.asarray(fn(g_global))[0]
    exact = np.asarray(g_global.mean(axis=0))
    # int8 per-tensor quantization: ~1% of max error
    tol = float(np.abs(g_global).max()) / 127
    assert np.abs(approx - exact).max() <= tol + 1e-6
    print("COMPRESS_OK")
    """
    assert "COMPRESS_OK" in run_devices(body)


# ---------------------------------------------------------------------------
# multi-pod mesh sanity (16 devices standing in for 512)
# ---------------------------------------------------------------------------

def test_multipod_mesh_axes_shard_batch():
    body = """
    from repro.distributed.sharding import rules_for_mesh, resolve_spec
    mesh = jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    rules = rules_for_mesh(mesh)
    spec = resolve_spec(P("data", None), rules)
    assert spec == P(("pod", "data"), None), spec
    sh = jax.sharding.NamedSharding(mesh, spec)
    x = jax.device_put(jnp.ones((8, 4)), sh)
    assert len(x.sharding.device_set) == 16
    y = jax.jit(lambda a: (a * 2).sum())(x)
    assert float(y) == 64.0
    print("MULTIPOD_OK")
    """
    assert "MULTIPOD_OK" in run_devices(body, n_devices=16)


# ---------------------------------------------------------------------------
# tensor-parallel paged serving (DESIGN.md §13)
# ---------------------------------------------------------------------------

def test_tp_serving_shard_count_invariance():
    """Logits and sampled token streams identical across tp in {1,2,4,8}
    for a GQA model (greedy + sampled lanes, chunked prefill)."""
    body = """
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve.engine import ServingEngine

    cfg = reduced_config("granite-3-2b", num_layers=2, d_model=64,
                         num_heads=16, num_kv_heads=8, head_dim=4,
                         d_ff=128, vocab_size=128, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 128, size=n)))
               for n in (5, 9, 3, 12)]
    probe = {"tokens": jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32),
             "segment_ids": jnp.zeros((1, 8), jnp.int32)}

    def run(tp):
        eng = ServingEngine(model, params, num_slots=4, capacity=64,
                            paged=True, page_size=8, chunk_size=4, tp=tp)
        _, lg = eng._prefill_packed(eng.params, probe)
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=8,
                       temperature=0.8 if i % 2 else 0.0, seed=17 + i)
        done = eng.run()
        return {r.rid: r.output for r in done}, np.asarray(lg)

    outs, logits = {}, {}
    for tp in (1, 2, 4, 8):
        outs[tp], logits[tp] = run(tp)
    for tp in (2, 4, 8):
        assert outs[tp] == outs[1], (tp, outs[tp], outs[1])
        # psum reorders float reductions vs single-device: close, not equal
        np.testing.assert_allclose(logits[tp], logits[1],
                                   rtol=1e-5, atol=1e-5)
    print("TP_INVARIANCE_OK")
    """
    assert "TP_INVARIANCE_OK" in run_devices(body)


def test_tp_page_pool_slicing_property():
    """Host allocator page indices address identical logical rows on every
    shard: each shard's local pool slice equals the global array at its
    head-slice index, and the tp=4 pool matches the tp=1 pool row-for-row
    (same host allocator, same page assignments)."""
    body = """
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve.engine import ServingEngine

    cfg = reduced_config("granite-3-2b", num_layers=2, d_model=64,
                         num_heads=8, num_kv_heads=4, head_dim=8,
                         d_ff=128, vocab_size=128, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(1, 128, size=n)))
               for n in (11, 6, 17)]

    def run(tp):
        eng = ServingEngine(model, params, num_slots=3, capacity=64,
                            paged=True, page_size=8, tp=tp)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        return eng

    e1, e4 = run(1), run(4)
    leaves1 = jax.tree.leaves(e1.state["caches"])
    leaves4 = jax.tree.leaves(e4.state["caches"])
    for l1, l4 in zip(leaves1, leaves4):
        glob1, glob4 = np.asarray(l1), np.asarray(l4)
        # identical logical pool content (rows land at the same allocator-
        # assigned (page, offset) on every shard count)
        np.testing.assert_allclose(glob4, glob1, rtol=1e-5, atol=1e-6)
        # each device holds exactly its head-slice of the logical pool
        assert len(l4.sharding.device_set) == 4
        for sh in l4.addressable_shards:
            np.testing.assert_array_equal(np.asarray(sh.data),
                                          glob4[sh.index])
            assert sh.data.shape[-1] == glob4.shape[-1] // 4
    print("TP_POOL_SLICING_OK")
    """
    assert "TP_POOL_SLICING_OK" in run_devices(body)
