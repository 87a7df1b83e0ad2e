"""Logical-axis sharding rules -> physical NamedShardings.

Model code annotates params/inputs with *logical* PartitionSpecs (axis names
like "embed", "heads", "ff", "expert", "vocab", "data"). A rule table maps
logical names to physical mesh axes; unlisted names are replicated. This is
the MaxText/T5X pattern: swapping a rule table re-shards the whole model
(that is how the §Perf hillclimb tries alternative shardings without
touching model code).
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# default rules: 2D/3D mesh with TP on "model", DP on ("pod","data")
DEFAULT_RULES: dict[str, Any] = {
    "embed": None,             # activations' feature dim replicated
    "heads": "model",          # attention head projections -> TP
    "ff": "model",             # FFN hidden -> TP
    "expert": "model",         # MoE experts -> EP (same physical axis)
    "vocab": "model",          # embedding/vocab rows -> TP
    "ssm_ff": "model",         # SSM projections -> TP
    "ssm_heads": "model",      # SSM decode-state heads -> TP
    "kv_seq": "model",         # KV-cache capacity -> sequence-sharded TP
    "data": "data",            # batch -> DP (expanded to ("pod","data") if present)
}


def tp_serve_rules() -> dict[str, Any]:
    """Rule table for the tensor-parallel serving engine (DESIGN.md §13).

    ONLY heads and the FFN hidden dim shard over "tp": embed/vocab stay
    replicated so activations and logits are replicated once the two
    projection psums run (sampling then needs no collective), and the page
    pool's page dim stays host-global — the pool shards over HEADS, page
    indices are valid on every shard (one logical pool, per-shard slices).
    """
    return {"heads": "tp", "ff": "tp"}


def sp_serve_rules() -> dict[str, Any]:
    """Rule table for the 2-D ``("sp", "tp")`` serving mesh (DESIGN.md §14).

    Extends :func:`tp_serve_rules` with one logical axis: "sp_seq", the
    PACKED QUERY-ROW axis of a chunked-prefill step, shards over "sp" —
    each sp-shard owns one contiguous slab of the chunk. Everything
    KV-side (the page pool, destination maps, page lists, kv
    segment/position rows) stays sp-REPLICATED: page indices remain
    host-global on every shard, and each shard scatters the FULL chunk's
    K/V (assembled via all-gather or ring ppermute) into its pool
    replica, keeping replicas bit-identical across sp.
    """
    return {**tp_serve_rules(), "sp_seq": "sp"}


def expected_sp_prefill_census(traced_layers: int, *, sp: int = 1,
                               strategy: str = "allgather") -> dict[str, int]:
    """The exact collective multiset a sharded chunked-prefill step must
    trace to (DESIGN.md §14 census contract) — shared by the serving
    tests and the throughput bench so the assertion cannot drift.

    Per traced layer: the 2 projection psums over "tp" (attention wo +
    MLP down — present whenever the mesh is active, even at tp=1 where
    the axis has size 1), plus the sp KV movement: ONE all_gather, or
    ``sp - 1`` neighbor ppermutes for the ring. ``traced_layers`` is 1
    under ``scan_layers`` (the scan body traces once), else num_layers.
    """
    census = {"psum": 2 * traced_layers}
    if sp > 1:
        if strategy == "ring":
            census["ppermute"] = (sp - 1) * traced_layers
        elif strategy == "allgather":
            census["all_gather"] = traced_layers
        else:
            raise ValueError(f"unknown sp strategy {strategy!r}")
    return census


def rules_for_mesh(mesh: Mesh, overrides: Mapping[str, Any] | None = None):
    rules = dict(DEFAULT_RULES)
    if "pod" in mesh.axis_names:
        rules["data"] = ("pod", "data")
    if overrides:
        rules.update(overrides)
    return rules


def auto_rules(cfg, mesh: Mesh, *, global_batch: int | None = None,
               overrides: Mapping[str, Any] | None = None):
    """Divisibility-aware rules for one (arch, mesh, shape) cell.

    GSPMD jit boundaries require sharded dims to divide evenly; this demotes
    any logical axis whose concrete dims do not divide the TP size to
    replicated (e.g. granite's vocab 49155 on TP-16, hymba's SSM widths),
    and replicates the batch when global_batch < DP (long_500k, batch 1).
    """
    rules = rules_for_mesh(mesh, overrides)
    m = mesh.shape.get("model", 1)

    def divisible(*dims):
        return all(d % m == 0 for d in dims)

    if cfg.vocab_size and not divisible(cfg.vocab_size):
        rules["vocab"] = None
    if cfg.num_experts and not divisible(cfg.num_experts):
        rules["expert"] = None
    if cfg.d_ff and not divisible(cfg.d_ff):
        rules["ff"] = None
    if cfg.num_heads:
        hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if not divisible(hq * hd, hkv * hd):
            rules["heads"] = None
    if cfg.ssm_state:
        d_inner = cfg.ssm_d_inner
        nheads = cfg.ssm_num_heads
        proj = 2 * d_inner + 2 * cfg.ssm_state + nheads
        conv_ch = d_inner + 2 * cfg.ssm_state
        if not divisible(proj, conv_ch, d_inner):
            rules["ssm_ff"] = None
        if not divisible(nheads):
            rules["ssm_heads"] = None
    if global_batch is not None:
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        if global_batch % dp != 0:
            rules["data"] = None
    if overrides:
        rules.update(overrides)
    return rules


def resolve_spec(logical: P, rules: Mapping[str, Any]) -> P:
    """Map a logical PartitionSpec to a physical one via the rule table."""
    out = []
    for entry in logical:
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        phys: list[str] = []
        for n in names:
            r = rules.get(n, None)
            if r is None:
                continue
            phys.extend(r if isinstance(r, tuple) else (r,))
        if not phys:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(tuple(phys))
    return P(*out)


def _is_spec(x) -> bool:
    return isinstance(x, P)


def resolve_tree(tree, mesh: Mesh, rules: Mapping[str, Any] | None = None):
    """Pytree of logical PartitionSpecs -> pytree of NamedShardings."""
    rules = rules or rules_for_mesh(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, resolve_spec(s, rules)), tree,
        is_leaf=_is_spec)


def validate_divisibility(shapes_tree, specs_tree, mesh: Mesh,
                          rules: Mapping[str, Any] | None = None) -> list[str]:
    """Return a list of human-readable problems where a sharded dim is not
    divisible by the product of its mesh axes (dry-run preflight)."""
    rules = rules or rules_for_mesh(mesh)
    problems: list[str] = []

    def check(path, shape, spec):
        phys = resolve_spec(spec, rules)
        for i, (dim, entry) in enumerate(zip(shape, phys)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % n != 0:
                problems.append(
                    f"{path}: shape {tuple(shape)} spec {phys} — dim[{i}]="
                    f"{dim} not divisible by mesh axes {axes} (size {n})")

    def walk(path, shapes, specs):
        if _is_spec(specs):
            check(path, shapes.shape if hasattr(shapes, "shape") else shapes, specs)
            return
        if isinstance(specs, dict):
            for k in specs:
                walk(f"{path}/{k}", shapes[k], specs[k])
        elif isinstance(specs, (list, tuple)):
            for i, s in enumerate(specs):
                walk(f"{path}[{i}]", shapes[i], s)

    walk("", shapes_tree, specs_tree)
    return problems


# ---------------------------------------------------------------------------
# Collective census (the tp-serving "no hidden communication" assertion)
# ---------------------------------------------------------------------------

COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter", "pgather",
})


def _collective_name(name: str) -> str | None:
    """The census name of a primitive, or None if it is not a collective.
    ``jax.shard_map(check_vma=True)`` traces a psum as ``psum_invariant``
    (and likewise for the other varying-manual-axes variants); they count
    under their plain name, so the census is the same under either
    setting."""
    name = name.removesuffix("_invariant")
    return name if name in COLLECTIVE_PRIMS else None


def collective_census(jaxpr) -> dict[str, int]:
    """Count collective primitives in a (closed) jaxpr, recursing through
    every sub-jaxpr (shard_map bodies, scan bodies, custom_vjp branches).

    The tp-serving invariant this backs (DESIGN.md §13): a head-sharded
    decode/prefill step's census is ``{"psum": 2}`` per layer trace — the
    attention-output and MLP down projections — and NOTHING else; attention
    itself, the paged cache writes, and sampling are communication-free
    because each q-head group is co-located with its kv head.
    """
    counts: dict[str, int] = {}

    def _maybe(v):
        if hasattr(v, "eqns"):              # Jaxpr
            walk(v)
        elif hasattr(v, "jaxpr"):           # ClosedJaxpr
            _maybe(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for x in v:
                _maybe(x)
        elif isinstance(v, dict):
            for x in v.values():
                _maybe(x)

    def walk(j):
        for eq in j.eqns:
            name = _collective_name(eq.primitive.name)
            if name is not None:
                counts[name] = counts.get(name, 0) + 1
            for v in eq.params.values():
                _maybe(v)

    _maybe(jaxpr)
    return counts
