"""IO-aware kernel tuning: every tile size is a resolved decision.

The paper derives its block sizes from the SRAM budget M (Alg. 1 line 1:
``B_c = ceil(M/4d)``); until PR 4 the repo instead hard-coded
``block_q = block_k = 128`` at ~a dozen call sites and kept the Theorem-2
accounting as a benchmark-only artifact. This module is the single audited
decision point those call sites now resolve through:

* ``TileConfig`` — one record of every tile-shaped choice a call makes:
  training/prefill ``(block_q, block_k)``, decode ``(decode_block_k,
  num_decode_splits)``, the accumulator ``variant``, and the grid loop
  order (``kv_major``).
* ``choose_tile_config`` — the ANALYTIC chooser: picks the largest
  lane-aligned tiles whose fwd+bwd VMEM working set
  (``core.io_model.attention_working_set_bytes``) fits a configurable SRAM
  budget, ranked by the Theorem-2 HBM-byte surface
  (``core.io_model.flash_hbm_bytes_tiled``). Pure arithmetic — safe at
  trace time, memoized.
* ``Autotuner`` — the optional EMPIRICAL refinement: times the analytic
  chooser's top candidates on-device and persists the winner in a JSON
  cache keyed by ``(device_kind, dtype, head_dim, seq_bucket, mask_class)``
  so the timing cost is paid once per (hardware, workload) class.
* ``resolve_tiles`` / ``resolve_decode_geometry`` — what consumers call.
  ``AttentionSpec.block_q/block_k/num_decode_splits`` default to ``None``
  (= auto); explicit integers pass through untouched (and are still
  validated), so tests and benchmarks can pin any geometry.

Paged invariant: the page is the mask IR's kv block and the unit of cache
ALLOCATION (DESIGN.md §6.5), so for paged decode the tuner does not get to
choose the kv block — it takes ``page_size`` or rejects an explicit
conflicting ``block_k``.

``python -m repro.kernels.tuning --smoke`` exercises the autotune
write+read roundtrip (scripts/ci.sh runs it twice and asserts the second
run is served from the cache).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any

from repro.core import io_model
from repro.telemetry.metrics import default_registry

LANES = io_model.LANES
SUBLANES = io_model.SUBLANES
MAX_BLOCK = 1024           # beyond this the S tile alone dwarfs any win
TARGET_DECODE_SPLITS = 8   # split-KV parallelism target (cores/megacore)
TARGET_GRID_CELLS = 8      # per-device (head, q-block) cells a sharded
                           # call should keep busy: with heads/tp local
                           # heads, block_q shrinks to recover grid
                           # parallelism lost to the head shard

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "bfloat16": 2, "bf16": 2, "float16": 2,
    "f16": 2, "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def _dtype_name(dtype: Any) -> str:
    try:
        import numpy as np
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def _elt_bytes(dtype: Any) -> int:
    return _DTYPE_BYTES.get(_dtype_name(dtype), 4)


# ---------------------------------------------------------------------------
# TileConfig — the resolved decision record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Every tile-shaped decision one attention call site makes.

    ``kv_major`` records the forward grid's loop order. The present Pallas
    forward kernel iterates kv innermost with q-major accumulators
    (``kv_major=False``); the field keeps the decision explicit so the IO
    model can score both orders and a future kv-major forward slots in
    without widening any signature. ``sp_strategy`` records the
    sequence-parallel KV-movement choice ("allgather" | "ring") for
    entries resolved by ``resolve_sp_strategy`` under the ``|spN``
    namespace (None everywhere else — old cache entries load fine since
    ``from_cache_entry`` filters by field names). ``source`` is
    observability only: "explicit" (caller pinned it), "analytic",
    "cache", or "autotuned".
    """
    block_q: int
    block_k: int
    decode_block_k: int | None = None
    num_decode_splits: int | None = None
    variant: str = "fa2"
    kv_major: bool = False
    sp_strategy: str | None = None
    source: str = "analytic"

    def as_cache_entry(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("source")
        return d

    @classmethod
    def from_cache_entry(cls, entry: dict) -> "TileConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in entry.items() if k in fields},
                   source="cache")


# ---------------------------------------------------------------------------
# Tuner-wide knobs (CLIs: --autotune / --sram-budget)
# ---------------------------------------------------------------------------

_DEFAULT_CACHE = os.environ.get(
    "REPRO_AUTOTUNE_CACHE",
    os.path.join(os.path.expanduser("~"), ".cache", "repro",
                 "autotune.json"))

_STATE: dict[str, Any] = {
    "sram_budget": int(os.environ["REPRO_SRAM_BUDGET"])
    if "REPRO_SRAM_BUDGET" in os.environ else None,
    "autotune": os.environ.get("REPRO_AUTOTUNE", "") == "1",
    "cache_path": _DEFAULT_CACHE,
}


def configure_tuning(*, sram_budget: int | None = None,
                     autotune: bool | None = None,
                     cache_path: str | None = None) -> None:
    """Process-wide tuner knobs (launch CLIs call this from flag values).
    ``None`` leaves a knob unchanged; analytic memoization is dropped so a
    new budget takes effect immediately."""
    if sram_budget is not None:
        _STATE["sram_budget"] = int(sram_budget)
    if autotune is not None:
        _STATE["autotune"] = bool(autotune)
    if cache_path is not None:
        _STATE["cache_path"] = cache_path
        global _CACHE
        _CACHE = None
    _analytic_choice.cache_clear()


def sram_budget() -> int:
    b = _STATE["sram_budget"]
    return io_model.DEFAULT_SRAM_BUDGET if b is None else int(b)


def autotune_enabled() -> bool:
    return bool(_STATE["autotune"])


# ---------------------------------------------------------------------------
# Block clamping (the lane-alignment fix for tiny/ragged sequence lengths)
# ---------------------------------------------------------------------------

def round_block(requested: int, seq_len: int) -> int:
    """Clamp a block size to a sequence WITHOUT producing an unaligned tile.

    The old clamp was ``min(block, seq_len)``: for seq_len = 100 that made a
    100-row tile — not a sublane multiple, so the Mosaic lowering either
    fails or pads every vreg on a real TPU. Instead, cap the block at the
    sequence rounded UP to the sublane multiple (the caller pads the
    operand to a block multiple anyway, so a ragged tail costs at most
    ``SUBLANES - 1`` padded rows) and round the result down to a sublane
    multiple, floor ``SUBLANES``.
    """
    cap = -(-max(seq_len, 1) // SUBLANES) * SUBLANES
    blk = min(int(requested), cap)
    blk = max(SUBLANES, (blk // SUBLANES) * SUBLANES)
    return min(blk, cap)


def _aligned_candidates(seq_len: int) -> list[int]:
    """Descending tile-size candidates for one axis: lane multiples first
    (what the MXU wants), sublane multiples only when the axis itself is
    shorter than one lane tile."""
    cap = min(MAX_BLOCK, -(-max(seq_len, 1) // SUBLANES) * SUBLANES)
    lane = [b for b in range(LANES, cap + 1, LANES)]
    if lane:
        return lane[::-1]
    return [b for b in range(SUBLANES, cap + 1, SUBLANES)][::-1] or [SUBLANES]


# ---------------------------------------------------------------------------
# Analytic chooser (Alg. 1 line 1 with the kernel's true footprint)
# ---------------------------------------------------------------------------

def _divisors_desc(n: int) -> list[int]:
    return [d for d in range(n, 0, -1) if n % d == 0]


def decode_split_target(shards: int = 1,
                        target_splits: int = TARGET_DECODE_SPLITS) -> int:
    """Split-KV parallelism target per device. Under tensor parallelism each
    shard's decode grid is ``(heads/tp) * num_splits`` cells — the head axis
    shrank by ``tp``, so the split count scales UP by ``tp`` to keep the
    per-device grid occupancy constant (per-shard geometry, DESIGN.md §13)."""
    return int(target_splits) * max(1, int(shards))


def choose_decode_geometry(capacity: int, head_dim: int, *,
                           elt: int = 4, budget: int | None = None,
                           target_splits: int = TARGET_DECODE_SPLITS,
                           pinned_splits: int | None = None,
                           ) -> tuple[int, int]:
    """Pick ``(decode_block_k, num_splits)`` for a contiguous cache.

    The split-KV kernel reads every valid cache byte exactly once whatever
    the block size, so the objective is parallelism-then-locality: among
    block sizes that divide the capacity (alignment-preferred, working set
    within budget), maximize the usable split count (capped at
    ``target_splits``), then the block size. Divisibility is guaranteed by
    construction — ``validate_decode_geometry`` can no longer fire for an
    auto-resolved geometry.

    ``pinned_splits`` (an explicit ``num_splits`` with an auto block) is a
    CONSTRAINT on the block search, not a preference: only blocks whose
    grid honors exactly that split count qualify; if no aligned divisor
    does, that's an error — never a silent clamp.
    """
    budget = sram_budget() if budget is None else budget
    cands = [b for b in _divisors_desc(capacity)
             if b % SUBLANES == 0 or b == capacity]
    cands = ([b for b in cands
              if io_model.decode_working_set_bytes(b, head_dim, elt)
              <= budget] or [min(cands, default=capacity)])
    best = None
    for blk in cands:
        nk = capacity // blk
        if pinned_splits is not None:
            if nk % pinned_splits:
                continue
            key = (pinned_splits, blk)
        else:
            splits = next(s for s in _divisors_desc(nk)
                          if s <= target_splits)
            key = (splits, blk)
        if best is None or key > best:
            best = key
    if best is None:
        raise ValueError(
            f"flash_decode: no aligned kv block of the {capacity}-slot "
            f"cache yields a grid divisible by num_splits "
            f"({pinned_splits}); pick a num_splits dividing the block "
            f"count or leave it auto")
    splits, blk = best[0], best[1]
    return blk, splits


def kv_major_fits(sq: int, block_k: int, head_dim: int, *,
                  heads_q: int = 1, heads_kv: int = 1, elt: int = 4,
                  backward: bool = True,
                  budget: int | None = None) -> bool:
    """Can the resident-q kv-major order run this shape at all? The whole
    grouped query block (``(hq/hkv)·sq`` rows) must fit the budget — for
    the forward alone, and for the reused backward kernels too when the
    call is trainable (they run with ``block_q = R``)."""
    budget = sram_budget() if budget is None else budget
    r_rows = max(1, heads_q // max(heads_kv, 1)) * sq
    if io_model.kv_major_working_set_bytes(
            r_rows, block_k, head_dim, in_elt=elt) > budget:
        return False
    if backward and io_model.attention_working_set_bytes(
            r_rows, block_k, head_dim, in_elt=elt,
            backward=True) > budget:
        return False
    return True


def _choose_kv_major(sq: int, sk: int, head_dim: int, bq: int, bk: int, *,
                     heads_q: int, heads_kv: int, elt: int,
                     backward: bool, budget: int) -> bool:
    """Loop-order decision: kv-major iff the two-order cost surface says it
    moves strictly fewer HBM bytes AND the resident group fits."""
    if heads_q < 1 or heads_kv < 1 or heads_q % heads_kv:
        return False
    costs = io_model.prefill_order_hbm_bytes(
        sq, sk, head_dim, heads_q, heads_kv, 1, bq, bk, elt=elt)
    if costs["kv_major"] >= costs["q_major"]:
        return False
    return kv_major_fits(sq, bk, head_dim, heads_q=heads_q,
                         heads_kv=heads_kv, elt=elt, backward=backward,
                         budget=budget)


@functools.lru_cache(maxsize=512)
def _analytic_choice(sq: int, sk: int, head_dim: int, elt: int,
                     backward: bool, budget: int,
                     fixed_bq: int | None, fixed_bk: int | None,
                     decode_capacity: int | None,
                     heads_q: int = 1, heads_kv: int = 1,
                     shards: int = 1) -> TileConfig:
    bq_cands = [fixed_bq] if fixed_bq is not None else _aligned_candidates(sq)
    bk_cands = [fixed_bk] if fixed_bk is not None else _aligned_candidates(sk)
    best: tuple | None = None
    for bq in bq_cands:
        for bk in bk_cands:
            ws = io_model.attention_working_set_bytes(
                bq, bk, head_dim, in_elt=elt, backward=backward)
            fits = ws <= budget
            hbm = io_model.flash_hbm_bytes_tiled(
                sq, sk, head_dim, 1, 1, bq, bk, elt=elt,
                fwd_and_bwd=backward)
            # Sharded calls see only heads/tp local heads, so the (head,
            # q-block) grid can collapse to a couple of cells; prefer tiles
            # that keep TARGET_GRID_CELLS cells busy per device before
            # minimizing HBM bytes (HBM traffic is tile-size-flat near the
            # optimum; idle cores are not). Unsharded calls (shards == 1)
            # rank exactly as before.
            par_ok = (shards <= 1
                      or max(1, heads_q) * -(-sq // bq) >= TARGET_GRID_CELLS)
            # rank: fitting first; among fitting, fewest HBM bytes then the
            # larger tile (fewer grid steps at equal traffic); among
            # non-fitting (caller pinned an over-budget tile, or the budget
            # is below one minimal tile) the smallest working set.
            key = (fits, par_ok, -hbm if fits else -ws, bq + bk, bk)
            if best is None or key > best[:5]:
                best = key + (bq, bk)
    bq, bk = best[5], best[6]
    # Loop-order decision: kv-major holds the WHOLE grouped q side
    # resident, so its kv tile is chosen independently of the q-major
    # optimum above — the largest candidate that still fits beside the
    # resident group (the HBM cost of kv-major is tile-size-invariant:
    # K/V stream exactly once either way).
    kvm = False
    for kbk in sorted(bk_cands, reverse=True):
        if _choose_kv_major(sq, sk, head_dim, bq, kbk, heads_q=heads_q,
                            heads_kv=heads_kv, elt=elt, backward=backward,
                            budget=budget):
            kvm, bk = True, kbk
            break
    dec_blk = dec_splits = None
    if decode_capacity is not None:
        dec_blk, dec_splits = choose_decode_geometry(
            decode_capacity, head_dim, elt=elt, budget=budget)
    return TileConfig(block_q=bq, block_k=bk, decode_block_k=dec_blk,
                      num_decode_splits=dec_splits, kv_major=kvm,
                      source="analytic")


def choose_tile_config(sq: int, sk: int, head_dim: int, *,
                       dtype: Any = "float32", backward: bool = True,
                       sram_budget_bytes: int | None = None,
                       decode_capacity: int | None = None,
                       block_q: int | None = None,
                       block_k: int | None = None,
                       heads_q: int = 1, heads_kv: int = 1,
                       shards: int = 1) -> TileConfig:
    """Analytic tile choice (see module docstring). Explicit ``block_q`` /
    ``block_k`` pin that axis and the chooser fills the rest. ``heads_q`` /
    ``heads_kv`` feed the LOOP-ORDER decision: with them the chooser costs
    both grid orders (``io_model.prefill_order_hbm_bytes``) and sets
    ``kv_major`` when the transposed resident-group order strictly wins
    and fits — the short-N_q/long-N_k serving shapes. ``shards`` > 1 means
    the call runs inside a ``tp``-sharded step with PER-SHARD head counts
    in ``heads_q``/``heads_kv``: the chooser then also keeps per-device
    grid occupancy above ``TARGET_GRID_CELLS`` (block_q shrinks with the
    local head count)."""
    budget = (sram_budget() if sram_budget_bytes is None
              else int(sram_budget_bytes))
    return _analytic_choice(int(sq), int(sk), int(head_dim),
                            _elt_bytes(dtype), bool(backward), budget,
                            block_q, block_k, decode_capacity,
                            int(heads_q), int(heads_kv), int(shards))


# ---------------------------------------------------------------------------
# Empirical autotuner + persistent cache
# ---------------------------------------------------------------------------

def seq_bucket(n: int) -> int:
    """Pow-2 bucket so one timing run covers a band of nearby lengths."""
    b = LANES
    while b < n:
        b *= 2
    return b


def cache_key(device_kind: str, dtype: Any, head_dim: int, bucket: int,
              mask_class: str, shards: int = 1, sp: int = 1) -> str:
    """Autotune cache key. ``shards`` > 1 namespaces tensor-parallel
    resolutions (``|tpN``): the per-shard head count changes which tiles
    win, so a sharded entry must never serve — or be served by — the
    single-device one. ``sp`` > 1 namespaces sequence-parallel prefill
    resolutions (``|spN``, DESIGN.md §14): the per-shard q slab is
    ``1/sp`` of the chunk, so both the winning tiles and the KV-movement
    strategy are sp-specific."""
    key = f"{device_kind}|{_dtype_name(dtype)}|{head_dim}|" \
          f"{bucket}|{mask_class}"
    if shards > 1:
        key += f"|tp{int(shards)}"
    if sp > 1:
        key += f"|sp{int(sp)}"
    return key


# Nominal HBM bandwidth per device kind, the denominator of the autotune
# calibration factor (measured effective bytes/s over what the hardware
# claims). v5e: 819 GB/s, Google Cloud documentation, "TPU v5e". A kind
# with no row — CPU hosts included — has no nominal figure, so its
# calibration factor is None ("not measured"), never a guessed ratio.
_NOMINAL_HBM_BW: dict[str, float] = {
    "TPU v5 lite": io_model.V5E_HBM_BW,
    "TPU v5e": io_model.V5E_HBM_BW,
}


def nominal_hbm_bw(device_kind: str) -> float | None:
    for k, bw in _NOMINAL_HBM_BW.items():
        if k.lower() in device_kind.lower():
            return bw
    return None


class AutotuneCache:
    """JSON-file persistence for autotuned ``TileConfig``s. Load is lazy;
    every ``put`` rewrites the file (entries are few — one per
    (device, dtype, head_dim, bucket, mask) class).

    Besides the per-key entries the file carries a per-``device_kind``
    ``calibration`` aggregate (the ROADMAP "measured-vs-model HBM bytes"
    item): every timed winner whose ``io_model`` byte prediction is known
    contributes ``(model_hbm_bytes, timed_us)``, from which
    :meth:`calibration` derives the effective model-implied bandwidth and
    its ratio to the device's nominal one — the factor by which the
    analytic surface over/under-predicts on this hardware."""

    def __init__(self, path: str):
        self.path = path
        self._entries: dict[str, dict] | None = None
        self._calib: dict[str, dict] | None = None
        self.hits = 0
        self.misses = 0

    def _load(self) -> dict[str, dict]:
        if self._entries is None:
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                self._entries = doc.get("entries", {})
                self._calib = doc.get("calibration", {})
            except (OSError, ValueError):
                self._entries = {}
                self._calib = {}
        return self._entries

    def get(self, key: str) -> TileConfig | None:
        entry = self._load().get(key)
        if entry is None:
            self.misses += 1
            default_registry().counter("tuning_cache_misses").inc()
            return None
        self.hits += 1
        default_registry().counter("tuning_cache_hits").inc()
        return TileConfig.from_cache_entry(entry)

    def _write(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "w") as f:
            json.dump({"version": 1, "entries": self._entries,
                       "calibration": self._calib}, f, indent=1,
                      sort_keys=True)

    def put(self, key: str, cfg: TileConfig, timed_us: float, *,
            model_hbm_bytes: float | None = None,
            device_kind: str | None = None) -> None:
        entries = self._load()
        default_registry().histogram(
            "autotune_timed_us",
            buckets=(10.0, 50.0, 100.0, 500.0, 1000.0, 5000.0, 10000.0,
                     50000.0)).observe(float(timed_us))
        entry = {**cfg.as_cache_entry(), "timed_us": timed_us}
        if model_hbm_bytes is not None:
            entry["model_hbm_bytes"] = float(model_hbm_bytes)
            if timed_us > 0 and device_kind:
                c = self._calib.setdefault(
                    device_kind, {"samples": 0, "model_bytes": 0.0,
                                  "us": 0.0})
                c["samples"] += 1
                c["model_bytes"] += float(model_hbm_bytes)
                c["us"] += float(timed_us)
        entries[key] = entry
        self._write()

    def calibration(self, device_kind: str) -> dict | None:
        """Aggregate calibration for one device kind, or None if no timed
        sample carried a model prediction yet. ``vs_nominal`` is the
        measured-vs-io_model factor: model-implied effective bandwidth
        over the kind's nominal bandwidth (1.0 = the analytic byte counts
        at nominal speed explain the clock exactly); None for a kind with
        no nominal bandwidth."""
        self._load()
        c = (self._calib or {}).get(device_kind)
        if not c or c["us"] <= 0:
            return None
        bytes_per_s = c["model_bytes"] / (c["us"] * 1e-6)
        nominal = nominal_hbm_bw(device_kind)
        return {"samples": c["samples"],
                "model_bytes_per_s": bytes_per_s,
                "vs_nominal": (None if nominal is None
                               else bytes_per_s / nominal)}


_CACHE: AutotuneCache | None = None


def autotune_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None or _CACHE.path != _STATE["cache_path"]:
        _CACHE = AutotuneCache(_STATE["cache_path"])
    return _CACHE


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.replace("|", "_")


def _time_candidates(sq: int, sk: int, head_dim: int, dtype,
                     candidates: list[tuple[int, int, bool]], *,
                     causal: bool, heads_q: int = 2, heads_kv: int = 2,
                     backward: bool = False,
                     iters: int = 3) -> tuple[int, int, bool, float]:
    """Time one call per ``(block_q, block_k, kv_major)`` candidate
    on-device, return the winner. ``backward=True`` times the full
    fwd+grad pipeline — the split dq (q-major grid) and dkv (kv-major
    grid) kernels run under the same tile config as the forward, so the
    winning tile is the one that wins the TRAINING step, not just the
    forward. Candidates are explicit, so the timed calls never re-enter
    resolution."""
    import time

    import jax
    import jax.numpy as jnp  # noqa: F401 — dtype strings resolve through jnp

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, heads_q, sq, head_dim), dtype)
    k = jax.random.normal(ks[1], (1, heads_kv, sk, head_dim), dtype)
    v = jax.random.normal(ks[2], (1, heads_kv, sk, head_dim), dtype)
    best: tuple[float, int, int, bool] | None = None
    for bq, bk, kvm in candidates:
        call = functools.partial(ops.flash_attention, causal=causal,
                                 block_q=bq, block_k=bk, kv_major=kvm)
        if backward:
            fn = jax.jit(jax.grad(
                lambda a, b, c, _call=call: _call(a, b, c).sum(),
                argnums=(0, 1, 2)))
        else:
            fn = jax.jit(call)
        jax.block_until_ready(fn(q, k, v))          # compile outside timing
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            ts.append(time.perf_counter() - t0)
        t = min(ts)
        if best is None or t < best[0]:
            best = (t, bq, bk, kvm)
    return best[1], best[2], best[3], best[0] * 1e6


def autotune_tiles(sq: int, sk: int, head_dim: int, *, dtype,
                   mask_class: str, backward: bool = True,
                   max_candidates: int = 4,
                   block_q: int | None = None,
                   block_k: int | None = None,
                   heads_q: int = 1, heads_kv: int = 1,
                   shards: int = 1, sp: int = 1) -> TileConfig:
    """Empirical resolution: cache lookup, else time the analytic chooser's
    top fitting candidates and persist the winner. A pinned ``block_q`` /
    ``block_k`` axis CONSTRAINS the candidate list (only combinations that
    honor the pin are timed) and is part of the cache key — a pinned call
    never reuses, or pollutes, the unpinned entry. The loop order is part
    of the decision: when the two-order cost model says kv-major can win
    the shape, a kv-major candidate is timed against the q-major ones and
    the winning order is persisted in the entry's ``kv_major`` field (the
    head-group ratio joins the key — the order decision is meaningless
    across different grouping). ``backward=True`` (trainable call sites)
    times the fwd+grad pipeline — the split dq/dkv kernels share the
    forward's tiles, and the bwd working set changes which tiles fit —
    under its own ``|bwd`` key namespace, so inference and training
    resolutions never serve each other's winner."""
    bucket = seq_bucket(max(sq, sk))
    key = cache_key(_device_kind(), dtype, head_dim, bucket, mask_class,
                    shards=shards, sp=sp)
    if block_q is not None:
        key += f"|bq={block_q}"
    if block_k is not None:
        key += f"|bk={block_k}"
    n_rep = max(1, heads_q // max(heads_kv, 1))
    if n_rep > 1:
        key += f"|g={n_rep}"
    if backward:
        key += "|bwd"
    cache = autotune_cache()
    hit = cache.get(key)
    if hit is not None:
        return hit
    analytic = choose_tile_config(bucket, bucket, head_dim, dtype=dtype,
                                  backward=backward,
                                  block_q=block_q, block_k=block_k,
                                  heads_q=heads_q, heads_kv=heads_kv,
                                  shards=shards)
    budget = sram_budget()
    elt = _elt_bytes(dtype)
    cands: list[tuple[int, int, bool]] = [
        (analytic.block_q, analytic.block_k, analytic.kv_major)]
    bq_cands = [block_q] if block_q is not None else _aligned_candidates(bucket)
    bk_cands = [block_k] if block_k is not None else _aligned_candidates(bucket)
    for bq in bq_cands:
        for bk in bk_cands:
            ws = io_model.attention_working_set_bytes(
                bq, bk, head_dim, in_elt=elt, backward=backward)
            if ws <= budget and (bq, bk, False) not in cands:
                cands.append((bq, bk, False))
    cands = cands[:max_candidates]
    if not analytic.kv_major and kv_major_fits(
            bucket, analytic.block_k, head_dim, heads_q=heads_q,
            heads_kv=heads_kv, elt=elt, backward=backward, budget=budget):
        # let the clock referee the loop order even when the byte model
        # called it for q-major — the timed winner is what persists.
        cands.append((analytic.block_q, analytic.block_k, True))
    bq, bk, kvm, t_us = _time_candidates(
        sq=bucket, sk=bucket, head_dim=head_dim, dtype=dtype,
        candidates=cands, causal="causal" in mask_class,
        heads_q=max(heads_q, 1), heads_kv=max(heads_kv, 1),
        backward=backward)
    cfg = dataclasses.replace(analytic, block_q=bq, block_k=bk,
                              kv_major=kvm, source="autotuned")
    # calibration sample: the winner's io_model byte prediction for the
    # TIMED shape (batch 1, heads_q heads) vs its clock (ROADMAP item).
    model_bytes = io_model.flash_hbm_bytes_tiled(
        bucket, bucket, head_dim, max(heads_q, 1), 1, bq, bk, elt=elt,
        fwd_and_bwd=backward, kv_major=kvm)
    cache.put(key, cfg, t_us, model_hbm_bytes=model_bytes,
              device_kind=_device_kind())
    return cfg


def _time_decode_candidates(capacity: int, head_dim: int, dtype,
                            candidates: list[tuple[int, int]], *,
                            page_size: int | None = None,
                            iters: int = 3) -> tuple[int, int, float]:
    """Time the decode kernel per ``(block_k, num_splits)`` candidate —
    contiguous (``flash_decode``) or paged (``flash_decode_paged``)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import flash_decode as fd

    hq = hkv = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, hq, 1, head_dim), dtype)
    kv_len = jnp.asarray([capacity], jnp.int32)
    if page_size is None:
        kc = jax.random.normal(ks[1], (1, hkv, capacity, head_dim), dtype)
        vc = jax.random.normal(ks[2], (1, hkv, capacity, head_dim), dtype)

        def _make(blk, splits):
            fn = jax.jit(functools.partial(fd.flash_decode, block_k=blk,
                                           num_splits=splits))
            return fn, (q, kc, vc, kv_len)
    else:
        pages = max(1, capacity // page_size)
        kp = jax.random.normal(ks[1], (hkv, pages, page_size, head_dim),
                               dtype)
        vp = jax.random.normal(ks[2], (hkv, pages, page_size, head_dim),
                               dtype)
        table = jnp.arange(pages, dtype=jnp.int32)[None]

        def _make(blk, splits):
            fn = jax.jit(functools.partial(fd.flash_decode_paged,
                                           num_splits=splits))
            return fn, (q, kp, vp, table, kv_len)

    best: tuple[float, int, int] | None = None
    for blk, splits in candidates:
        fn, call_args = _make(blk, splits)
        jax.block_until_ready(fn(*call_args))       # compile outside timing
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*call_args))
            ts.append(time.perf_counter() - t0)
        t = min(ts)
        if best is None or t < best[0]:
            best = (t, blk, splits)
    return best[1], best[2], best[0] * 1e6


def autotune_decode_geometry(capacity: int, head_dim: int, *, dtype,
                             page_size: int | None = None,
                             target_splits: int = TARGET_DECODE_SPLITS,
                             max_candidates: int = 4,
                             shards: int = 1) -> TileConfig:
    """Empirical decode resolution: time ``(decode_block_k, num_splits)``
    candidates and persist the winner — the ROADMAP "Autotune coverage"
    item. Keyed by EXACT capacity (not the pow-2 bucket): split validity is
    a divisibility property of the real grid, so a bucket-timed entry could
    hand a neighboring capacity an invalid geometry. For a paged cache the
    block is pinned to the page (allocation-unit invariant) and only the
    split count is searched."""
    kind = f"paged{page_size}" if page_size is not None else "contig"
    key = (f"decode|{_device_kind()}|{_dtype_name(dtype)}|{head_dim}|"
           f"{capacity}|{kind}")
    if shards > 1:
        key += f"|tp{int(shards)}"
        target_splits = decode_split_target(shards, target_splits)
    cache = autotune_cache()
    hit = cache.get(key)
    if hit is not None and hit.decode_block_k is not None:
        return hit
    cands: list[tuple[int, int]] = []
    if page_size is not None:
        pages = max(1, capacity // page_size)
        for s in _divisors_desc(pages):
            if s <= 2 * target_splits:
                cands.append((page_size, s))
    else:
        blk, splits = choose_decode_geometry(capacity, head_dim,
                                             elt=_elt_bytes(dtype),
                                             target_splits=target_splits)
        cands.append((blk, splits))
        for b2 in _divisors_desc(capacity):
            if b2 % SUBLANES or b2 == capacity:
                continue
            nk = capacity // b2
            s2 = next(s for s in _divisors_desc(nk) if s <= target_splits)
            if (b2, s2) not in cands:
                cands.append((b2, s2))
    blk, splits, t_us = _time_decode_candidates(
        capacity, head_dim, dtype, cands[:max_candidates],
        page_size=page_size)
    cfg = TileConfig(block_q=1, block_k=blk, decode_block_k=blk,
                     num_decode_splits=splits, source="autotuned")
    # calibration: decode reads every valid K/V byte exactly once — the
    # timing harness runs 2 kv heads at full capacity (q/o traffic ~0).
    model_bytes = float(2 * 2 * capacity * head_dim * _elt_bytes(dtype))
    cache.put(key, cfg, t_us, model_hbm_bytes=model_bytes,
              device_kind=_device_kind())
    return cfg


# ---------------------------------------------------------------------------
# Resolution entry points (what the kernels / engine / models call)
# ---------------------------------------------------------------------------

def mask_class_of(*, causal: bool = False, window: int | None = None,
                  has_kv_mask: bool = False, has_segments: bool = False,
                  has_sparse: bool = False,
                  has_positions: bool = False) -> str:
    parts = [p for p, on in [("causal", causal), ("win", window is not None),
                             ("seg", has_segments), ("kvm", has_kv_mask),
                             ("sparse", has_sparse),
                             ("pos", has_positions)] if on]
    return "+".join(parts) or "dense"


def resolve_tiles(block_q: int | None, block_k: int | None, *,
                  sq: int, sk: int, head_dim: int, dtype: Any,
                  mask_class: str = "dense",
                  backward: bool = True,
                  heads_q: int = 1, heads_kv: int = 1,
                  shards: int = 1) -> TileConfig:
    """THE audited decision point for training/prefill tiles.

    Explicit (non-``None``) values pass through untouched; ``None`` means
    auto — empirical when autotuning is enabled, analytic otherwise. The
    caller still owes ``round_block`` against its true (possibly ragged)
    sequence lengths: resolution works on the padded geometry.
    ``heads_q``/``heads_kv`` inform the loop-order (``kv_major``) decision;
    a call that pins both blocks has opted out of resolution entirely, so
    its config keeps the default q-major order. ``shards`` is the tensor-
    parallel shard count of the calling step (1 = unsharded): it joins the
    autotune cache key and biases the chooser toward per-device grid
    occupancy, since ``heads_q``/``heads_kv`` are then per-shard counts.
    """
    if block_q is not None and block_k is not None:
        return TileConfig(block_q=int(block_q), block_k=int(block_k),
                          source="explicit")
    if autotune_enabled():
        return autotune_tiles(sq, sk, head_dim, dtype=dtype,
                              mask_class=mask_class, backward=backward,
                              block_q=block_q, block_k=block_k,
                              heads_q=heads_q, heads_kv=heads_kv,
                              shards=shards)
    return choose_tile_config(sq, sk, head_dim, dtype=dtype,
                              backward=backward,
                              block_q=block_q, block_k=block_k,
                              heads_q=heads_q, heads_kv=heads_kv,
                              shards=shards)


def resolve_sp_strategy(chunk: int, prefix: int, head_dim: int, *,
                        heads_q: int = 1, heads_kv: int = 1, sp: int = 1,
                        dtype: Any = "float32", layers: int = 1) -> dict:
    """Resolve the sequence-parallel prefill KV-movement strategy and the
    per-shard (slab) tiles for one engine shape (DESIGN.md §14).

    Costs both strategies against replicated prefill via
    ``io_model.sp_prefill_hbm_bytes`` using the slab's analytically chosen
    ``block_q`` (``heads_q``/``heads_kv`` are PER-TP-SHARD counts, matching
    what the sharded step's kernels see). With autotuning enabled the
    decision persists under the ``|spN`` cache-key namespace — the
    ``TileConfig`` entry carries both the slab tiles and ``sp_strategy`` —
    so repeat engines resolve from the cache.

    Returns ``{"strategy", "costs", "tiles", "source"}``; at sp <= 1 the
    strategy is "allgather" (degenerate: never used) and nothing persists.
    """
    slab = max(1, -(-int(chunk) // max(1, int(sp))))
    tiles = choose_tile_config(slab, prefix + chunk, head_dim, dtype=dtype,
                               backward=False, heads_q=heads_q,
                               heads_kv=heads_kv, shards=max(1, sp))
    costs = io_model.sp_prefill_hbm_bytes(
        chunk, prefix, head_dim, max(1, heads_q), max(1, heads_kv), sp,
        block_q=tiles.block_q, elt=_elt_bytes(dtype), layers=max(1, layers))
    if sp <= 1:
        return {"strategy": "allgather", "costs": costs, "tiles": tiles,
                "source": "analytic"}
    strategy = costs["best"]
    if autotune_enabled():
        key = cache_key(_device_kind(), dtype, head_dim, seq_bucket(chunk),
                        "causal+seg+pos", sp=sp)
        cache = autotune_cache()
        hit = cache.get(key)
        if hit is not None and hit.sp_strategy in ("allgather", "ring"):
            return {"strategy": hit.sp_strategy, "costs": costs,
                    "tiles": hit, "source": "cache"}
        cfg = dataclasses.replace(tiles, sp_strategy=strategy)
        # analytic decision, not a timed one: no calibration sample.
        cache.put(key, cfg, 0.0)
        return {"strategy": strategy, "costs": costs, "tiles": cfg,
                "source": "analytic"}
    return {"strategy": strategy, "costs": costs, "tiles": tiles,
            "source": "analytic"}


def resolve_decode_geometry(capacity: int, block_k: int | None,
                            num_splits: int | None, *, head_dim: int,
                            dtype: Any = "float32",
                            page_size: int | None = None,
                            target_splits: int = TARGET_DECODE_SPLITS,
                            shards: int = 1) -> tuple[int, int]:
    """Resolve decode ``(block_k, num_splits)`` for a contiguous or paged
    cache. For a paged cache the kv block IS the page (allocation-unit
    invariant, DESIGN.md §6.5): an explicit conflicting ``block_k`` is
    rejected, never silently overridden; ``capacity`` is then the
    per-sequence capacity (``pages_per_seq * page_size``).

    Explicit values are validated exactly as before (misalignment raises);
    auto values are valid by construction.
    """
    from repro.kernels.flash_decode import (validate_decode_geometry,
                                            validate_paged_decode_geometry)

    if block_k is None and num_splits is None and autotune_enabled():
        # Fully-auto geometry with the autotuner on: serve the timed winner.
        # The timed candidates pass explicit geometry, so no re-entry here.
        cfg = autotune_decode_geometry(capacity, head_dim, dtype=dtype,
                                       page_size=page_size,
                                       target_splits=target_splits,
                                       shards=shards)
        block_k, num_splits = cfg.decode_block_k, cfg.num_decode_splits
    if shards > 1:
        # per-shard geometry: the head grid shrank by tp, splits scale up
        target_splits = decode_split_target(shards, target_splits)

    if page_size is not None:
        if block_k is not None and int(block_k) != int(page_size):
            raise ValueError(
                f"paged decode: block_k ({block_k}) must equal page_size "
                f"({page_size}) — the page is the unit of cache allocation "
                f"and the mask IR's kv block; re-tile the pool or leave "
                f"block_k auto")
        pages_per_seq = max(1, capacity // page_size)
        if num_splits is None:
            num_splits = next(s for s in _divisors_desc(pages_per_seq)
                              if s <= target_splits)
        else:
            num_splits = validate_paged_decode_geometry(pages_per_seq,
                                                        int(num_splits))
        return int(page_size), int(num_splits)

    if block_k is None:
        block_k, num_splits = choose_decode_geometry(
            capacity, head_dim, elt=_elt_bytes(dtype),
            target_splits=target_splits,
            pinned_splits=None if num_splits is None else int(num_splits))
    elif num_splits is None:
        block_k = min(int(block_k), capacity)
        nk = max(1, capacity // max(int(block_k), 1))
        num_splits = next(s for s in _divisors_desc(nk)
                          if s <= target_splits)
    return validate_decode_geometry(capacity, int(block_k), int(num_splits))


# ---------------------------------------------------------------------------
# CLI: the CI smoke roundtrip
# ---------------------------------------------------------------------------

def _main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape (256, d=64) so CI stays cheap")
    ap.add_argument("--cache", default=None, help="autotune cache path")
    ap.add_argument("--sram-budget", type=int, default=None)
    ap.add_argument("--expect-hit", action="store_true",
                    help="fail unless resolution was served from the cache")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard count: resolve against the "
                         "per-shard cache-key namespace (|tpN)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel shard count: resolve the sp "
                         "prefill strategy + slab tiles under |spN")
    args = ap.parse_args()

    configure_tuning(sram_budget=args.sram_budget, autotune=True,
                     cache_path=args.cache)
    seq = args.seq if args.seq is not None else (256 if args.smoke else 2048)
    import jax.numpy as jnp
    cfg = autotune_tiles(seq, seq, args.head_dim, dtype=jnp.float32,
                         mask_class="causal", backward=False,
                         shards=args.tp)
    cache = autotune_cache()
    fixed = io_model.flash_hbm_bytes_tiled(seq, seq, args.head_dim, 1, 1,
                                           128, 128, elt=4)
    chosen = io_model.flash_hbm_bytes_tiled(seq, seq, args.head_dim, 1, 1,
                                            cfg.block_q, cfg.block_k, elt=4)
    hit = cfg.source == "cache"
    print(f"autotune seq={seq} d={args.head_dim}: block_q={cfg.block_q} "
          f"block_k={cfg.block_k} source={cfg.source} "
          f"hbm_vs_128x128={chosen / fixed:.3f} cache_hit={hit} "
          f"(hits={cache.hits} misses={cache.misses}) path={cache.path}")
    bwd = autotune_tiles(seq, seq, args.head_dim, dtype=jnp.float32,
                         mask_class="causal", backward=True,
                         shards=args.tp)
    bwd_hit = bwd.source == "cache"
    print(f"autotune bwd seq={seq} d={args.head_dim}: block_q={bwd.block_q} "
          f"block_k={bwd.block_k} source={bwd.source} cache_hit={bwd_hit}")
    dec = autotune_decode_geometry(seq, args.head_dim, dtype=jnp.float32,
                                   shards=args.tp)
    dec_hit = dec.source == "cache"
    print(f"autotune decode cap={seq} d={args.head_dim}: "
          f"block_k={dec.decode_block_k} splits={dec.num_decode_splits} "
          f"source={dec.source} cache_hit={dec_hit}")
    sp_hit = True
    if args.sp > 1:
        res = resolve_sp_strategy(seq, 4 * seq, args.head_dim, heads_q=2,
                                  heads_kv=2, sp=args.sp,
                                  dtype=jnp.float32)
        sp_hit = res["source"] == "cache"
        c = res["costs"]
        print(f"autotune sp={args.sp} chunk={seq}: "
              f"strategy={res['strategy']} source={res['source']} "
              f"cache_hit={sp_hit} "
              f"speedup_vs_replicated="
              f"{c['replicated'] / min(c['allgather'], c['ring']):.2f}")
    kind = _device_kind()
    cal = cache.calibration(kind)
    if cal is not None:
        ratio = ("not measured" if cal["vs_nominal"] is None else
                 f"{cal['vs_nominal']:.3f}x nominal "
                 f"({nominal_hbm_bw(kind) / 1e9:.0f} GB/s)")
        print(f"calibration[{kind}]: io_model-implied "
              f"{cal['model_bytes_per_s'] / 1e9:.2f} GB/s over "
              f"{cal['samples']} timed samples = {ratio}")
    if args.expect_hit and not (hit and bwd_hit and dec_hit and sp_hit):
        raise SystemExit("expected a cache hit but resolution re-tuned "
                         f"(fwd={hit} bwd={bwd_hit} decode={dec_hit} "
                         f"sp={sp_hit})")


if __name__ == "__main__":
    _main()
