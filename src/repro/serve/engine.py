"""Serving engine: a thin EXECUTOR for the continuous-batching scheduler
(serve/scheduler.py), over a PAGED KV cache by default.

Why this is the paper's payoff at serving time: the decode step's attention
reads O(kv_len) cache bytes per token (no N x N materialization), so a
sequence's memory footprint is exactly its cache length — FlashAttention's
linear memory is what makes large decode batches fit at all (paper §4.3,
Fig. 3 right). The paged cache (serve/kv_cache.py, DESIGN.md §6) allocates
that memory in mask-IR kv blocks ("pages"), and FlashAttention's tiling
makes a long-prompt prefill cheap PER CHUNK — a query chunk attends to all
prior KV in one call via the mask IR's traced positions (per-segment
q_offset, DESIGN.md §10) — which is what the scheduler exploits to
interleave chunked prefill with decode.

Division of labour (DESIGN.md §10):

  * **ChunkScheduler** owns every policy decision — admission (FIFO under
    lane + free-page budgets), per-step chunk emission under a token
    budget, partial-prompt page growth, preemption at chunk boundaries,
    capacity finishes, fairness. It is model-free and unit-tested without
    jax (tests/test_scheduler.py).
  * **ServingEngine** executes the returned ``StepPlan``: at most one
    packed zero-offset prefill call (chunks starting at position 0 — pure
    packed self-attention, the historical path), one packed suffix-chunk
    call (``Model.prefill_chunk_paged``: scatter the chunks' K/V rows into
    pages, attend each segment's gathered prefix with traced positions),
    and one batched decode step per scheduler step. It also owns the
    device state (pool upload, host kv_len mirror) and the Request
    bookkeeping (EOS, token budgets, preemption requeue-vs-finish).

Chunked prefill (``chunk_size=...``, paged mode only) is what stops a 32k
prompt from head-of-line blocking decode: the prompt prefills
``chunk_size`` tokens per step while every running sequence keeps decoding
one token per step, and the two interleave inside one step loop under
``token_budget`` total tokens. ``chunk_size=None`` (default) is atomic
prefill — the historical behaviour, and exactly the degenerate chunking
whose one chunk covers the whole prompt; greedy outputs are
token-identical across ALL chunk sizes (tests/test_chunked_prefill.py).

Sampling (serve/sampling.py): ``submit(..., temperature=, top_p=, seed=)``
— the sampling key is a pure function of (seed, position), so
preempt->resume is token-identical under sampling too, not just greedy.

Dense mode (``paged=False``, and automatically for SSM/hybrid/enc-dec/
frontend families whose recurrent state cannot be paged) keeps the
fixed-slot cache and atomic prefill, driven through the same scheduler
(no page accounting) — it remains the exactness baseline.

Prefix caching (on by default in paged mode, ``prefix_cache=False`` to
disable): ``submit`` stages the prompt's rolling content hash with the
allocator, admission maps any indexed full-page prefix read-only into the
new request's table (scheduler counts only suffix pages), and the chunk
executors publish pages as their rows materialize — see kv_cache.py and
DESIGN.md §12. A hit's skipped rows are credited in HBM bytes via
``io_model.prefix_cache_hbm_bytes_saved``.

Tensor parallelism (``tp=N``, paged dense-family mode; DESIGN.md §13):
the page pool and every attention/MLP projection shard over a ``("tp",)``
mesh by HEADS / FFN hidden dim — each shard owns whole kv heads together
with their q-head groups, so decode and paged prefill run collective-free
and only the two per-layer output projections ``psum``. The scheduler,
allocator, page tables, and prefix-cache index stay host-global: one
logical pool, per-shard head slices, page indices valid on every shard.

``prefill_calls`` / ``decode_calls`` count model invocations;
``preemptions`` / ``peak_active`` / ``kv.utilization()`` expose scheduler
behaviour (printed by launch/serve.py per step); ``prefix_cache_hit_rate``
/ ``prefill_tokens_skipped`` / ``prefill_hbm_bytes_saved`` the cache;
``latency_stats()`` per-request TTFT and per-token decode percentiles.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import io_model, masks
from repro.core.masks import POS_PAD, SEG_PAD_Q
from repro.distributed import meshes as dist_meshes
from repro.distributed import sharding as dist_sharding
from repro.kernels import tuning
from repro.models.attention_layer import attn_spec_from_config
from repro.models.model_zoo import Model
from repro.serve import kv_cache as kvc
from repro.serve import sampling
from repro.serve.scheduler import ChunkScheduler, ChunkTask, SchedulerConfig
from repro.telemetry import IOLedger, ServePriceModel, Telemetry


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    params: sampling.SamplingParams = dataclasses.field(
        default_factory=sampling.SamplingParams)
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # latency observability: submit wall-clock and first-generated-token
    # wall-clock (None until the first chunk of prefill completes; survives
    # preempt->resume — the FIRST emission is the TTFT).
    t_submit: float = 0.0
    t_first: float | None = None

    @property
    def resume_tokens(self) -> list[int]:
        """Prefill input: the prompt plus anything generated before a
        preemption. Re-running this prefix reproduces the continuation
        token-identically — greedy trivially, sampling because the key of
        the i-th generated token depends only on (seed, i)."""
        return self.prompt + self.output


class ServingEngine:
    def __init__(self, model: Model, params, *, num_slots: int,
                 capacity: int, eos_id: int | None = None,
                 packed_prefill: bool = True,
                 prefill_bucket: int = 64, paged: bool | None = None,
                 page_size: int = 16, num_pages: int | None = None,
                 chunk_size: int | None = None,
                 token_budget: int | None = None,
                 chunk_kv_bucket: int | None = None,
                 prefix_cache: bool | None = None,
                 tp: int = 1, sp: int = 1,
                 sp_strategy: str | None = None,
                 telemetry: Telemetry | None = None, trace: bool = False):
        self.model = model
        self.params = params
        self.B = num_slots
        self.capacity = capacity
        self.eos_id = eos_id
        self.packed_prefill = packed_prefill and model.supports_packed_prefill()
        self.prefill_bucket = prefill_bucket
        # Telemetry bundle (registry + tracer + IO ledger, DESIGN.md §15):
        # every historical ad-hoc counter becomes a registry series and the
        # attribute names below survive as read-only property views. A
        # shared bundle (``telemetry=``) puts engine + scheduler metrics on
        # one scrape surface; ``trace=True`` records the per-step /
        # per-request event timeline (exported via ``tm.tracer``).
        self.tm = telemetry if telemetry is not None else Telemetry(trace=trace)
        reg = self.tm.registry
        self._c_prefill_calls = reg.counter(
            "serve_prefill_calls", "model prefill invocations")
        self._c_decode_calls = reg.counter(
            "serve_decode_calls", "batched decode invocations")
        # packed-prefill block-skip observability (mask IR, DESIGN.md §3):
        # how many attention blocks the compiled layout proves skippable
        # (cross-document + padded-tail), cumulated over packed prefills.
        self._c_blocks_skipped = reg.counter(
            "serve_blocks_skipped", "mask-IR blocks proven skippable")
        self._c_blocks_total = reg.counter(
            "serve_blocks_total", "mask-IR blocks in packed layouts")
        self._g_layout_density = reg.gauge(
            "serve_prefill_layout_density",
            "1 - skip rate of the last packed layout")
        self._g_layout_density.set(1.0)
        # scheduler observability (both modes; paged specifics are zero in
        # dense mode).
        self._c_preemptions = reg.counter(
            "serve_preemptions", "preempted requests requeued/finished")
        self._g_peak_active = reg.gauge(
            "serve_peak_active", "max concurrently active lanes")
        self._g_step = {
            name: reg.gauge(f"serve_step_{name}",
                            f"last step's {name.replace('_', ' ')}")
            for name in ("active", "occupancy", "pool_utilization",
                         "prefill_tokens", "decode_tokens",
                         "deferred_chunks", "queued")}
        self._h_ttft = reg.histogram(
            "serve_ttft_s", "submit -> first generated token (s)")
        self._h_tok = reg.histogram(
            "serve_tok_latency_s", "per-token decode step latency (s)")
        self._stepped = False
        self._step_idx = 0
        self._preempted_rids: set[int] = set()

        can_page = model.supports_paged_decode()
        self.paged = can_page if paged is None else bool(paged)
        if self.paged and not can_page:
            raise ValueError(
                f"paged decode needs a per-token KV cache; family "
                f"{model.cfg.family!r} (hybrid={model.cfg.hybrid}) carries "
                f"recurrent/encoder state that cannot be paged")
        if chunk_size is not None and not self.paged:
            raise ValueError(
                "chunked prefill appends to paged KV state; the dense slot "
                "cache only supports atomic prefill (chunk_size=None)")
        if prefix_cache and not self.paged:
            raise ValueError(
                "prefix caching shares pool pages across page tables; the "
                "dense slot cache has neither (prefix_cache=False)")
        # Copy-on-write prefix caching (kv_cache.py / DESIGN.md §12): on by
        # default in paged mode — a miss costs one index walk at admission.
        self.prefix_cache = self.paged if prefix_cache is None \
            else bool(prefix_cache)
        cfg = model.cfg

        # ---- tensor parallelism over a ("tp",) mesh (DESIGN.md §13) ----
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if self.tp > 1:
            if not self.paged:
                raise ValueError(
                    "tensor-parallel serving shards the page pool over "
                    "heads; dense slot mode supports tp=1 only (pass "
                    "paged=True)")
            if cfg.family != "dense":
                raise ValueError(
                    f"tp>1 serving shards attention heads and the dense "
                    f"MLP hidden dim; family {cfg.family!r} is out of "
                    f"scope (expert parallelism is a separate axis)")
            # GQA: every shard must own WHOLE kv heads, each co-located
            # with its full q-head group, or decode attention would need a
            # collective. Fail here, at construction, not inside a deep
            # shard_map trace.
            if cfg.num_kv_heads % self.tp:
                raise ValueError(
                    f"GQA kv heads ({cfg.num_kv_heads}) not divisible by "
                    f"tp={self.tp}: each shard must own whole kv heads "
                    f"(with their q-head groups) for collective-free "
                    f"decode attention")
            if cfg.num_heads % self.tp:
                raise ValueError(
                    f"query heads ({cfg.num_heads}) not divisible by "
                    f"tp={self.tp}")
            if cfg.d_ff and cfg.d_ff % self.tp:
                raise ValueError(
                    f"d_ff ({cfg.d_ff}) not divisible by tp={self.tp}")

        # ---- sequence parallelism over the sp axis (DESIGN.md §14) ----
        self.sp = int(sp)
        if self.sp < 1:
            raise ValueError(f"sp must be >= 1, got {sp}")
        if self.sp > 1:
            if not self.paged:
                raise ValueError(
                    "sequence-parallel prefill shards the packed chunk "
                    "call's query rows; dense slot mode supports sp=1 only "
                    "(pass paged=True)")
            if cfg.family != "dense":
                raise ValueError(
                    f"sp>1 serving runs prefill through the paged chunk "
                    f"step; family {cfg.family!r} is out of scope")
            # every packed prefill call pads its width to prefill_bucket;
            # rounding the bucket to sp * SUBLANES keeps each shard's slab
            # a whole number of lane-aligned sublane rows (ragged final
            # slabs are SEG_PAD_Q/POS_PAD padding rows that self-mask).
            m = self.sp * io_model.SUBLANES
            self.prefill_bucket += (-self.prefill_bucket) % m
            chunk_hint = chunk_size or self.prefill_bucket
            res = tuning.resolve_sp_strategy(
                chunk_hint, capacity, cfg.head_dim,
                heads_q=cfg.num_heads // self.tp,
                heads_kv=cfg.num_kv_heads // self.tp,
                sp=self.sp, dtype=cfg.dtype, layers=cfg.num_layers)
            self.sp_prefill_costs = res["costs"]
            sp_strategy = sp_strategy or res["strategy"]
            if sp_strategy not in ("allgather", "ring"):
                raise ValueError(
                    f"sp_strategy must be 'allgather' or 'ring', "
                    f"got {sp_strategy!r}")
            self.sp_strategy: str | None = sp_strategy
        else:
            self.sp_strategy = None
            self.sp_prefill_costs = None
        # seeds every content-hash chain: pages must never collide across
        # model weights / dtype / attention geometry identities.
        self._model_key = (f"{cfg.name}|{cfg.family}|{cfg.dtype}"
                           f"|L{cfg.num_layers}|hq{cfg.num_heads}"
                           f"|hkv{cfg.num_kv_heads}|d{cfg.head_dim}"
                           f"|V{cfg.vocab_size}")
        self._c_prefix_lookups = reg.counter(
            "serve_prefix_lookups", "admissions with lookup enabled")
        self._c_prefix_hits = reg.counter(
            "serve_prefix_hits", "admissions mapping >= 1 page")
        self._c_prefix_pages = reg.counter(
            "serve_prefix_pages_shared", "pages mapped from the index")
        self._c_tokens_skipped = reg.counter(
            "serve_prefill_tokens_skipped", "prompt rows never prefilled")
        self._c_hbm_saved = reg.counter(
            "serve_prefill_hbm_bytes_saved", "io_model credit for those rows")
        # hot-path IO the in-place kv side no longer pays: the bytes the
        # per-layer prefix gather (read pages + write packed rows, K and V)
        # would have moved for the same chunk steps.
        self._c_gather_elim = reg.counter(
            "serve_prefill_gather_bytes_eliminated",
            "prefix-gather bytes the paged chunk path avoids")

        self.requests: dict[int, Request] = {}
        self.slot_req: list[Request | None] = [None] * num_slots
        self.finished: list[Request] = []
        self.next_token = np.zeros((num_slots,), np.int32)
        self._rid = itertools.count()
        self._sample = jax.jit(sampling.sample_tokens)

        if self.tp > 1 or self.sp > 1:
            # The mesh and the per-shard MODEL VIEW: inside shard_map every
            # array is a per-shard slice, so the step functions trace with a
            # config whose head/ff counts are the per-shard ones and whose
            # tp_axis makes the two projection boundaries psum
            # (models/attention_layer._tp_reduce). Host bookkeeping (page
            # allocator, prefix hashes, io accounting) keeps the GLOBAL cfg.
            # sp composes as the leading axis of a 2-D ("sp", "tp") mesh
            # (DESIGN.md §14); the tp axis — size 1 when only sp is
            # requested — always carries the projection psums, so the
            # census contract is uniform whenever the mesh is active.
            self.mesh = (dist_meshes.sp_tp_mesh(self.sp, self.tp)
                         if self.sp > 1 else dist_meshes.tp_mesh(self.tp))
            shard_cfg = dataclasses.replace(
                cfg,
                num_heads=cfg.num_heads // self.tp,
                num_kv_heads=cfg.num_kv_heads // self.tp,
                d_ff=cfg.d_ff // self.tp,
                tp_axis="tp", tp_shards=self.tp,
                sp_axis="sp" if self.sp > 1 else None,
                sp_shards=self.sp,
                sp_strategy=self.sp_strategy or cfg.sp_strategy)
            self._shard_model = type(model)(shard_cfg)
            rules = (dist_sharding.sp_serve_rules() if self.sp > 1
                     else dist_sharding.tp_serve_rules())
            logical = model.param_specs()
            problems = dist_sharding.validate_divisibility(
                params, logical, self.mesh, rules)
            if problems:
                raise ValueError("tp sharding preflight failed:\n"
                                 + "\n".join(problems))
            self._param_specs = jax.tree.map(
                lambda s: dist_sharding.resolve_spec(s, rules), logical,
                is_leaf=lambda x: isinstance(x, P))
            self.params = params = jax.device_put(
                params, dist_sharding.resolve_tree(logical, self.mesh, rules))
            self._rep = NamedSharding(self.mesh, P())
        else:
            self.mesh = None
            self._shard_model = model
            self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

        if self.paged:
            if capacity % page_size:
                raise ValueError(
                    f"capacity ({capacity}) must be a multiple of page_size "
                    f"({page_size}): the page is the mask-IR kv block and "
                    f"the per-sequence page table has capacity/page_size "
                    f"entries")
            self.page_size = page_size
            self.pages_per_seq = capacity // page_size
            if num_pages is None:
                # HBM-equivalent default: exactly the dense engine's cells.
                num_pages = num_slots * self.pages_per_seq
            self.kv = kvc.PagedKVCache(num_pages, page_size,
                                       registry=self.tm.registry)
            self.state = model.init_paged_decode_state(
                num_slots, num_pages, page_size, self.pages_per_seq)
            self._kv_len_h = np.zeros((num_slots,), np.int64)
            self._paged_dirty = True     # device table/kv_len need upload
            if self.mesh is not None:
                self._build_tp_step_fns()
            else:
                self._scatter = jax.jit(kvc.scatter_packed_segments,
                                        donate_argnums=(0,))
                self._prefill_packed = jax.jit(model.prefill_packed)
                self._prefill_chunk = jax.jit(model.prefill_chunk_paged,
                                              donate_argnums=(2,))
            # kv-side width bucket for suffix chunks: coarse enough to
            # bound the jit-trace family over a long prompt's prefill, and
            # rounded UP to a page multiple — the in-place kv side is a
            # page LIST, so its packed width must be whole pages.
            ckb = chunk_kv_bucket or max(self.prefill_bucket,
                                         2 * (chunk_size or 0))
            self.chunk_kv_bucket = ckb + (-ckb) % page_size
            self.scheduler = ChunkScheduler(
                SchedulerConfig(num_lanes=num_slots, capacity=capacity,
                                page_size=page_size, chunk_size=chunk_size,
                                token_budget=token_budget,
                                # full chunks split into equal sp slabs;
                                # the bucket padding carries lane alignment
                                chunk_multiple=self.sp),
                kv=self.kv, telemetry=self.tm)
        else:
            if token_budget is not None:
                raise ValueError("token_budget requires chunked (paged) mode")
            self.state = model.init_decode_state(num_slots, capacity)
            if model.supports_packed_prefill():
                self._prefill_packed = jax.jit(model.prefill_packed)
            self.scheduler = ChunkScheduler(
                SchedulerConfig(num_lanes=num_slots, capacity=capacity),
                telemetry=self.tm)

            def _insert(state, slot_state, slot, kv_len_new, slot_sizes=None):
                def ins(big, small):
                    # big: (L, B, ...); small: (L, 1, ...) -> write at batch idx
                    idx = (0, slot) + (0,) * (big.ndim - 2)
                    return jax.lax.dynamic_update_slice(big, small.astype(big.dtype), idx)

                caches = jax.tree.map(ins, state["caches"], slot_state["caches"])
                kv_len = state["kv_len"].at[slot].set(kv_len_new)
                return {"caches": caches, "kv_len": kv_len}

            self._insert = jax.jit(_insert, donate_argnums=(0,),
                                   static_argnums=(2,))

            def _insert_segment(state, packed_caches, slot, offset, length,
                                kv_len_new):
                """Scatter one packed segment's K/V rows [offset, offset+length)
                into slot's cache rows [0, length). Cache leaves are
                (L, B, hkv, capacity, hd); packed leaves (L, 1, hkv, ΣL, hd).
                ``length`` is static (shape-determining, bucketed by the
                single-request path); ``offset`` and the recorded valid
                length ``kv_len_new`` are traced."""
                def ins(big, small):
                    seg = jax.lax.dynamic_slice_in_dim(small, offset, length, axis=3)
                    idx = (0, slot) + (0,) * (big.ndim - 2)
                    return jax.lax.dynamic_update_slice(big, seg.astype(big.dtype), idx)

                caches = jax.tree.map(ins, state["caches"], packed_caches)
                kv_len = state["kv_len"].at[slot].set(kv_len_new)
                return {"caches": caches, "kv_len": kv_len}

            # slot and length static (shape-determining); offset and the
            # valid length traced, so one trace per (slot, padded length)
            # pair — the single-request path buckets `length`, keeping its
            # cache O(#slots x #buckets).
            self._insert_segment = jax.jit(_insert_segment, donate_argnums=(0,),
                                           static_argnums=(2, 4))

        # Resolve the decode tile geometry ONCE at construction through the
        # tuner — the same resolution the kernels perform per call, so a bad
        # explicit (capacity, block, splits) combo fails fast here instead
        # of inside the first jitted decode step, auto fields get a
        # divisor-valid geometry by construction, and (paged mode) an
        # explicit block_k conflicting with the page size — the unit of
        # cache allocation — is rejected, never silently overridden.
        spec = attn_spec_from_config(model.cfg)
        if spec.use_decode_kernel:
            self.decode_block_k, self.num_decode_splits = \
                tuning.resolve_decode_geometry(
                    capacity, spec.block_k, spec.num_decode_splits,
                    head_dim=model.cfg.head_dim, dtype=model.cfg.dtype,
                    page_size=page_size if self.paged else None,
                    shards=self.tp)

        # IO-ledger pricing surface (telemetry/io_ledger.py): the model
        # geometry plus ONE representative tuner-resolved tile config
        # (analytic chooser only — construction must never trigger a
        # device-timing autotune) price every executed step's predicted
        # HBM bytes next to its measured wall-clock.
        rep = tuning.choose_tile_config(
            self.prefill_bucket, max(capacity, self.prefill_bucket),
            cfg.head_dim, dtype=cfg.dtype, backward=False,
            heads_q=max(1, cfg.num_heads // self.tp),
            heads_kv=max(1, cfg.num_kv_heads // self.tp), shards=self.tp)
        self.tm.ledger = IOLedger(ServePriceModel(
            d=cfg.head_dim, heads_q=cfg.num_heads,
            heads_kv=cfg.num_kv_heads, d_model=cfg.d_model,
            layers=cfg.num_layers, elt=tuning._elt_bytes(cfg.dtype),
            block_q=rep.block_q, block_k=rep.block_k, kv_major=rep.kv_major,
            tp=self.tp, sp=self.sp,
            sp_strategy=self.sp_strategy or "replicated"))

    # --------------------- back-compat views over the telemetry registry
    @property
    def prefill_calls(self) -> int:
        return int(self._c_prefill_calls.total())

    @property
    def decode_calls(self) -> int:
        return int(self._c_decode_calls.total())

    @property
    def blocks_skipped(self) -> int:
        return int(self._c_blocks_skipped.total())

    @property
    def blocks_total(self) -> int:
        return int(self._c_blocks_total.total())

    @property
    def last_prefill_layout_density(self) -> float:
        return self._g_layout_density.value(default=1.0)

    @property
    def preemptions(self) -> int:
        return int(self._c_preemptions.total())

    @property
    def peak_active(self) -> int:
        return int(self._g_peak_active.value())

    @property
    def prefix_lookups(self) -> int:
        return int(self._c_prefix_lookups.total())

    @property
    def prefix_hits(self) -> int:
        return int(self._c_prefix_hits.total())

    @property
    def prefix_pages_shared(self) -> int:
        return int(self._c_prefix_pages.total())

    @property
    def prefill_tokens_skipped(self) -> int:
        return int(self._c_tokens_skipped.total())

    @property
    def prefill_hbm_bytes_saved(self) -> int:
        return int(self._c_hbm_saved.total())

    @property
    def prefill_gather_bytes_eliminated(self) -> int:
        return int(self._c_gather_elim.total())

    @property
    def ttfts(self) -> list[float]:
        """Raw TTFT samples (seconds) — histogram-backed view."""
        return self._h_ttft.samples()

    @property
    def tok_latencies(self) -> list[float]:
        """Raw per-token decode latency samples — histogram-backed view."""
        return self._h_tok.samples()

    @property
    def last_step_stats(self) -> dict[str, Any]:
        """The most recent step's gauges, assembled from the registry
        (empty before the first step, matching the historical dict)."""
        if not self._stepped:
            return {}
        g = self._g_step
        return {
            "active": int(g["active"].value()),
            "occupancy": g["occupancy"].value(),
            "pool_utilization": (g["pool_utilization"].value()
                                 if self.paged else None),
            "prefill_tokens": int(g["prefill_tokens"].value()),
            "decode_tokens": int(g["decode_tokens"].value()),
            "deferred_chunks": int(g["deferred_chunks"].value()),
            "queued": int(g["queued"].value()),
        }

    # ----------------------------------------- tensor/sequence parallelism
    def _build_tp_step_fns(self) -> None:
        """shard_map-wrap the device step functions over the serving mesh
        (1-D ``("tp",)``, or 2-D ``("sp", "tp")`` when sp > 1).

        Per-shard layout: pool leaves (L, hkv, pages, page_size, hd) and
        packed-prefill leaves (L, 1, hkv, S, hd) shard their KV-HEAD axis;
        tokens, page tables, kv lengths, scatter indices, and logits are
        replicated (``P()``) — the host allocator's page indices are valid
        on every shard, and replicated logits make sampling a plain jit
        with no collective. ``check_vma=False``: the bodies psum at the
        projection boundaries and return replicated values the
        varying-manual-axes checker is not asked to prove.

        sp > 1 (DESIGN.md §14) changes ONLY the chunk-prefill call: its
        q-side batch rows (tokens / q_segment_ids / q_positions) shard
        ``P(None, "sp")`` — each shard gets one contiguous slab of the
        packed width — and its logits come back ``P(None, "sp", None)``;
        everything kv-side stays replicated, and the pool's specs leave
        "sp" unmentioned (= replicated), which is sound because every
        shard scatters the full gathered chunk (see
        ``attention_layer._sp_gather_kv``). Decode runs sp-replicated:
        its specs never mention "sp", so every sp row of the mesh computes
        the identical step and the census stays psum-only. The packed
        zero-offset prefill + scatter pair is a sp=1-only path — at sp > 1
        the engine routes ALL chunks (zero-offset included) through the
        chunk step, whose suffix machinery is exact at start=0."""
        mesh = self.mesh
        pool_spec = jax.tree.map(
            lambda _: P(None, "tp", None, None, None), self.state["caches"])
        state_spec = {"caches": pool_spec, "page_table": P(), "kv_len": P()}
        self._state_spec = state_spec
        sm = self._shard_model

        self._decode_sm = jax.shard_map(
            sm.decode_step, mesh=mesh,
            in_specs=(self._param_specs, state_spec, P()),
            out_specs=(state_spec, P()), check_vma=False)
        self._decode = jax.jit(self._decode_sm, donate_argnums=(1,))
        if self.sp == 1:
            packed_spec = jax.tree.map(
                lambda _: P(None, None, "tp", None, None),
                self.state["caches"])
            self._scatter_sm = jax.shard_map(
                kvc.scatter_packed_segments, mesh=mesh,
                in_specs=(pool_spec, packed_spec, P(), P()),
                out_specs=pool_spec, check_vma=False)
            self._scatter = jax.jit(self._scatter_sm, donate_argnums=(0,))
            self._prefill_packed_sm = jax.shard_map(
                sm.prefill_packed, mesh=mesh,
                in_specs=(self._param_specs,
                          {"tokens": P(), "segment_ids": P()}),
                out_specs=(packed_spec, P()), check_vma=False)
            self._prefill_packed = jax.jit(self._prefill_packed_sm)
        q_spec = P(None, "sp") if self.sp > 1 else P()
        logits_spec = P(None, "sp", None) if self.sp > 1 else P()
        chunk_batch_spec = {
            "tokens": q_spec, "q_segment_ids": q_spec, "q_positions": q_spec,
            "kv_segment_ids": P(), "kv_positions": P(),
            "dest_page": P(), "dest_off": P(), "page_list": P()}
        self._chunk_batch_spec = chunk_batch_spec
        self._prefill_chunk_sm = jax.shard_map(
            sm.prefill_chunk_paged, mesh=mesh,
            in_specs=(self._param_specs, chunk_batch_spec, pool_spec),
            out_specs=(pool_spec, logits_spec), check_vma=False)
        self._prefill_chunk = jax.jit(self._prefill_chunk_sm,
                                      donate_argnums=(2,))
        # shard the freshly built (zero) pool in place; table/len replicated
        self.state = jax.device_put(self.state, jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_spec,
            is_leaf=lambda x: isinstance(x, P)))

    def decode_collective_census(self) -> dict[str, int]:
        """Collective primitives in one sharded decode step's jaxpr —
        the "no hidden communication" assertion (DESIGN.md §13): exactly
        ``{"psum": 2}`` per traced layer (attention-output + MLP down
        projections), nothing inside attention, cache writes, or sampling
        — at sp > 1 included (decode is sp-replicated, its specs never
        mention the sp axis). Empty when unsharded."""
        if self.mesh is None:
            return {}
        tok = jnp.zeros((self.B,), jnp.int32)
        jaxpr = jax.make_jaxpr(self._decode_sm)(self.params, self.state, tok)
        return dist_sharding.collective_census(jaxpr)

    def prefill_collective_census(self, kind: str = "chunk") -> dict[str, int]:
        """Collective census of one sharded PREFILL step function's jaxpr
        (abstract trace — nothing executes). Kinds:

        * ``"chunk"`` — the paged suffix/zero chunk step
          (``prefill_chunk_paged``): the sp tentpole's contract is
          ``dist_sharding.expected_sp_prefill_census(traced_layers,
          sp=..., strategy=...)`` — the 2/layer projection psums plus the
          sp KV movement (one all_gather/layer, or (sp-1) ppermutes).
        * ``"packed"`` — the zero-offset packed prefill (sp=1 only; at
          sp > 1 zero chunks route through the chunk step): psums only.
        * ``"scatter"`` — the packed->pool page scatter (sp=1 only): a
          pure data movement, expected census ``{}``.

        Empty when unsharded or in dense mode.
        """
        if self.mesh is None or not self.paged:
            return {}
        S = self.prefill_bucket
        if kind == "chunk":
            Sk = self.chunk_kv_bucket
            batch = {
                "tokens": jnp.zeros((1, S), jnp.int32),
                "q_segment_ids": jnp.full((1, S), SEG_PAD_Q, jnp.int32),
                "q_positions": jnp.full((1, S), POS_PAD, jnp.int32),
                "kv_segment_ids": jnp.zeros((1, Sk), jnp.int32),
                "kv_positions": jnp.zeros((1, Sk), jnp.int32),
                "dest_page": jnp.full((S,), self.kv.num_pages, jnp.int32),
                "dest_off": jnp.zeros((S,), jnp.int32),
                "page_list": jnp.zeros((1, Sk // self.page_size), jnp.int32),
            }
            jaxpr = jax.make_jaxpr(self._prefill_chunk_sm)(
                self.params, batch, self.state["caches"])
        elif kind == "packed":
            if self.sp > 1:
                raise ValueError(
                    "sp>1 routes zero-offset chunks through the chunk "
                    "step; census kind='chunk' instead")
            batch = {"tokens": jnp.zeros((1, S), jnp.int32),
                     "segment_ids": jnp.full((1, S), SEG_PAD_Q, jnp.int32)}
            jaxpr = jax.make_jaxpr(self._prefill_packed_sm)(
                self.params, batch)
        elif kind == "scatter":
            if self.sp > 1:
                raise ValueError(
                    "the packed->pool scatter is an sp=1-only path")
            packed = jax.tree.map(
                lambda c: jnp.zeros((c.shape[0], 1, c.shape[1], S,
                                     c.shape[4]), c.dtype),
                self.state["caches"])
            dest_page = jnp.full((S,), self.kv.num_pages, jnp.int32)
            dest_off = jnp.zeros((S,), jnp.int32)
            jaxpr = jax.make_jaxpr(self._scatter_sm)(
                self.state["caches"], packed, dest_page, dest_off)
        else:
            raise ValueError(f"unknown prefill census kind {kind!r}")
        return dist_sharding.collective_census(jaxpr)

    # ----------------------------------------------------------------- admit
    def submit(self, prompt: list[int], max_new_tokens: int, *,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int | None = None) -> int:
        rid = next(self._rid)
        if len(prompt) + 1 > self.capacity:
            # both modes: a longer prompt would fail asynchronously during
            # run() (paged: no table room for the first decode write;
            # dense: the prefill insert cannot fit the slot) with an error
            # that no longer names the offending request.
            raise ValueError(
                f"prompt of {len(prompt)} tokens cannot decode within "
                f"capacity {self.capacity}")
        if self.paged:
            # the final generated token is emitted but never written back
            # (the request finishes first), so the worst-case footprint is
            # prompt + max_new - 1 cache rows.
            worst = self.kv.pages_for(
                min(len(prompt) + max_new_tokens - 1, self.capacity))
            if worst > self.kv.num_pages:
                raise ValueError(
                    f"request needs up to {worst} pages but the pool has "
                    f"{self.kv.num_pages}; enlarge num_pages or shorten "
                    f"the request")
        sp = sampling.SamplingParams(
            temperature=temperature, top_p=top_p,
            seed=rid if seed is None else seed)
        req = Request(rid, list(prompt), max_new_tokens, params=sp,
                      t_submit=time.perf_counter())
        self.requests[rid] = req
        self._stage_prefix(req)
        self.scheduler.submit(rid, len(prompt))
        tr = self.tm.tracer
        if tr.enabled:
            tr.event("req", "submit", rid=rid, prompt_len=len(prompt),
                     max_new=max_new_tokens)
        return rid

    def _stage_prefix(self, req: Request) -> None:
        """Hand the allocator the rolling content hash of the request's
        resume tokens (full pages only), keyed by model identity. The
        scheduler peeks/acquires these at admission; the executor publishes
        them as the pages' rows materialize. Staging the full-page set is
        safe — the scheduler clamps ACQUISITION below the last prompt
        token, so the page a request writes is always private, while a
        page-aligned prompt's final full page still becomes publishable
        once this request finishes writing it."""
        if not self.prefix_cache:
            return
        self.kv.stage_prefix(req.rid, kvc.prefix_page_keys(
            self._model_key, req.resume_tokens, self.page_size))

    @property
    def queue(self):
        """Pending (not yet admitted) requests, in service order."""
        return [self.requests[rid] for rid, _ in self.scheduler.queue]

    def _bucketed(self, length: int) -> int:
        """Pad a prefill length to the bucket multiple (capped at capacity)
        so jit caches stay O(#buckets), not O(#distinct lengths)."""
        bucket = max(1, min(self.prefill_bucket, self.capacity))
        return min(length + (-length) % bucket, self.capacity)

    def _packed_batch(self, reqs: list[Request], lengths: list[int]):
        """Tokens + segment ids for a packed prefill of each request's
        FIRST ``lengths[i]`` resume tokens, padded to the prefill bucket.
        (Atomic mode passes the full resume length; a chunked first chunk
        passes ``chunk_size``.)"""
        offsets = np.concatenate([[0], np.cumsum(lengths)])
        total = int(offsets[-1])
        padded = total + (-total) % self.prefill_bucket
        toks = np.zeros((1, padded), np.int32)
        segs = np.full((1, padded), SEG_PAD_Q, np.int32)
        for i, (r, n) in enumerate(zip(reqs, lengths)):
            toks[0, offsets[i]:offsets[i + 1]] = r.resume_tokens[:n]
            segs[0, offsets[i]:offsets[i + 1]] = i
        return toks, segs, offsets

    # ----------------------------------------------------------- sampling
    def _sample_rows(self, logits_rows,
                     reqs: list[Request | None]) -> np.ndarray:
        """Sample one token per row with each request's persisted sampling
        state; counts index the position so preempt->resume replays
        identically. ONE code path for prefill-emitted and decoded tokens
        (``None`` rows — idle decode lanes — sample greedy and are
        discarded by the caller)."""
        seeds = np.asarray([r.params.seed if r else 0 for r in reqs],
                           np.uint32)
        counts = np.asarray([len(r.output) if r else 0 for r in reqs],
                            np.uint32)
        temps = np.asarray([r.params.temperature if r else 0.0 for r in reqs],
                           np.float32)
        tops = np.asarray([r.params.top_p if r else 1.0 for r in reqs],
                          np.float32)
        return np.asarray(self._sample(logits_rows, jnp.asarray(seeds),
                                       jnp.asarray(counts),
                                       jnp.asarray(temps),
                                       jnp.asarray(tops)), np.int32)

    # ------------------------------------------------------------- bookkeeping
    def _publish_prefix(self, req: Request, n_rows: int) -> None:
        """Index req's fully-materialized pages (first ``n_rows`` KV rows
        are written) under their staged content keys. Called at every
        chunk boundary — not only at finish — so a request preempted
        mid-stream has already published its prompt pages and its own
        resume (or a sibling's admission) can hit them."""
        if self.prefix_cache:
            self.kv.publish_prefix(req.rid, n_rows // self.page_size)

    def _finish(self, lane: int, req: Request,
                reason: str = "stop") -> None:
        req.done = True
        self.finished.append(req)
        tr = self.tm.tracer
        if tr.enabled:
            tr.event("req", "finish", rid=req.rid, reason=reason,
                     tokens=len(req.output))
        if self.paged:
            # publish before release: zero-ref indexed pages are RETAINED
            # (LRU) instead of freed — the pool doubles as the cache.
            self._publish_prefix(req, int(self._kv_len_h[lane]))
        self.scheduler.finish(req.rid)      # frees lane + pages
        self.slot_req[lane] = None
        if self.paged:
            self._kv_len_h[lane] = 0
            self._paged_dirty = True

    def _post_prefill(self, lane: int, req: Request, tok: int) -> None:
        """The final chunk's logits produced the first generated token."""
        if req.t_first is None:
            req.t_first = time.perf_counter()
            self._h_ttft.observe(req.t_first - req.t_submit)
            tr = self.tm.tracer
            if tr.enabled:
                tr.event("req", "first_token", rid=req.rid,
                         ttft_s=req.t_first - req.t_submit)
        req.output.append(tok)
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if hit_eos or len(req.output) >= req.max_new_tokens:
            self._finish(lane, req,
                         "eos" if hit_eos else "max_new_tokens")
            return
        self.next_token[lane] = tok

    def _clear_lane(self, rid: int, lane: int) -> None:
        """Clear an evicted sequence's lane — only if the lane still holds
        it: a request evicted in the same plan it was admitted was never
        placed, and a prepass-freed lane may have been handed to a new
        admission already."""
        if self.slot_req[lane] is self.requests[rid]:
            self.slot_req[lane] = None
            if self.paged:
                self._kv_len_h[lane] = 0

    def _sync_evictions(self, plan) -> None:
        """Translate scheduler evictions into Request outcomes. The
        scheduler already released pages and lanes (and recorded each
        victim's lane in the plan — eviction and admission can touch the
        same lane within one plan); the engine decides requeue vs finish
        (it knows the generated prefix)."""
        tr = self.tm.tracer
        for rid, lane in plan.finished_capacity:
            req = self.requests[rid]
            self._clear_lane(rid, lane)
            req.done = True
            self.finished.append(req)
            if tr.enabled:
                tr.event("req", "finish", rid=rid, reason="capacity",
                         tokens=len(req.output))
        for rid, lane in plan.preempted:
            req = self.requests[rid]
            self._clear_lane(rid, lane)
            self._preempted_rids.add(rid)
            if tr.enabled:
                tr.event("req", "preempt", rid=rid,
                         reason=plan.preempt_reasons.get(rid, ""),
                         generated=len(req.output))
            if len(req.resume_tokens) > self.capacity:
                # already at per-sequence capacity: a resumed prefill could
                # not decode further — finish instead of requeueing an
                # over-capacity resume prompt.
                req.done = True
                self.finished.append(req)
                if tr.enabled:
                    tr.event("req", "finish", rid=rid, reason="capacity",
                             tokens=len(req.output))
                continue
            self._stage_prefix(req)     # release dropped the staged keys;
            # the resume chain's prompt pages hash identically, so a
            # resumed request re-acquires its OWN retained pages (if LRU
            # pressure spared them) and re-prefills only what was lost.
            self.scheduler.resubmit_front(rid, len(req.resume_tokens))
            self._c_preemptions.inc()
        if plan.dirty and self.paged:
            self._paged_dirty = True

    # ----------------------------------------- executor: zero-offset prefill
    def _exec_zero_paged(self, tasks: list[ChunkTask]) -> None:
        """Chunks starting at logical position 0 attend nothing before
        themselves, so they run as ONE packed self-attention prefill (the
        historical path) scattered straight into pool pages."""
        t_w = time.perf_counter()
        reqs = [self.requests[t.rid] for t in tasks]
        lengths = [t.length for t in tasks]
        toks, segs, offsets = self._packed_batch(reqs, lengths)
        caches, logits = self._prefill_packed(
            self.params, {"tokens": jnp.asarray(toks),
                          "segment_ids": jnp.asarray(segs)})
        self._c_prefill_calls.inc()
        self._record_layout_stats(segs)
        tables = [self.kv.table(t.rid) for t in tasks]
        total = toks.shape[1]
        dest_page, dest_off = kvc.packed_destinations(
            tables, offsets, lengths, self.page_size, total,
            self.kv.num_pages)
        self.state["caches"] = self._scatter(
            self.state["caches"], caches, jnp.asarray(dest_page),
            jnp.asarray(dest_off))
        self._paged_dirty = True
        for i, t in enumerate(tasks):
            self._kv_len_h[t.lane] = t.length
            self._publish_prefix(reqs[i], t.length)
        self._emit_first_tokens(tasks, logits, offsets)
        self._account_prefill("prefill_zero", tasks,
                              time.perf_counter() - t_w)

    def _emit_first_tokens(self, tasks, logits, offsets) -> None:
        """Sample the first generated token of every task whose chunk
        completes its prefill (the chunk's last-row logits)."""
        lasts = [(i, t) for i, t in enumerate(tasks) if t.last]
        if not lasts:
            return
        rows = jnp.stack([logits[0, int(offsets[i]) + tasks[i].length - 1]
                          for i, _ in lasts])
        toks = self._sample_rows(rows, [self.requests[t.rid]
                                        for _, t in lasts])
        for (_, t), tok in zip(lasts, toks):
            self._post_prefill(t.lane, self.requests[t.rid], int(tok))

    # -------------------------------------------- executor: suffix chunks
    def _kv_bucketed(self, width: int) -> int:
        """Round the packed kv gather width UP to the bucket multiple —
        never capped: several segments' prefixes can sum past one
        sequence's capacity, and an uncapped round-up is what bounds the
        jit-trace family (POS_PAD rows self-mask, so padding is free)."""
        b = max(1, self.chunk_kv_bucket)
        return width + (-width) % b

    def _exec_suffix_paged(self, tasks: list[ChunkTask]) -> None:
        """Chunks with history run as ONE packed varlen call against the
        page pool: scatter each chunk's K/V rows into its sequence's pages,
        then attend each sequence's full logical prefix IN PLACE through a
        page list (``kv_cache.paged_prefix_lists``) with traced per-segment
        positions (q_offset = chunk start). No ``gather_sources`` copy runs
        per layer — the kernel's kv BlockSpec resolves physical pages from
        the scalar-prefetched list, so zero prefix KV bytes move on the hot
        path (counted in ``prefill_gather_bytes_eliminated``).
        """
        t_w = time.perf_counter()
        reqs = [self.requests[t.rid] for t in tasks]
        lengths = [t.length for t in tasks]
        starts = [t.start for t in tasks]
        q_off = np.concatenate([[0], np.cumsum(lengths)])
        total_q = int(q_off[-1])
        Sq = total_q + (-total_q) % self.prefill_bucket
        toks = np.zeros((1, Sq), np.int32)
        qseg = np.full((1, Sq), SEG_PAD_Q, np.int32)
        qpos = np.full((1, Sq), POS_PAD, np.int32)
        for i, (r, st, n) in enumerate(zip(reqs, starts, lengths)):
            sl = slice(int(q_off[i]), int(q_off[i + 1]))
            toks[0, sl] = r.resume_tokens[st:st + n]
            qseg[0, sl] = i
            qpos[0, sl] = np.arange(st, st + n)

        spans = [st + n for st, n in zip(starts, lengths)]
        tables = [self.kv.table(t.rid) for t in tasks]
        dest_page, dest_off = kvc.chunk_destinations(
            tables, starts, q_off, lengths, self.page_size, Sq,
            self.kv.num_pages)
        # page-aligned kv packing: segment i's prefix occupies its own
        # whole page slots, so the packed width is pages * page_size,
        # bucketed (the bucket is a page multiple by construction).
        pages_needed = sum(kvc.pages_for(sp, self.page_size) for sp in spans)
        Sk = self._kv_bucketed(pages_needed * self.page_size)
        page_list, kseg, kpos = kvc.paged_prefix_lists(
            tables, spans, self.page_size, Sk // self.page_size)
        cfg = self.model.cfg
        self._c_gather_elim.inc(int(sum(
            io_model.gather_hbm_bytes(sp, cfg.head_dim, cfg.num_kv_heads,
                                      elt=tuning._elt_bytes(cfg.dtype),
                                      layers=cfg.num_layers)
            for sp in spans)))

        batch = {"tokens": jnp.asarray(toks),
                 "q_segment_ids": jnp.asarray(qseg),
                 "q_positions": jnp.asarray(qpos),
                 "kv_segment_ids": jnp.asarray(kseg[None]),
                 "kv_positions": jnp.asarray(kpos[None]),
                 "dest_page": jnp.asarray(dest_page),
                 "dest_off": jnp.asarray(dest_off),
                 "page_list": jnp.asarray(page_list[None])}
        caches, logits = self._prefill_chunk(self.params, batch,
                                             self.state["caches"])
        self.state["caches"] = caches
        self._c_prefill_calls.inc()
        self._paged_dirty = True
        for t, r in zip(tasks, reqs):
            self._kv_len_h[t.lane] = t.start + t.length
            self._publish_prefix(r, t.start + t.length)
        self._emit_first_tokens(tasks, logits, q_off)
        self._account_prefill("prefill_chunk", tasks,
                              time.perf_counter() - t_w)

    # --------------------------------------------- executor: dense prefill
    def _exec_dense(self, tasks: list[ChunkTask]) -> None:
        """Dense mode is atomic-only: every task covers its whole prompt."""
        t_w = time.perf_counter()
        reqs = [self.requests[t.rid] for t in tasks]
        if (self.packed_prefill and len(tasks) > 1):
            self._admit_packed([t.lane for t in tasks], tasks, reqs)
        else:
            for t, req in zip(tasks, reqs):
                self._admit_one(t.lane, t, req)
        self._account_prefill("prefill_dense", tasks,
                              time.perf_counter() - t_w)

    def _admit_one(self, slot: int, task: ChunkTask, req: Request) -> None:
        """Sequential dense path: one batch-1 prefill call + state insert.
        For packed-capable families the prompt is padded to the prefill
        bucket (one trace per bucket); families with recurrent state (SSM/
        hybrid/enc-dec) prefill unpadded — padding would run the recurrence
        past the real tokens."""
        toks = req.resume_tokens
        L = len(toks)
        if self.model.supports_packed_prefill():
            padded = self._bucketed(L)
            arr = np.zeros((1, padded), np.int32)
            arr[0, :L] = toks
            segs = np.full((1, padded), SEG_PAD_Q, np.int32)
            segs[0, :L] = 0
            caches, logits = self._prefill_packed(
                self.params, {"tokens": jnp.asarray(arr),
                              "segment_ids": jnp.asarray(segs)})
            self._c_prefill_calls.inc()
            self.state = self._insert_segment(self.state, caches, slot,
                                              0, padded, L)
            tok = self._sample_rows(logits[0, L - 1][None], [req])[0]
            self._post_prefill(slot, req, int(tok))
            return
        slot_state, logits = self.model.prefill(
            self.params, {"tokens": jnp.asarray([toks], jnp.int32)},
            self.capacity)
        self._c_prefill_calls.inc()
        self.state = self._insert(self.state, slot_state, slot, L)
        tok = self._sample_rows(logits[0, -1][None], [req])[0]
        self._post_prefill(slot, req, int(tok))

    def _admit_packed(self, slots: list[int], tasks: list[ChunkTask],
                      reqs: list[Request]) -> None:
        """Packed dense path: ONE (1, ΣLᵢ) prefill for all drained requests."""
        lengths = [len(r.resume_tokens) for r in reqs]
        toks, segs, offsets = self._packed_batch(reqs, lengths)
        caches, logits = self._prefill_packed(
            self.params, {"tokens": jnp.asarray(toks),
                          "segment_ids": jnp.asarray(segs)})
        self._c_prefill_calls.inc()
        self._record_layout_stats(segs)
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            self.state = self._insert_segment(
                self.state, caches, slot, int(offsets[i]), lengths[i],
                lengths[i])
        self._emit_first_tokens(tasks, logits, offsets)

    def _record_layout_stats(self, segs: np.ndarray) -> None:
        """Compile the packed call's causal+segment layout and count the
        blocks it proves skippable (cross-document and padded-tail tiles the
        dense geometry alone would run). The report tile comes from the
        same tuner the model's packed-prefill call resolves through
        (kernels/ops.py) — analytic path only: a counter must never
        trigger a device-timing autotune run."""
        s = segs.shape[1]
        spec = attn_spec_from_config(self.model.cfg)
        report_block = (spec.block_q if spec.block_q is not None
                        else tuning.choose_tile_config(
                            s, s, self.model.cfg.head_dim,
                            dtype=self.model.cfg.dtype,
                            shards=self.tp).block_q)
        bq = min(report_block, self.prefill_bucket, s)
        if s % bq:
            return  # bucket not block-aligned; skip the report, not the call
        ids = jnp.asarray(segs)
        layout = masks.compile_block_layout(
            masks.MaskSpec(causal=True, q_segment_ids=ids,
                           kv_segment_ids=ids), s, s, bq, bq)
        # one device->host transfer, then numpy: counters must not add
        # extra sync points to the serving loop.
        arr = np.asarray(layout.layout)
        skipped = int((arr == masks.BLOCK_SKIP).sum())
        total = arr.size
        self._c_blocks_skipped.inc(skipped)
        self._c_blocks_total.inc(total)
        self._g_layout_density.set(1.0 - skipped / total)

    # ------------------------------------------------------ executor: decode
    def _exec_decode(self, decode_lanes: list[int]) -> None:
        lanes = [l for l in decode_lanes if self.slot_req[l] is not None]
        if not lanes:
            return
        # pre-step KV lengths price the split-KV reads (ledger, below)
        kv_lens = [self.scheduler.by_rid[self.slot_req[l].rid].filled
                   for l in lanes]
        if self.paged and self._paged_dirty:
            # upload the host allocator's view only when it changed
            # (admission, chunk scatter, page append, finish, preemption).
            # On event-free steps — most steps, for page_size >> 1 — the
            # device table is already current and decode_step's own
            # kv_len+1 matches the host mirror's increment below. Lanes
            # still PREFILLING get -1 rows: the decode scatter drops their
            # writes and the mask IR classifies their pages SKIP, so a
            # mid-prefill sequence is untouchable by the decode call — its
            # pages are reached only through the chunk path's explicit
            # scatter/gather indices.
            lane_set = set(lanes)
            row_rids = [
                (self.slot_req[l].rid
                 if l in lane_set and self.slot_req[l] is not None else None)
                for l in range(self.B)]
            pt = jnp.asarray(
                self.kv.table_array(row_rids, self.pages_per_seq))
            kl = jnp.asarray(self._kv_len_h, jnp.int32)
            if self.mesh is not None:
                # commit the host uploads replicated on the mesh so the
                # whole (donated) state keeps shardings matching in_specs
                pt = jax.device_put(pt, self._rep)
                kl = jax.device_put(kl, self._rep)
            self.state["page_table"] = pt
            self.state["kv_len"] = kl
            self._paged_dirty = False
        t0 = time.perf_counter()
        tok = jnp.asarray(self.next_token)
        reqs_by_lane = [self.slot_req[l] for l in range(self.B)]
        self.state, logits = self._decode(self.params, self.state, tok)
        self._c_decode_calls.inc()
        nxt = self._sample_rows(logits[:, 0], reqs_by_lane)
        # _sample_rows materialized host tokens, so the step's device work
        # is done: one wall-clock sample covers every token emitted here.
        dt = time.perf_counter() - t0
        for _ in lanes:
            self._h_tok.observe(dt)
        hbm = self.tm.ledger.price.decode_bytes(kv_lens)
        self.tm.ledger.account("decode", hbm_bytes=hbm, wall_s=dt,
                               tokens=len(lanes))
        tr = self.tm.tracer
        if tr.enabled:
            tr.span("step", "decode", tr.now() - dt, dt,
                    step=self._step_idx, lanes=list(lanes),
                    tokens=len(lanes), kv_rows=int(sum(kv_lens)),
                    hbm_bytes=hbm, census=self._declared_census("decode"),
                    tiles=self._tile_args())
        for lane in lanes:
            req = self.slot_req[lane]
            t = int(nxt[lane])
            req.output.append(t)
            self.next_token[lane] = t
            self.scheduler.token_appended(req.rid)
            if self.paged:
                self._kv_len_h[lane] += 1
            hit_eos = self.eos_id is not None and t == self.eos_id
            if len(req.output) >= req.max_new_tokens or hit_eos:
                self._finish(lane, req,
                             "eos" if hit_eos else "max_new_tokens")

    # ------------------------------------- telemetry accounting helpers
    def _account_prefill(self, name: str, tasks: list[ChunkTask],
                         dt: float) -> None:
        """IO-ledger + trace bookkeeping for one executed prefill call."""
        spans = [(t.start, t.length) for t in tasks]
        tokens = sum(t.length for t in tasks)
        hbm = self.tm.ledger.price.prefill_bytes(spans)
        self.tm.ledger.account(name, hbm_bytes=hbm, wall_s=dt,
                               tokens=tokens)
        tr = self.tm.tracer
        if tr.enabled:
            tr.span("step", name, tr.now() - dt, dt, step=self._step_idx,
                    lanes=[t.lane for t in tasks],
                    chunks=[[t.start, t.length] for t in tasks],
                    tokens=tokens, hbm_bytes=hbm,
                    census=self._declared_census(name),
                    tiles=self._tile_args())
            for t in tasks:
                tr.event("req", "chunk", rid=t.rid, lane=t.lane,
                         start=t.start, length=t.length, last=t.last)

    def _declared_census(self, kind: str) -> dict[str, int]:
        """DECLARED per-step collective census for span args — the cheap
        contract from DESIGN.md §13/§14. The jaxpr-counted census methods
        (``decode_collective_census`` / ``prefill_collective_census``)
        PROVE this declaration at construction/test time; re-tracing per
        step would dwarf the step itself."""
        if self.mesh is None:
            return {}
        cfg = self.model.cfg
        layers = 1 if cfg.scan_layers else cfg.num_layers
        if kind == "prefill_chunk" and self.sp > 1:
            return dist_sharding.expected_sp_prefill_census(
                layers, sp=self.sp, strategy=self.sp_strategy)
        return {"psum": 2 * layers}

    def _tile_args(self) -> dict[str, Any]:
        """Tuner-resolved tile geometry for span args."""
        p = self.tm.ledger.price
        out: dict[str, Any] = {"block_q": p.block_q, "block_k": p.block_k,
                               "kv_major": p.kv_major}
        if hasattr(self, "decode_block_k"):
            out["decode_block_k"] = self.decode_block_k
            out["num_decode_splits"] = self.num_decode_splits
        return out

    # ------------------------------------------------------------------ step
    def step(self) -> None:
        t_step = time.perf_counter()
        self._step_idx += 1
        plan = self.scheduler.plan_step()
        # evictions FIRST (they clear lanes the admissions below may
        # reuse — a prepass eviction frees a lane before admission runs),
        # and a request both admitted and starve-evicted within this plan
        # is requeued by _sync_evictions and must never be placed.
        self._sync_evictions(plan)
        evicted = ({rid for rid, _ in plan.preempted}
                   | {rid for rid, _ in plan.finished_capacity})
        tr = self.tm.tracer
        for rid, lane in plan.admitted:
            if rid not in evicted:
                self.slot_req[lane] = self.requests[rid]
                self._record_prefix_hit(rid)
                if tr.enabled:
                    # a re-admission after preemption is the RESUME leg of
                    # the lifecycle; the validator pairs it with the
                    # preempt marker.
                    tr.event("req",
                             "resume" if rid in self._preempted_rids
                             else "admit",
                             rid=rid, lane=lane,
                             cached=self.scheduler.by_rid[rid].cached)

        zero = [t for t in plan.prefill if t.start == 0]
        suffix = [t for t in plan.prefill if t.start > 0]
        if self.paged and self.sp > 1:
            # one step function at sp>1: the chunk path is exact at
            # start=0 and carries the P(None,"sp") q-row sharding; the
            # packed+scatter pair was never built on the 2-D mesh.
            if plan.prefill:
                self._exec_suffix_paged(list(plan.prefill))
        elif self.paged:
            if zero:
                if self.packed_prefill and len(zero) > 1:
                    self._exec_zero_paged(zero)
                else:
                    for t in zero:
                        self._exec_zero_paged([t])
            if suffix:
                self._exec_suffix_paged(suffix)
        elif zero:
            self._exec_dense(zero)

        active = sum(r is not None for r in self.slot_req)
        self._g_peak_active.max_update(active)
        g = self._g_step
        g["active"].set(active)
        g["occupancy"].set(active / self.B)
        if self.paged:
            g["pool_utilization"].set(self.kv.utilization())
        g["prefill_tokens"].set(sum(t.length for t in plan.prefill))
        g["decode_tokens"].set(len(plan.decode_lanes))
        g["deferred_chunks"].set(plan.deferred_chunks)
        g["queued"].set(len(self.scheduler.queue))
        self._stepped = True
        self._exec_decode(plan.decode_lanes)
        # post-decode queue depth (finish/reclaim just happened)
        g["queued"].set(len(self.scheduler.queue))
        if tr.enabled:
            dt = time.perf_counter() - t_step
            stats = self.last_step_stats
            tr.span("stepsum", "step", tr.now() - dt, dt,
                    step=self._step_idx, **stats)

    def run(self, max_steps: int = 10_000, on_step=None) -> list[Request]:
        """Drive the engine to drain. ``on_step(engine)`` is called after
        every step — the one place per-step observability hangs off
        (``last_step_stats``, pool utilization), instead of each caller
        hand-rolling the drain loop."""
        for _ in range(max_steps):
            if self.scheduler.idle():
                break
            self.step()
            if on_step is not None:
                on_step(self)
        return self.finished

    # --------------------------------------------------------- observability
    def _record_prefix_hit(self, rid: int) -> None:
        """Account one admission's prefix-cache outcome: rows the scheduler
        mapped from shared pages are prefill that never runs, credited in
        HBM bytes through the same Theorem-2 surface the tuner optimizes
        (``io_model.prefix_cache_hbm_bytes_saved``)."""
        if not self.prefix_cache:
            return
        self._c_prefix_lookups.inc()
        cached = self.scheduler.by_rid[rid].cached
        if not cached:
            return
        self._c_prefix_hits.inc()
        self._c_prefix_pages.inc(cached // self.page_size)
        self._c_tokens_skipped.inc(cached)
        cfg = self.model.cfg
        saved = int(io_model.prefix_cache_hbm_bytes_saved(
            cached, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            elt=tuning._elt_bytes(cfg.dtype), layers=cfg.num_layers))
        self._c_hbm_saved.inc(saved)
        # prefix hits are bytes NOT spent: the ledger carries them as a
        # separate credit kind, never summed into total_bytes().
        self.tm.ledger.account("prefix_saved", hbm_bytes=saved,
                               tokens=cached)
        tr = self.tm.tracer
        if tr.enabled:
            tr.event("req", "prefix_hit", rid=rid, cached_tokens=cached,
                     pages=cached // self.page_size, hbm_bytes_saved=saved)

    @property
    def prefix_cache_hit_rate(self) -> float:
        """Fraction of admissions (lookups) that mapped >= 1 shared page."""
        return self.prefix_hits / max(1, self.prefix_lookups)

    @staticmethod
    def step_stats_printer():
        """``run(on_step=...)`` callback printing per-step batch occupancy
        and page-pool utilization (shared by launch/serve.py and the
        serving examples — one format, one place)."""
        counter = itertools.count(1)

        def show(e):
            s = e.last_step_stats
            util = (f" pool {s['pool_utilization']:.0%}"
                    if s["pool_utilization"] is not None else "")
            work = ""
            if s.get("prefill_tokens"):
                work = (f" prefill {s['prefill_tokens']}t"
                        f"+decode {s['decode_tokens']}t")
            print(f"  step {next(counter):>3}: batch {s['active']}/{e.B} "
                  f"({s['occupancy']:.0%}){util}{work} queued {s['queued']}")

        return show

    def cache_bytes(self) -> int:
        """HBM bytes resident in the decode KV state (pool or slot cache),
        summed over all shards (jax reports global nbytes)."""
        return int(sum(leaf.nbytes
                       for leaf in jax.tree.leaves(self.state["caches"])))

    def per_shard_cache_bytes(self) -> int:
        """Per-DEVICE resident KV bytes: the head-sharded pool puts 1/tp of
        every page on each shard, so at equal total concurrency the
        per-device footprint shrinks by the shard count."""
        return self.cache_bytes() // max(1, self.tp)

    def latency_stats(self) -> dict[str, float]:
        """Percentile-reduced per-request latencies (seconds): TTFT (submit
        -> first generated token, chunked prefill and queueing included)
        and per-token decode step latency. Zeros when no samples exist.
        The percentile math lives in ONE place — the telemetry histogram
        (``telemetry.metrics.percentile``)."""
        out: dict[str, float] = {}
        for name, h in (("ttft", self._h_ttft),
                        ("tok_latency", self._h_tok)):
            for q in (50, 95):
                out[f"{name}_p{q}"] = h.percentile(q)
        return out
