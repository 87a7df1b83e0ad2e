"""Serving launcher: continuous-batching scheduler over the paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --slots 8 --requests 12 --page-size 16 --pages 24
    # chunked prefill: a long prompt no longer head-of-line blocks decode
    PYTHONPATH=src python -m repro.launch.serve --capacity 512 \
        --long-prompt 300 --chunk-size 64 --token-budget 80

By default the engine serves the family-faithful reduced config (CPU
tests and demos); ``--full`` serves the published config of ``--arch``
(e.g. granite-3-2b at 40 layers, d_model 2048, 32q/8kv heads) on the
accelerator, single-device or over the ``--tp``/``--sp`` mesh:

    python -m repro.launch.serve --full --slots 8 --capacity 2048 \
        --chunk-size 512 --requests 8 --max-new 32
``--dense`` selects the fixed-slot baseline cache; by default the engine
pages (families with recurrent state fall back to dense automatically).
``--chunk-size`` splits prompt prefills into fixed-size chunks the
scheduler interleaves with decode under ``--token-budget`` total tokens
per step (DESIGN.md §10); ``--temperature``/``--top-p`` switch decode from
greedy to sampling (per-request keys, preemption-safe).
``--shared-prefix N`` prepends the same N tokens to every prompt (the
system-prompt workload): with the prefix cache on (default in paged mode;
``--no-prefix-cache`` disables) later requests map those pages read-only
and skip their prefill — the summary prints hit-rate, pages shared, and
the HBM bytes saved (DESIGN.md §12). ``--tp``/``--sp`` shard the engine
over a 2-D (sp, tp) device mesh: tp slices heads, sp slices each prefill
chunk's query rows with all-gathered or ring-rotated KV (DESIGN.md
§13–14); the summary prints the strategy, io_model cost surface, and the
collective censuses. Each step prints
batch occupancy, page-pool utilization, and the step's prefill/decode
token split so scheduler behaviour (admission waves, chunk interleaving,
preemption, reclamation) is visible live."""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.kernels import tuning
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config of --arch instead of "
                         "the reduced CPU-sized one")
    ap.add_argument("--autotune", action="store_true",
                    help="empirically time tile candidates on this device "
                         "(persisted in the autotune cache)")
    ap.add_argument("--sram-budget", type=int, default=None,
                    help="tuner SRAM budget in bytes (default: "
                         "io_model.DEFAULT_SRAM_BUDGET)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch lanes (dense: also the cache slots)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--capacity", type=int, default=128,
                    help="per-sequence max cache length")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--dense", action="store_true",
                    help="fixed-slot dense KV cache baseline")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV rows per page (== mask-IR kv block)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page pool size (default: slots*capacity/page_size,"
                         " the dense engine's HBM budget)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="prefill chunk length (paged mode): long prompts "
                         "prefill this many tokens per step, interleaved "
                         "with decode instead of head-of-line blocking it "
                         "(default: atomic prefill)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max tokens one step may process (decode lanes + "
                         "prefill chunks; default: slots + chunk-size)")
    ap.add_argument("--long-prompt", type=int, default=None,
                    help="also submit one prompt of this many tokens (shows "
                         "chunked-prefill interleaving live)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="decode temperature (0 = greedy); per-request PRNG "
                         "keys persist across preemption")
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="share content-identical full prompt pages across "
                         "requests copy-on-write (default: on in paged mode)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix-cache page sharing")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical tokens to every "
                         "prompt (system-prompt workload: later requests "
                         "hit the prefix cache and skip that prefill)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards over a (tp,) device mesh "
                         "(paged mode): page pool and projections shard by "
                         "heads, scheduler stays host-global; needs tp "
                         "visible devices (CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N); "
                         "composes with --prefix-cache and --autotune")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel shards over the leading axis of "
                         "a 2-D (sp, tp) mesh (paged mode): each shard owns "
                         "a contiguous slab of every prefill chunk's query "
                         "rows; the causal-prefix KV moves by all-gather or "
                         "ring ppermute, chosen per shape via io_model "
                         "(override with --sp-strategy); needs sp*tp "
                         "visible devices")
    ap.add_argument("--sp-strategy", default=None,
                    choices=("allgather", "ring"),
                    help="force the sp KV movement strategy instead of the "
                         "io_model cost pick")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record the serve and write a Chrome trace-event "
                         "JSON here (load in Perfetto / chrome://tracing; "
                         "validate with python -m repro.telemetry.validate)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics-registry table and the IO "
                         "ledger (predicted HBM bytes per step kind) at "
                         "exit")
    ap.add_argument("--smoke", action="store_true",
                    help="preset pressure workload (tight page pool + "
                         "chunked prefill + shared prefix) that forces at "
                         "least one preemption→resume and prefix hits — "
                         "the CI trace-validation scenario")
    args = ap.parse_args()

    if args.smoke:
        # Tight pool + two long chunked prompts: decode outgrows the pages,
        # the scheduler preempts a lane and resumes it after reclamation;
        # the shared prefix gives the prefix cache hits to annotate.
        args.slots, args.capacity, args.dense = 2, 32, False
        args.page_size, args.pages = 8, 4
        args.chunk_size, args.token_budget = 8, 18
        args.requests, args.max_new = 2, 5
        args.long_prompt, args.shared_prefix = 16, 8

    enable_compile_cache()
    tuning.configure_tuning(sram_budget=args.sram_budget,
                            autotune=args.autotune or None)
    cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
    if not args.full and args.tp > 1 and cfg.num_kv_heads % args.tp:
        # the reduced demo config may carry fewer kv heads than shards
        # (granite reduces to 4q/1kv); scale BOTH head counts, keeping the
        # GQA ratio, so every shard owns whole kv-head groups — the real
        # config on a real slice divides and never takes this branch.
        import dataclasses
        ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
        kv = -(-cfg.num_kv_heads // args.tp) * args.tp
        cfg = dataclasses.replace(cfg, num_kv_heads=kv,
                                  num_heads=kv * ratio)
        print(f"[tp={args.tp}] scaled reduced config to {kv * ratio}q/"
              f"{kv}kv heads so every shard owns whole kv-head groups")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=args.slots,
                        capacity=args.capacity,
                        paged=False if args.dense else None,
                        page_size=args.page_size, num_pages=args.pages,
                        chunk_size=args.chunk_size,
                        token_budget=args.token_budget,
                        prefix_cache=args.prefix_cache,
                        tp=args.tp, sp=args.sp,
                        sp_strategy=args.sp_strategy,
                        trace=bool(args.trace))
    rng = np.random.default_rng(0)
    shared = list(rng.integers(1, cfg.vocab_size, size=args.shared_prefix))
    t0 = time.perf_counter()
    if args.long_prompt:
        eng.submit(shared + list(rng.integers(1, cfg.vocab_size,
                                              size=args.long_prompt)),
                   max_new_tokens=4, temperature=args.temperature,
                   top_p=args.top_p)
    for _ in range(args.requests):
        plen = int(rng.integers(3, 16))
        eng.submit(shared + list(rng.integers(1, cfg.vocab_size, size=plen)),
                   max_new_tokens=int(rng.integers(4, args.max_new)),
                   temperature=args.temperature, top_p=args.top_p)

    mode = "paged" if eng.paged else "dense"
    chunked = (f" chunk={args.chunk_size}" if args.chunk_size else "")
    tp_note = (f" tp={args.tp} ({eng.per_shard_cache_bytes()/1e6:.2f} MB"
               f"/shard)" if args.tp > 1 else "")
    if args.sp > 1:
        tp_note += f" sp={args.sp}({eng.sp_strategy})"
    print(f"arch={cfg.name} mode={mode}{chunked} lanes={args.slots} "
          f"cache={eng.cache_bytes()/1e6:.2f} MB{tp_note}"
          + (f" pool={eng.kv.num_pages}x{eng.kv.page_size}" if eng.paged
             else f" slots={args.slots}x{args.capacity}"))
    done = eng.run(on_step=ServingEngine.step_stats_printer())
    dt = time.perf_counter() - t0
    tok = sum(len(r.output) for r in done)
    extra = (f", peak_concurrent={eng.peak_active}, "
             f"preemptions={eng.preemptions}" if eng.paged else "")
    print(f"{len(done)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s{extra})")
    if eng.paged and eng.prefix_cache:
        print(f"prefix cache: hit-rate {eng.prefix_cache_hit_rate:.0%} "
              f"({eng.prefix_hits}/{eng.prefix_lookups} admissions), "
              f"{eng.prefix_pages_shared} pages shared, "
              f"{eng.prefill_tokens_skipped} prefill tokens skipped, "
              f"{eng.prefill_hbm_bytes_saved/1e6:.2f} MB HBM saved, "
              f"{eng.kv.cached_pages} pages indexed "
              f"({eng.kv.cache_evictions} evicted under pressure)")
    if eng.tp > 1:
        print(f"tp={eng.tp}: per-shard pool utilization "
              f"{eng.kv.utilization():.0%} (identical on every shard — one "
              f"logical pool, head-sliced), "
              f"{eng.per_shard_cache_bytes()/1e6:.2f} MB KV/shard, "
              f"decode census {eng.decode_collective_census()}")
    if eng.sp > 1:
        c = eng.sp_prefill_costs
        print(f"sp={eng.sp}: strategy={eng.sp_strategy} "
              f"(io_model chunk bytes: replicated {c['replicated']/1e6:.2f} "
              f"MB, allgather {c['allgather']/1e6:.2f} MB, "
              f"ring {c['ring']/1e6:.2f} MB), "
              f"prefill census {eng.prefill_collective_census('chunk')}, "
              f"decode census {eng.decode_collective_census()}")
    for r in done[:5]:
        print(f"  req{r.rid}: {len(r.output)} tokens {r.output[:8]}...")
    if args.trace:
        n = eng.tm.tracer.to_chrome_trace(args.trace)
        print(f"trace: {n} events -> {args.trace} "
              f"(validate: python -m repro.telemetry.validate {args.trace})")
    if args.metrics:
        print("\n-- metrics registry --")
        print(eng.tm.registry.table())
        print("\n-- IO ledger (predicted HBM bytes per step kind) --")
        print(eng.tm.ledger.table())


if __name__ == "__main__":
    main()
