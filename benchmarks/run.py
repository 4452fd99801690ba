"""Benchmark aggregator — one registered suite per artifact.

    PYTHONPATH=src python -m benchmarks.run [--workdir DIR] [--fast]
    PYTHONPATH=src python -m benchmarks.run --suites loading

Suites register in ``SUITES`` and the default run executes all of them:
the paper-figure harnesses print one ``name,value,derived`` CSV block
per table/figure, and every suite that measures loading bandwidth emits
its ``BENCH_*.json`` (the files CI's bench lane uploads and gates with
``benchmarks/compare.py``) — one entry point, all BENCH json.  Absolute
numbers are for THIS container (CPU + tmpfs + simulated storage
profiles); the paper's relative effects are the claims under test (see
EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _suite_figs(args):
    """Table I + Figs. 2-4 (CSV blocks; no BENCH json)."""
    names = ["web-sm", "social-sm", "web-md"] if args.fast else None

    from benchmarks import (fig2_pgfuse, fig3_compbin, fig4_crossover,
                            table1_datasets)

    print("=" * 72)
    print("Table I — datasets & format sizes")
    print("=" * 72)
    t1_rows = table1_datasets.run(args.workdir, names)
    for r in t1_rows:
        print(f"table1,{r['name']},wg_MiB={r['webgraph_MiB']:.2f},"
              f"cb_MiB={r['compbin_MiB']:.2f},ratio={r['compression_ratio']:.2f}")

    print("=" * 72)
    print("Fig. 2 — PG-Fuse on/off (WebGraph loading)")
    print("=" * 72)
    f2 = fig2_pgfuse.run(args.workdir, args.profile, names)
    for r in f2:
        print(f"fig2,{r['name']},base_s={r['base_s']:.4f},"
              f"pgfuse_s={r['pgfuse_s']:.4f},speedup={r['speedup']:.2f}")
    sp = [r["speedup"] for r in f2]
    print(f"fig2,SUMMARY,speedup_min={min(sp):.2f},speedup_max={max(sp):.2f},"
          f"paper_range=0.9-7.6")

    print("=" * 72)
    print("Fig. 3 — CompBin & PG-Fuse speedups over baseline")
    print("=" * 72)
    f3 = fig3_compbin.run(args.workdir, args.profile, names)
    for r in f3:
        print(f"fig3,{r['name']},compbin_x={r['compbin_speedup']:.2f},"
              f"pgfuse_x={r['pgfuse_speedup']:.2f}")
    cb = [r["compbin_speedup"] for r in f3]
    print(f"fig3,SUMMARY,compbin_max={max(cb):.2f},paper_max=21.8")

    print("=" * 72)
    print("Fig. 4 — PG-Fuse vs CompBin crossover (shared-contended profile)")
    print("=" * 72)
    f4 = fig4_crossover.run(args.workdir, "lustre_shared", names)
    for r in f4:
        print(f"fig4,{r['name']},size_diff_MiB={r['size_diff_MiB']:.2f},"
              f"ratio={r['pgfuse_over_compbin']:.3f}")
    x = fig4_crossover.crossover_MiB(f4)
    print(f"fig4,SUMMARY,crossover_MiB={x if x else 'none'}")
    return {"table1": {r["name"]: r for r in t1_rows},
            "fig2": {r["name"]: r for r in f2},
            "fig3": {r["name"]: r for r in f3},
            "fig4": {r["name"]: r for r in f4}}


def _suite_loading(args):
    """Streaming-loader bandwidth (topology + feature store) ->
    BENCH_loading.json, the artifact CI's bench regression lane gates."""
    from benchmarks import loading

    print("=" * 72)
    print("Loading — streamed topology + features (emits BENCH json)")
    print("=" * 72)
    return loading.run(workdir=args.workdir, profile=args.profile,
                scale=13 if args.fast else 16, hosts=args.hosts,
                out=args.bench_out)


def _suite_query(args):
    """Random-access query engine vs sequential policy on a zipf trace
    (+ host-vs-device decode arms on a large-fanout trace) ->
    BENCH_query.json (virtual-clock p50/p99 latency + hit rate, gated
    downward/upward respectively by the bench lane)."""
    from benchmarks import query

    print("=" * 72)
    print("Query — random-access neighbor engine (emits BENCH json)")
    print("=" * 72)
    return query.run(workdir=args.workdir, profile=args.profile,
              scale=14 if args.fast else 17,
              out=args.query_out)


def _suite_traversal(args):
    """Frontier-batched traversal service vs per-vertex naive BFS on a
    zipf seed trace (+ a deterministic overload replay through the
    admission gate) -> BENCH_traversal.json (virtual-clock p50/p99
    gated downward, frontier-batching advantage gated upward)."""
    from benchmarks import traversal

    print("=" * 72)
    print("Traversal — multi-hop service vs per-vertex BFS (emits BENCH json)")
    print("=" * 72)
    return traversal.run(workdir=args.workdir, profile=args.profile,
                  scale=13 if args.fast else 15,
                  out=args.traversal_out)


def _suite_sharded(args):
    """1/2/4-shard scatter-gather deployments replaying the same zipf
    hub trace on per-shard simulated storage -> BENCH_sharded.json
    (2-shard aggregate-makespan advantage gated upward with a hard
    >=1.5x floor, 2-shard virtual-clock p50/p99 gated downward)."""
    from benchmarks import sharded

    print("=" * 72)
    print("Sharded — scatter-gather scale-out 1/2/4 shards (emits BENCH json)")
    print("=" * 72)
    return sharded.run(workdir=args.workdir, profile=args.profile,
                scale=13 if args.fast else 15,
                out=args.sharded_out)


def _suite_hotset(args):
    """HBM-resident hot-set tier (decoded hub runs, degree-aware
    admission) vs the packed-byte-only engine on a degree-correlated
    zipf trace -> BENCH_hotset.json (hit advantage gated upward with a
    hard >=1.5x floor, hot-arm virtual-clock p50/p99 gated downward)."""
    from benchmarks import hotset

    print("=" * 72)
    print("Hotset — HBM decoded-run tier vs packed path (emits BENCH json)")
    print("=" * 72)
    return hotset.run(workdir=args.workdir,
               scale=13 if args.fast else 16,
               out=args.hotset_out)


def _suite_reorder(args):
    """Offline graph compiler (BFS locality reorder + LogCSR re-encode,
    inverse-permutation sidecar) vs the scrambled original on the same
    logical zipf trace and capped PG-Fuse budget -> BENCH_reorder.json
    (hit-rate gain gated upward with a hard in-bench floor, compiled-arm
    virtual-clock p50/p99 gated downward)."""
    from benchmarks import reorder

    print("=" * 72)
    print("Reorder — locality compile vs scrambled order (emits BENCH json)")
    print("=" * 72)
    return reorder.run(workdir=args.workdir, profile=args.profile,
                scale=13 if args.fast else 16,
                out=args.reorder_out)


#: registered suites, executed in order by default — add new benchmark
#: harnesses here so ``python -m benchmarks.run`` stays the one entry
#: point that emits every artifact (CSV blocks and BENCH_*.json alike)
SUITES = {
    "figs": _suite_figs,
    "loading": _suite_loading,
    "query": _suite_query,
    "traversal": _suite_traversal,
    "sharded": _suite_sharded,
    "hotset": _suite_hotset,
    "reorder": _suite_reorder,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default="/tmp/repro_bench")
    ap.add_argument("--profile", default="lustre_ssd")
    ap.add_argument("--fast", action="store_true",
                    help="small suite only (CI)")
    ap.add_argument("--suites", default=",".join(SUITES),
                    help=f"comma list of suites to run "
                         f"(available: {', '.join(SUITES)})")
    ap.add_argument("--hosts", type=int, default=2,
                    help="simulated hosts for the loading suite")
    ap.add_argument("--bench-out", default="BENCH_loading.json",
                    help="where the loading suite writes its BENCH json")
    ap.add_argument("--query-out", default="BENCH_query.json",
                    help="where the query suite writes its BENCH json")
    ap.add_argument("--traversal-out", default="BENCH_traversal.json",
                    help="where the traversal suite writes its BENCH json")
    ap.add_argument("--sharded-out", default="BENCH_sharded.json",
                    help="where the sharded suite writes its BENCH json")
    ap.add_argument("--hotset-out", default="BENCH_hotset.json",
                    help="where the hotset suite writes its BENCH json")
    ap.add_argument("--reorder-out", default="BENCH_reorder.json",
                    help="where the reorder suite writes its BENCH json")
    args = ap.parse_args()

    picked = [s.strip() for s in args.suites.split(",") if s.strip()]
    unknown = [s for s in picked if s not in SUITES]
    if unknown:
        ap.error(f"unknown suites {unknown}; available: {', '.join(SUITES)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    t0 = time.time()
    for name in picked:
        result = SUITES[name](args)
        if isinstance(result, dict):
            # one flattened metrics sidecar per suite next to its BENCH
            # json — dotted numeric keys only (repro.obs.metrics
            # .flatten_numeric), uploaded by CI's bench lane so every
            # run doubles as a metrics-surface smoke artifact
            from repro.obs.metrics import flatten_numeric
            side = f"BENCH_{name}_metrics.json"
            with open(side, "w") as f:
                json.dump(flatten_numeric(result), f, indent=2,
                          sort_keys=True)
                f.write("\n")
            print(f"{name}: wrote {side} "
                  f"({len(flatten_numeric(result))} metrics)")
    print("=" * 72)
    print(f"done in {time.time()-t0:.1f}s  "
          f"(roofline table: python -m benchmarks.roofline)")


if __name__ == "__main__":
    sys.exit(main())
