"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines, then a summary that checks
the paper's headline claims:
  * 10–20× speedup vs a comparable CPU (modeled cycles, Fig. 5)
  * 2–5× better power efficiency vs a GPU (modeled, Fig. 6)
and the directly MEASURED async-vs-sync work reduction the claims rest on.

A machine-readable snapshot (per-algorithm sweeps, edge_work, crit_tiles,
modeled speedups) is written to ``BENCH_graph.json`` by default so later
PRs have a perf trajectory to diff against; ``--json ''`` disables it.

  PYTHONPATH=src python -m benchmarks.run [--scale 1/256] [--json out]
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from . import algo_suite, async_vs_sync, common, dist_async, \
    dist_batched, fig5_cycles, fig6_power, kernel_bench, lm_bench, \
    serve_latency


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=common.SCALE,
                    help="fraction of full paper graph size (default "
                         "1/256; 1.0 = paper scale)")
    ap.add_argument("--json", default="BENCH_graph.json",
                    help="output path for the machine-readable snapshot "
                         "('' disables)")
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["fig5", "fig6", "avs", "dist", "dist_async",
                             "kernel", "kernel_fused", "lm", "serve",
                             "algo_suite"])
    args = ap.parse_args()
    enable_compile_cache()

    graphs = common.load_graphs(args.scale)
    out = {"meta": {"scale": args.scale,
                    "graphs": {name: dict(n=g.n, nnz=g.nnz,
                                          avg_degree=g.avg_degree)
                               for name, g in graphs.items()}}}
    for name, g in graphs.items():
        common.csv_line(f"graph/{name}", 0.0,
                        f"n={g.n} nnz={g.nnz} avg_deg={g.avg_degree:.2f}")
    if "fig5" not in args.skip:
        out["fig5"] = fig5_cycles.run(graphs)
    if "fig6" not in args.skip:
        out["fig6"] = fig6_power.run(graphs)
    if "algo_suite" not in args.skip:
        out["algo_suite"] = algo_suite.run(graphs)
    if "avs" not in args.skip:
        out["async_vs_sync"] = async_vs_sync.run(graphs)
    if "dist" not in args.skip:
        out["distributed_batched"] = dist_batched.run(graphs)
    if "dist_async" not in args.skip:
        out["dist_async"] = dist_async.run(graphs)
    if "serve" not in args.skip:
        out["serve_latency"] = serve_latency.run(graphs)
    if "kernel" not in args.skip:
        out["kernel"] = kernel_bench.run(graphs)
    if "kernel_fused" not in args.skip:
        out["kernel_fused"] = kernel_bench.run_fused(args.scale)
    if "lm" not in args.skip:
        out["lm"] = lm_bench.run(graphs)

    # --- paper-claim summary -------------------------------------------
    if "fig5" in out:
        par = [r for r in out["fig5"] if r["algo"] not in ("dfs",)]
        sp = np.array([r["speedup_cpu"] for r in par])
        gp = [r["perf_per_watt_vs_gpu"] for r in out.get("fig6", [])
              if r["algo"] not in ("dfs",)]
        print("\n== paper-claim check (modeled; constants in "
              "core/power.py) ==")
        print(f"speedup vs CPU  : geomean {np.exp(np.log(sp).mean()):.1f}x"
              f"  range [{sp.min():.1f}, {sp.max():.1f}]  "
              f"(paper: 10-20x)")
        if gp:
            gp = np.array(gp)
            print(f"perf/W vs GPU   : geomean "
                  f"{np.exp(np.log(gp).mean()):.1f}x  "
                  f"range [{gp.min():.1f}, {gp.max():.1f}]  (paper: 2-5x)")
    if "algo_suite" in out:
        asp = np.array([r["speedup_cpu"] for r in out["algo_suite"]])
        print(f"algorithm catalog (pagerank_delta/cc/kcore/tricount, "
              f"modeled): geomean {np.exp(np.log(asp).mean()):.1f}x vs "
              f"CPU  range [{asp.min():.1f}, {asp.max():.1f}]")
    if "async_vs_sync" in out:
        wr = [r["work_reduction"] for r in out["async_vs_sync"]
              if "work_reduction" in r]
        print(f"async work reduction (measured): geomean "
              f"{np.exp(np.log(wr).mean()):.2f}x over bulk-synchronous")
    if "distributed_batched" in out:
        ds = np.array([r["speedup_vs_sequential"]
                       for r in out["distributed_batched"]])
        print(f"batched distributed dispatch (modeled, "
              f"{dist_batched.REF_DEVICES}-device node): geomean "
              f"{np.exp(np.log(ds).mean()):.2f}x vs per-source loop")
    if "dist_async" in out:
        da = out["dist_async"]
        sp = np.array([r["speedup_vs_sync"] for r in da])
        hr = np.array([r["halo_exchange_reduction"] for r in da])
        print(f"self-timed distributed engine (modeled): geomean "
              f"{np.exp(np.log(sp).mean()):.2f}x vs bulk-synchronous, "
              f"halo exchanges cut {np.exp(np.log(hr).mean()):.2f}x")
    if "kernel_fused" in out:
        kf = out["kernel_fused"]
        sp = np.array([r["speedup_modeled"] for r in kf])
        sk = np.array([r["tiles_skipped"] for r in kf])
        road = [r for r in kf if r["graph"] == "road" and r["algo"] == "bfs"]
        print(f"fused frontier-masked kernel (modeled): geomean "
              f"{np.exp(np.log(sp).mean()):.2f}x vs unfused sync loop, "
              f"tiles skipped {sk.min():.0%}..{sk.max():.0%}"
              + (f" (sparse-frontier BFS: {road[0]['speedup_modeled']:.2f}x,"
                 f" {road[0]['tiles_skipped']:.0%} skipped)" if road else ""))
    if "serve_latency" in out:
        sl = out["serve_latency"]
        sp = np.array([r["speedup_vs_unbatched"] for r in sl])
        aw = np.mean([r["achieved_wave"] for r in sl])
        p99 = max(r["p99_ms"] for r in sl)
        print(f"continuous-batching front door: geomean modeled "
              f"{np.exp(np.log(sp).mean()):.2f}x vs unbatched dispatch "
              f"(achieved wave {aw:.1f}, worst p99 {p99:.1f} ms)")

    # --- serving-layer accounting --------------------------------------
    store = common.service().store.stats()
    out["plan_store"] = store
    print(f"plan store: {store['plans']} plans "
          f"({store['bytes'] / 1e6:.2f} MB), hit rate "
          f"{store['hit_rate']:.1%} = {store['mem_hit_rate']:.1%} mem "
          f"+ {store['disk_hit_rate']:.1%} disk "
          f"({store['mem_hits']} mem hits, {store['disk_hits']} disk "
          f"hits, {store['misses']} builds)")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=float)
        print(f"\nwrote {args.json}")


if __name__ == '__main__':
    main()
