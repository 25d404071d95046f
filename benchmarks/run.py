"""Benchmark harness: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV (plus a roofline summary read from the
dry-run artifacts when present).

  PYTHONPATH=src python -m benchmarks.run [--full]

The ``algorithms`` suite additionally writes a machine-readable
``BENCH_algorithms.json`` (per-algo, per-structure ``t_algo``/``t_sync``,
device-pool counters, memory; ``$BENCH_ALGORITHMS_JSON`` overrides the
path) so the perf trajectory is tracked across PRs — CI uploads it as an
artifact. ``--datasets a,b`` restricts that suite's dataset pool (the CI
smoke job runs one small dataset through all structures).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full dataset pool (slower)")
    ap.add_argument("--only", default="",
                    help="comma list: algorithms,scalability,waiting,"
                         "kernel_params,memory_scaling,adjacency,"
                         "persistence,faults")
    ap.add_argument("--datasets", default="",
                    help="comma list restricting the algorithms suite's "
                         "dataset pool (e.g. --datasets engine)")
    ap.add_argument("--shards", action="store_true",
                    help="run the scalability suite's shard sweep "
                         "(shards x workers cells, DESIGN.md §9) instead "
                         "of its worker sweep")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (bench_adjacency, bench_algorithms,
                            bench_faults, bench_kernel_params,
                            bench_memory_scaling, bench_persistence,
                            bench_scalability, bench_waiting)

    suites = {
        "algorithms": bench_algorithms,     # paper Figs. 7/8/9
        "scalability": bench_scalability,   # paper Tables 3/4
        "waiting": bench_waiting,           # paper Tables 5/6/7
        "kernel_params": bench_kernel_params,  # paper Appendix A
        "memory_scaling": bench_memory_scaling,  # Figs. 7-9 memory bars
        "adjacency": bench_adjacency,       # batched vs scalar completion
        "persistence": bench_persistence,   # pairing vs reduction A/B
        "faults": bench_faults,             # §12 recovery: identical=
    }
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        if only and name not in only:
            continue
        kw = {}
        if name == "algorithms" and args.datasets:
            kw["datasets"] = tuple(args.datasets.split(","))
        if name == "scalability" and args.shards:
            kw["shards"] = True
        for row in mod.run(quick=quick, **kw):
            print(row, flush=True)

    # roofline summary from dry-run artifacts (if the sweep has run)
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "dryrun")
    if os.path.isdir(d):
        n_ok = n_skip = n_err = 0
        for f in os.listdir(d):
            if not f.endswith(".json"):
                continue
            rec = json.load(open(os.path.join(d, f)))
            s = rec.get("status")
            n_ok += s == "ok"
            n_skip += s == "skipped"
            n_err += s not in ("ok", "skipped")
        print(f"dryrun/cells_ok,{n_ok},skipped={n_skip};errors={n_err}")


if __name__ == "__main__":
    main()
