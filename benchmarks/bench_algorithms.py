"""Paper Figs. 7/8/9: total time + memory of the four TDA algorithms
(critical points, discrete gradient, Morse-Smale, persistence pairing) with
{GALE, ACTOPO, TopoCluster, Explicit Triangulation} across datasets.

The GALE engine is benchmarked through BOTH consumer arms (docs/DESIGN.md
§6): ``gale`` drives the drivers device-resident off the engine's block
pool, ``gale_host`` is the same engine through the PR-3 host-consumer path.
Every measurement is a steady-state (second) run so comparisons reflect
the pipelines, not jit compile order; the ``dev_vs_host`` rows carry
the speedup and a bit-identical flag, and every engine-backed record
asserts the hot loop performed zero per-batch host block reads (all reads
served by the device pool or counted uploads).

Machine-readable output: ``run()`` writes ``BENCH_algorithms.json`` at the
repo root (override the path with ``$BENCH_ALGORITHMS_JSON``) with one
record per
(algo, dataset, structure) — ``t_algo``, ``t_sync``, devpool counters,
memory — so the perf trajectory is tracked across PRs (CI uploads it as an
artifact).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

from repro.algorithms.critical_points import critical_points
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale
from repro.algorithms.persistence import persistence_pairs

from . import common

CP_RELS = ("VV", "VT")                       # paper: 2 queues
DG_RELS = ("VE", "VF", "VT")                 # paper: 3 queues
MS_RELS = ("VE", "VF", "VT", "FT", "TT")     # + FT/TT for separatrices
PD_RELS = MS_RELS                            # persistence: same 5 queues
# (engine-backed morse_smale assembles ascending successors from completed
# TT adjacency; the other structures take the FT-gather path — bit-identical)

STRUCTURES = ("gale", "gale_host", "actopo", "topocluster", "explicit")

# consumer arm per structure: the gale pair is the device-vs-host A/B;
# everything else auto-selects (explicit exposes the batch API and runs the
# same device-consumer code path, the CPU baselines stay host)
_CONSUMER = {"gale": "device", "gale_host": "host"}


def _run_algo(algo: str, ds, pre, rank, kind: str):
    consumer = _CONSUMER.get(kind, "auto")
    if algo == "critical_points":
        return critical_points(ds, pre, rank, batch_segments=16,
                               consumer=consumer)
    if algo == "discrete_gradient":
        return discrete_gradient(ds, pre, rank, batch_segments=16,
                                 consumer=consumer)
    if algo == "morse_smale":
        # the device pipeline co-prefetches TT during the gradient sweep so
        # completion kernels hide behind the lower-star state machines
        co = ("TT",) if consumer == "device" else ()
        g = discrete_gradient(ds, pre, rank, batch_segments=16,
                              consumer=consumer, co_prefetch=co)
        return morse_smale(ds, pre, g, consumer=consumer)
    if algo == "persistence":
        co = ("TT", "FT") if consumer == "device" else ()
        g = discrete_gradient(ds, pre, rank, batch_segments=16,
                              consumer=consumer, co_prefetch=co)
        return persistence_pairs(ds, pre, rank, grad=g, consumer=consumer)
    raise KeyError(algo)


def _zero_host_reads(ds) -> Optional[bool]:
    """Engine-backed structures: every block read served device-side."""
    stats = getattr(ds, "stats", None)
    if stats is None or stats.requests == 0:
        return None
    return stats.requests == stats.devpool_hits + stats.devpool_uploads


def bench(algo: str, relations, datasets, structures=STRUCTURES,
          capacity=64, records: Optional[List[Dict]] = None) -> List[str]:
    rows = []
    ref = {}
    for name in datasets:
        sm, pre, rank, t_pre = common.prepare(name, relations, capacity)
        gale_t = {}
        for kind in structures:
            # every structure is timed warm (second run, fresh data
            # structure) so cross-structure rows and the device-vs-host A/B
            # measure the pipelines, not jit compile order
            runs = 2
            for _ in range(runs):
                t0 = time.perf_counter()
                ds = common.make_ds(kind, pre, relations)
                t_init = time.perf_counter() - t0
                t_algo, out = common.timed(_run_algo, algo, ds, pre, rank,
                                           kind)
            mem = common.ds_memory_bytes(ds)
            # correctness cross-check between structures
            sig = _signature(algo, out)
            ref.setdefault(name, sig)
            ok = "ok" if sig == ref[name] else "MISMATCH"
            stats = getattr(ds, "stats", None)
            zero = _zero_host_reads(ds)
            rows.append(common.row(
                f"{algo}/{name}/{kind}", t_init + t_algo,
                f"init_s={t_init + t_pre:.3f};algo_s={t_algo:.3f};"
                f"mem_mb={mem / 1e6:.1f};{ok}"))
            if records is not None:
                records.append({
                    "algo": algo, "dataset": name, "structure": kind,
                    "t_init": t_init, "t_pre": t_pre, "t_algo": t_algo,
                    "t_sync": stats.t_sync if stats else 0.0,
                    "t_dispatch": stats.t_dispatch if stats else 0.0,
                    "requests": stats.requests if stats else 0,
                    "devpool_hits": stats.devpool_hits if stats else 0,
                    "devpool_uploads": stats.devpool_uploads if stats else 0,
                    "mem_mb": mem / 1e6, "ok": ok == "ok",
                    "zero_host_reads": zero, "warmed": runs > 1,
                })
            if kind in ("gale", "gale_host"):
                gale_t[kind] = (t_algo, sig)
                if kind == "gale" and zero is False:
                    rows.append(common.row(
                        f"{algo}/{name}/gale_host_reads", 0.0,
                        "zero_host_reads=False"))
        if "gale" in gale_t and "gale_host" in gale_t:
            t_dev, sig_dev = gale_t["gale"]
            t_host, sig_host = gale_t["gale_host"]
            sp = t_host / t_dev if t_dev > 0 else float("inf")
            ident = sig_dev == sig_host
            rows.append(common.row(
                f"{algo}/{name}/dev_vs_host", t_dev,
                f"host_s={t_host:.3f};speedup={sp:.2f};identical={ident}"))
            if records is not None:
                records.append({
                    "algo": algo, "dataset": name, "structure": "dev_vs_host",
                    "t_algo": t_dev, "t_host": t_host, "speedup": sp,
                    "ok": ident, "zero_host_reads": None,
                })
    return rows


def _signature(algo, out):
    if algo == "critical_points":
        return tuple(sorted(out[1].items()))
    if algo == "persistence":
        # full bit-identity across structures/arms, not just counts
        return out.digest()
    return tuple(sorted(out.counts().items()))


def _interp_guard(records: Optional[List[Dict]] = None) -> List[str]:
    """Pallas-interpret smoke: the device consumer arm must be the one
    auto-selected on an engine whatever the kernel backend — CI fails if
    the drivers silently fall back to host block reads there."""
    from repro.core.engine import RelationEngine

    sm, pre, rank, _ = common.prepare("toy", CP_RELS, capacity=8)
    eng = RelationEngine(pre, CP_RELS, backend="pallas_interpret")
    t_algo, out = common.timed(critical_points, eng, pre, rank,
                               batch_segments=2)
    zero = _zero_host_reads(eng)
    row = common.row(
        "critical_points/toy/gale_interp", t_algo,
        f"consumer={'device' if zero else 'HOST-FALLBACK'};"
        f"zero_host_reads={zero}")
    if records is not None:
        records.append({
            "algo": "critical_points", "dataset": "toy",
            "structure": "gale_interp", "t_algo": t_algo,
            "ok": bool(zero), "zero_host_reads": zero,
        })
    return [row]


def run(quick: bool = True, datasets=None) -> List[str]:
    data = datasets or (common.QUICK_DATASETS if quick
                        else common.FULL_DATASETS)
    structs = (("gale", "gale_host", "actopo", "explicit") if quick
               else STRUCTURES)
    rows = []
    records: List[Dict] = []
    # critical points keeps all five structures (incl. TopoCluster) so the
    # localized-vs-localized ordering is visible even in quick mode
    rows += bench("critical_points", CP_RELS, data, STRUCTURES,
                  records=records)
    rows += bench("discrete_gradient", DG_RELS, data, structs,
                  records=records)
    rows += bench("morse_smale", MS_RELS,
                  data[:2] if quick else data, structs, records=records)
    rows += bench("persistence", PD_RELS,
                  data[:2] if quick else data, structs, records=records)
    rows += _interp_guard(records)

    # aggregate device-vs-host verification row (the PR's A/B gate)
    sp = [r for r in records if r["structure"] == "dev_vs_host"]
    if sp:
        tot_dev = sum(r["t_algo"] for r in sp)
        tot_host = sum(r["t_host"] for r in sp)
        ident = all(r["ok"] for r in sp)
        rows.append(common.row(
            "algorithms/dev_vs_host_total", tot_dev,
            f"host_s={tot_host:.3f};speedup={tot_host / tot_dev:.2f};"
            f"identical={ident}"))
        records.append({
            "algo": "all", "dataset": "all", "structure": "dev_vs_host_total",
            "t_algo": tot_dev, "t_host": tot_host,
            "speedup": tot_host / tot_dev, "ok": ident,
        })

    path = os.environ.get(
        "BENCH_ALGORITHMS_JSON",
        os.path.join(os.path.dirname(__file__), "..",
                     "BENCH_algorithms.json"))
    with open(path, "w") as fh:
        json.dump({"suite": "algorithms", "quick": quick,
                   "records": records}, fh, indent=1)
    return rows
