"""Paper Appendix A: kernel parameter study — now the autotune harness.

  t_s (threads per segment)  -> Pallas block shapes (block_x, block_y):
       how finely one segment's relation tile is partitioned (counts
       fallback kernels only; the sparse entry kernels launch one grid
       step per batched segment).
  t_b x n_b (block dim)      -> segments per batched launch (lookahead x
       batch_max): how much work one leader launch covers.

Four sections, all recorded in ``BENCH_kernel_params.json`` (override the
path with ``$BENCH_KERNEL_PARAMS_JSON``):

  1. launch-size sweep, sparse entry assembly vs the old one-hot counts +
     ``top_k`` epilogue (``assembly="dense"``) on the xla backend — the
     wall-clock A/B the acceptance gate reads (``speedup`` per row);
  2. per-relation extraction throughput (paper Fig. 11 analogue);
  3. pallas_interpret-vs-xla parity for ALL TEN relations through the real
     engine dispatch — structural-correctness rows, ``identical=True`` is
     what the ``kernel-params-smoke`` CI job greps;
  4. roofline-ranked autotune candidates (``launch/autotune.py``) measured
     on the real engine, winner persisted, then reloaded through
     ``RelationEngine(tune=<path>)`` and verified bit-identical.

Interpreter rows are structural checks only (VMEM tiling benefits require
the real MXU); xla rows are meaningful wall-clock on this CPU container."""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

from repro.core.engine import RelationEngine
from repro.core.segtables import OFFLOADED_RELATIONS
from repro.launch import autotune, roofline

from . import common

RELATIONS = ("VV", "VT", "VE", "VF", "ET", "EF", "FT")


def _sweep_time(eng, n_req: int, batch: int) -> float:
    t0 = time.perf_counter()
    for s0 in range(0, n_req, batch):
        eng.get_batch("VV", list(range(s0, min(s0 + batch, n_req))))
        eng.clear_cache()
    return time.perf_counter() - t0


def run(quick: bool = True) -> List[str]:
    rows: List[str] = []
    records: List[Dict] = []
    sm, pre, rank, _ = common.prepare("engine" if quick else "fish",
                                      RELATIONS)
    ns = sm.n_segments

    # -- 1. segments-per-launch sweep, sparse vs dense assembly (xla) ------
    n_req = min(64 if quick else 256, ns)
    for batch in (1, 4, 16, 64):
        times = {}
        for assembly in ("sparse", "dense"):
            eng = RelationEngine(pre, RELATIONS, backend="xla", lookahead=0,
                                 batch_max=batch,
                                 cache_segments=2 * batch + 8,
                                 tune="off", assembly=assembly)
            _sweep_time(eng, n_req, batch)        # warmup: jit compile
            times[assembly] = _sweep_time(eng, n_req, batch)
        speedup = times["dense"] / times["sparse"]
        rows.append(common.row(
            f"kernel_params/segments_per_launch/{batch}",
            times["sparse"] / n_req,
            f"dense_us={times['dense'] / n_req * 1e6:.1f};"
            f"speedup={speedup:.2f}"))
        records.append({"section": "segments_per_launch", "batch": batch,
                        "sparse_s": times["sparse"],
                        "dense_s": times["dense"], "speedup": speedup})

    # -- 2. per-relation extraction throughput (paper Fig. 11 analogue) ----
    segs = list(range(min(64, ns)))
    for R in RELATIONS:
        eng = RelationEngine(pre, RELATIONS, backend="xla", lookahead=0,
                             batch_max=64, cache_segments=4, tune="off")
        t0 = time.perf_counter()
        eng.get_batch(R, segs)
        t = time.perf_counter() - t0
        rows.append(common.row(
            f"kernel_params/relation/{R}", t / len(segs),
            f"segments={len(segs)};total_s={t:.3f}"))
        records.append({"section": "relation", "relation": R,
                        "total_s": t, "segments": len(segs)})

    # -- 3. pallas_interpret vs xla parity, all ten relations --------------
    # the sparse entry kernels (and the EE/FF counts fallback) through the
    # REAL engine dispatch; identical=True rows are the CI smoke gate
    par_segs = list(range(min(2, ns)))
    e_ref = RelationEngine(pre, OFFLOADED_RELATIONS, backend="xla",
                           lookahead=0, tune="off")
    e_pal = RelationEngine(pre, OFFLOADED_RELATIONS,
                           backend="pallas_interpret", lookahead=0,
                           batch_max=len(par_segs), tune="off")
    for R in OFFLOADED_RELATIONS:
        ref = e_ref.get_batch(R, par_segs)
        t0 = time.perf_counter()
        pal = e_pal.get_batch(R, par_segs)
        t = time.perf_counter() - t0
        same = all(np.array_equal(mr, mp) and np.array_equal(lr, lp)
                   for (mr, lr), (mp, lp) in zip(ref, pal))
        rows.append(common.row(
            f"kernel_params/parity/{R}", t / len(par_segs),
            f"identical={same};interpret=1"))
        records.append({"section": "parity", "relation": R,
                        "identical": bool(same)})

    # -- 4. autotune: roofline-ranked candidates, measured, persisted ------
    rows_per_seg = int(pre.tables.NT)
    kind = jax.devices()[0].device_kind
    if kind in roofline.PEAKS:
        cands = autotune.candidate_configs(ns, kind, rows_per_seg,
                                           max_candidates=3 if quick else 8)
    else:
        # no published peaks to rank by on this device: measure only the
        # built-in default, so the table round trip below still runs
        cands = [autotune.KernelConfig()]
        rows.append(common.row("kernel_params/autotune/unranked", 0.0,
                               f"device_kind={kind}"))
    tune_segs = list(range(min(32, ns)))

    def make_engine(cfg):
        return RelationEngine(pre, RELATIONS, backend="xla", lookahead=0,
                              batch_max=cfg.batch_max,
                              block_x=cfg.block_x, block_y=cfg.block_y,
                              cache_segments=len(tune_segs) + 8,
                              tune="off")

    best_cfg, best_s = None, float("inf")
    for cfg in cands:
        t = autotune.measure_engine(make_engine, ("VV", "ET"), tune_segs,
                                    cfg, repeats=2)
        rows.append(common.row(
            f"kernel_params/autotune/bx{cfg.block_x}_by{cfg.block_y}"
            f"_bm{cfg.batch_max}_fl{cfg.bucket_floor}",
            t / len(tune_segs), f"measured_s={t:.4f}"))
        records.append({"section": "autotune_candidate",
                        "config": cfg.to_dict(), "measured_s": t})
        if t < best_s:
            best_cfg, best_s = cfg, t

    # persist the winner and prove the round trip: an engine constructed
    # with tune=<table> adopts the tuned knobs and produces the identical
    # blocks as today's defaults
    tune_path = os.environ.get(
        "REPRO_TUNE_TABLE",
        os.path.join(tempfile.gettempdir(), "TUNE_kernel_params.json"))
    autotune.record("xla", ns, best_cfg, kind, path=tune_path,
                    score_s=best_s)
    e_def = RelationEngine(pre, RELATIONS, backend="xla", lookahead=0,
                           tune="off")
    e_tun = RelationEngine(pre, RELATIONS, backend="xla", lookahead=0,
                           tune=tune_path)
    adopted = (e_tun.batch_max == best_cfg.batch_max
               and e_tun.block_x == best_cfg.block_x
               and e_tun.block_y == best_cfg.block_y
               and e_tun.bucket_floor == best_cfg.bucket_floor)
    same = all(
        np.array_equal(md, mt) and np.array_equal(ld, lt)
        for R in ("VV", "ET")
        for (md, ld), (mt, lt) in zip(e_def.get_batch(R, par_segs),
                                      e_tun.get_batch(R, par_segs)))
    rows.append(common.row(
        "kernel_params/autotune/roundtrip", best_s / len(tune_segs),
        f"identical={bool(adopted and same)};"
        f"winner=bx{best_cfg.block_x}_bm{best_cfg.batch_max}"))
    records.append({"section": "autotune_roundtrip",
                    "identical": bool(adopted and same),
                    "winner": best_cfg.to_dict(), "score_s": best_s})

    path = os.environ.get(
        "BENCH_KERNEL_PARAMS_JSON",
        os.path.join(os.path.dirname(__file__), "..",
                     "BENCH_kernel_params.json"))
    with open(path, "w") as fh:
        json.dump({"suite": "kernel_params", "quick": quick,
                   "records": records}, fh, indent=1)
    return rows
