"""Full TDA pipeline (the paper's four algorithms) on one dataset, with a
GALE vs Explicit-Triangulation comparison — results must be identical.

Both structures run the device-resident consumer pipeline
(docs/DESIGN.md §6): the drivers read relation blocks as ConsumerBatch
device arrays (`get_full_dev_many`) and the GALE engine serves every read
from its device block pool — the stats line shows zero host block reads.
``--workers N`` runs the drivers' consumer arms on N CPU threads through
the scheduler (docs/DESIGN.md §8); results are bit-identical for any N.
``--shards K`` builds the GALE engine over K segment shards (one device
per shard when the platform has them, docs/DESIGN.md §9); the drivers
follow the engine's plan automatically and results stay bit-identical.

``--simplify T`` additionally cancels every persistence pair below
threshold T and reports the simplified Morse-Smale complex.

  PYTHONPATH=src python examples/analyze_mesh.py [dataset] [--workers N]
                                                 [--shards K] [--simplify T]
"""

import argparse
import time

from repro.algorithms import fields
from repro.algorithms.critical_points import critical_points, total_order
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale
from repro.algorithms.persistence import persistence_pairs, simplify_ms
from repro.core.engine import RelationEngine
from repro.core.explicit import ExplicitTriangulation
from repro.core.mesh import segment_mesh
from repro.core.segtables import precondition
from repro.data.meshgen import load_dataset
from repro.launch import compile_cache

RELS = ["VV", "VE", "VF", "VT", "FT", "TT"]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("dataset", nargs="?", default="foot")
    ap.add_argument("--workers", type=int, default=1,
                    help="consumer threads per driver (DESIGN.md §8)")
    ap.add_argument("--shards", type=int, default=1,
                    help="segment shards on the GALE engine (DESIGN.md §9)")
    ap.add_argument("--simplify", type=float, default=None, metavar="T",
                    help="cancel persistence pairs below threshold T and "
                         "report the simplified MS complex (DESIGN.md §10)")
    args = ap.parse_args()
    compile_cache.enable()
    name, workers = args.dataset, args.workers
    mesh = load_dataset(name, scalar_fn=fields.gaussians(2, k=5, sigma=5.0))
    sm = segment_mesh(mesh, capacity=64)
    pre = precondition(sm, relations=RELS)
    rank = total_order(sm.scalars)
    chi = sm.n_vertices - pre.n_edges + pre.n_faces - sm.n_tets
    print(f"{name}: v={sm.n_vertices} e={pre.n_edges} f={pre.n_faces} "
          f"t={sm.n_tets}  chi={chi}")

    for label, ds in (
            ("GALE", RelationEngine(pre, RELS, lookahead=8,
                                    dev_pool_segments=4096,
                                    shards=args.shards)),
            ("Explicit", ExplicitTriangulation(pre, RELS))):
        t0 = time.perf_counter()
        _, cp = critical_points(ds, pre, rank, batch_segments=16,
                                workers=workers)
        # co-prefetch the TT queue: completion kernels for the Morse-Smale
        # step execute behind the lower-star sweep (DESIGN.md §6)
        g = discrete_gradient(ds, pre, rank, batch_segments=16,
                              co_prefetch=("TT",), workers=workers)
        ms = morse_smale(ds, pre, g, workers=workers)
        diag = persistence_pairs(ds, pre, rank, grad=g, workers=workers)
        dt = time.perf_counter() - t0
        assert g.euler() == chi, "Morse-Euler identity violated!"
        s = ds.stats
        print(f"[{label:9s}] {dt:6.2f}s  critical={cp}  "
              f"gradient={g.counts()}  ms={ms.counts()}")
        pd = diag.counts()
        pers = diag.persistence0()
        print(f"            persistence: {pd['pairs0']} dim-0 pairs "
              f"(max pers {pers.max() if len(pers) else 0:.3f}), "
              f"{pd['pairs2']} dim-2 pairs, "
              f"{pd['essential0']} essential component(s)  "
              f"digest={diag.digest()[:12]}")
        if args.simplify is not None:
            simp, rep = simplify_ms(ms, diag, args.simplify)
            print(f"            simplified @ {args.simplify:g}: "
                  f"cancelled {rep['cancelled0']}+{rep['cancelled2']} pairs, "
                  f"minima {rep['minima_before']}->{rep['minima_after']}, "
                  f"maxima {rep['maxima_before']}->{rep['maxima_after']}")
        print(f"            consumer: {s.requests} block reads = "
              f"{s.devpool_hits} device-pool hits + "
              f"{s.devpool_uploads} uploads "
              f"(host reads: {s.requests - s.devpool_hits - s.devpool_uploads})"
              f"  t_sync={s.t_sync:.3f}s")
        shard_stats = getattr(ds, "shard_stats", {})
        if len(shard_stats) > 1:
            per = {k: v.segments_produced
                   for k, v in sorted(shard_stats.items())}
            print(f"            shards: segments_produced per shard = {per}")


if __name__ == "__main__":
    main()
