"""Quickstart: build a mesh, stand up GALE, extract critical points.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.algorithms import fields
from repro.algorithms.critical_points import critical_points, total_order
from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    # 1. A tetrahedral mesh with a scalar field (4 Gaussian bumps).
    mesh = structured_grid(12, 12, 12,
                           scalar_fn=fields.gaussians(0, k=4, sigma=3.0,
                                                      scale=12))
    print(f"mesh: {mesh.n_vertices} vertices, {mesh.n_tets} tets")

    # 2. Segment (localized PR-octree leaves) + preconditioning: only the
    #    relations the algorithm needs (paper: VV + VT for critical points).
    sm = segment_mesh(mesh, capacity=64)
    pre = precondition(sm, relations=["VV", "VT"])
    print(f"segments: {sm.n_segments} (<=64 vertices each)")

    # 3. GALE: the task-parallel relation engine. Consumers call get();
    #    the leader producer batches requests + lookahead into one kernel.
    gale = RelationEngine(pre, ["VV", "VT"], lookahead=8)

    # 4. Run the consumer algorithm.
    rank = total_order(sm.scalars)
    types, counts = critical_points(gale, pre, rank)
    print("critical points:", counts)
    s = gale.stats
    print(f"engine: {s.kernel_launches} launches for "
          f"{s.segments_produced} segments produced, "
          f"{s.cache_hits} hits / {s.cache_misses} misses")


if __name__ == "__main__":
    main()
