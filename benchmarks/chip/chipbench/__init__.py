"""On-chip benchmark of the GALE relation engine and its analysis drivers.

``benchmarks/chip/run.py`` runs one cell of ``BENCHMARK.json`` once. The
package holds the parts that stay fixed while the program changes: mesh
generation from a seed (:mod:`.meshgen`), the pass loop that drives the
program (:mod:`.drive`), the plain reference and the comparison that decides
``correct`` (:mod:`.reference`, :mod:`.check`), the reduction of the
profiler trace (:mod:`.xplane`), the roofline arithmetic and peaks table
(:mod:`.roofline`) and the harness that finds configurations, traffic mixes
and per-layer metrics by name (:mod:`.harness`).
"""
