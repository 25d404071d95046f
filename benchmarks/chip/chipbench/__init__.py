"""On-chip benchmark of the GALE relation engine and its analysis drivers.

``benchmarks/chip/run.py`` runs one cell of ``BENCHMARK.json`` once. The
package holds the parts that stay fixed while the program changes: mesh
generation from a seed (:mod:`.meshgen`, each kind's generator found by name
under ``meshes/``), the pass loop that drives the program (:mod:`.drive`),
the plain reference and the comparison that decides ``correct``
(:mod:`.reference`, :mod:`.check`), the reduction of the profiler trace
(:mod:`.xplane`), the roofline arithmetic and peaks table (:mod:`.roofline`)
and the harness that finds configurations, mesh kinds, traffic mixes and
per-layer metrics by name (:mod:`.harness`).
"""

import importlib.util


def load_file(path: str, module_name: str):
    """The Python file at ``path``, loaded as a module named
    ``module_name`` (its dots and dashes read as ``_``): how the harness
    finds what a name in ``BENCHMARK.json`` or a configuration file points
    at."""
    module_name = module_name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
