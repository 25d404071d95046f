"""Deployment meshes generated from a configuration file and a seed.

A configuration file names its mesh's generator under ``"mesh"``: the
module ``meshes/<mesh>.py`` of the benchmark's directory, found by name as
traffic mixes and per-layer metrics are. Its ``generate(config, seed)``
returns a :class:`RawMesh`, which :func:`generate` checks before anyone
reads it. The same configuration and seed give the same points, tets and
scalars, bit for bit. What holds for every mesh lives here: the checks, the
map of program vertices onto raw ones by coordinates, the seeded Gaussian
field and the injective vertex order. Nothing here imports the program
under test.
"""

from __future__ import annotations

import dataclasses
import os
import numpy as np

from . import load_file

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MeshError(ValueError):
    """A generator returned a mesh that breaks what every mesh must be."""


def _coordinate_runs(rows: np.ndarray):
    """The order of a stable sort of float32 ``rows`` by their exact
    coordinates (-0.0 read as 0.0), and for each position of it the
    position where its run of equal rows starts."""
    bits = (np.asarray(rows, np.float32) + np.float32(0)).view(np.uint32)
    bits = bits.astype(np.uint64)
    hi, lo = (bits[:, 0] << np.uint64(32)) | bits[:, 1], bits[:, 2]
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    new = np.ones(len(order), bool)
    new[1:] = (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1])
    return order, np.maximum.accumulate(
        np.where(new, np.arange(len(order)), 0))


@dataclasses.dataclass
class RawMesh:
    """The benchmark's own copy of a generated mesh: ``points`` (nv, 3)
    float32 rows, all different, ``tets`` (nt, 4) int64 rows of four
    vertex ids in ascending order, ``scalars`` (nv,) float32, and
    ``facts``, what the generator reports of the mesh (logged at set-up,
    read by nothing)."""

    points: np.ndarray
    tets: np.ndarray
    scalars: np.ndarray
    facts: dict = dataclasses.field(default_factory=dict)

    @property
    def n_vertices(self) -> int:
        return len(self.points)

    @property
    def n_tets(self) -> int:
        return len(self.tets)

    def raw_ids(self, points: np.ndarray) -> np.ndarray:
        """Raw vertex id of each float32 row of ``points``: the vertex of
        this mesh at exactly those coordinates, or -1 where none is. One
        stable sort of this mesh's rows followed by ``points``'s: a row of
        ``points`` finds its vertex at the start of its run."""
        n = self.n_vertices
        order, start = _coordinate_runs(np.concatenate(
            [self.points, np.asarray(points, np.float32)]))
        first = order[start]
        query = order >= n
        out = np.full(len(order) - n, -1, np.int64)
        out[order[query] - n] = np.where(first[query] < n, first[query], -1)
        return out


def check_mesh(raw: RawMesh, generator: str) -> RawMesh:
    """``raw`` if it is what every generator must return; raises
    :class:`MeshError` naming ``generator`` otherwise."""
    def refuse(what):
        raise MeshError(f"mesh generator {generator!r}: {what}")
    p, t, s = raw.points, raw.tets, raw.scalars
    if p.dtype != np.float32 or p.ndim != 2 or p.shape[1] != 3:
        refuse(f"points must be (nv, 3) float32, got {p.shape} {p.dtype}")
    if not np.isfinite(p).all():
        refuse("points must be finite")
    _, start = _coordinate_runs(p)
    repeated = int((start != np.arange(len(p))).sum())
    if repeated:
        refuse(f"{repeated} points repeat another point's coordinates")
    if t.dtype != np.int64 or t.ndim != 2 or t.shape[1] != 4:
        refuse(f"tets must be (nt, 4) int64, got {t.shape} {t.dtype}")
    if len(t) and (t.min() < 0 or t.max() >= len(p)):
        refuse(f"tet vertex ids must lie in [0, {len(p)}), got "
               f"[{t.min()}, {t.max()}]")
    unsorted = int((t[:, 1:] <= t[:, :-1]).any(1).sum())
    if unsorted:
        refuse(f"{unsorted} tet rows are not four ids in ascending order")
    if s.dtype != np.float32 or s.shape != (len(p),):
        refuse(f"scalars must be ({len(p)},) float32, got {s.shape} "
               f"{s.dtype}")
    return raw


def generate(config: dict, seed: int,
             bench_dir: str = BENCH_DIR) -> RawMesh:
    """The mesh of ``config`` (a configuration file's JSON object) with the
    field of ``seed``, made by ``<bench_dir>/meshes/<config["mesh"]>.py``
    and checked by :func:`check_mesh`."""
    name = config["mesh"]
    mod = load_file(os.path.join(bench_dir, "meshes", name + ".py"),
                    "chipbench_mesh_" + name)
    return check_mesh(mod.generate(config, seed), name)


def gaussian_field(seed: int, k: int, sigma: float, scale: float):
    """Sum of ``k`` Gaussian bumps of width ``sigma`` with random signs,
    centred uniformly in ``[0, scale)^3``; evaluated in float64 and
    returned as float32."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0, scale, size=(k, 3))
    signs = rng.choice([-1.0, 1.0], size=k)

    def fn(p: np.ndarray) -> np.ndarray:
        p = np.asarray(p, dtype=np.float64)
        acc = np.zeros(len(p))
        for c, s in zip(centers, signs):
            d2 = ((p - c[None, :]) ** 2).sum(axis=1)
            acc += s * np.exp(-d2 / (2 * sigma * sigma))
        return acc.astype(np.float32)
    return fn


def injective_rank(scalars: np.ndarray) -> np.ndarray:
    """Rank of each vertex under (scalar, raw vertex id): the simulation
    of simplicity that makes the order injective."""
    n = len(scalars)
    order = np.lexsort((np.arange(n), np.asarray(scalars)))
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return rank
