"""Deployment meshes generated from a configuration file and a seed.

A configuration names a regular scalar volume, tetrahedralized by the Kuhn
(Freudenthal) split of each voxel into six tets around its main diagonal,
with an optional mask that removes null-valued voxels first (GALE,
arXiv:2507.15230 §5). The field is a sum of seeded Gaussian bumps. The same
configuration and seed give the same points, tets and scalars, bit for bit.
Nothing here imports the program under test.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# Kuhn split: six tets per voxel sharing the diagonal from corner 0 to
# corner 7; corners are bit-coded x + 2y + 4z.
KUHN_TETS = ((0, 1, 3, 7), (0, 1, 5, 7), (0, 2, 3, 7),
             (0, 2, 6, 7), (0, 4, 5, 7), (0, 4, 6, 7))
CORNERS = np.array([[b & 1, (b >> 1) & 1, (b >> 2) & 1] for b in range(8)])


@dataclasses.dataclass
class RawMesh:
    """The benchmark's own copy of a generated mesh: ``points`` (nv, 3)
    f32 integer grid coordinates, ``tets`` (nt, 4) i64 rows sorted
    ascending, ``scalars`` (nv,) f32, ``grid`` the vertex counts per axis
    and ``kept_share`` the share of voxels the mask kept."""

    points: np.ndarray
    tets: np.ndarray
    scalars: np.ndarray
    grid: tuple
    kept_share: float

    @property
    def n_vertices(self) -> int:
        return len(self.points)

    @property
    def n_tets(self) -> int:
        return len(self.tets)

    def grid_ids(self, points: np.ndarray) -> np.ndarray:
        """Raw vertex id of each row of ``points`` (grid coordinates), or
        -1 where no vertex of this mesh sits there."""
        nx, ny, nz = self.grid
        p = np.rint(np.asarray(points, np.float64)).astype(np.int64)
        full = (p[:, 0] * ny + p[:, 1]) * nz + p[:, 2]
        lut = np.full(nx * ny * nz, -1, np.int64)
        mine = np.rint(self.points.astype(np.float64)).astype(np.int64)
        lut[(mine[:, 0] * ny + mine[:, 1]) * nz + mine[:, 2]] = np.arange(
            len(mine))
        ok = ((p >= 0).all(1) & (p[:, 0] < nx) & (p[:, 1] < ny)
              & (p[:, 2] < nz))
        return np.where(ok, lut[np.where(ok, full, 0)], -1)


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


def generate(config: dict, seed: int) -> RawMesh:
    """The mesh of ``config`` (a configuration file's JSON object) with
    the field of ``seed``. Keys read: ``grid`` [nx, ny, nz] vertices per
    axis, ``field`` {``k``, ``sigma_per_span``, ``scale_per_span``} with
    span the largest grid extent, and ``mask`` (null, or
    {``drop_below_quantile`` q, ``field_seed`` s}: the voxels where the
    field of seed s, at the voxel centre, lies below the q-quantile of
    those values are removed). The mask's field has a seed of its own, so
    the mesh is the same for every seed and only the analysed field
    changes with ``seed``."""
    nx, ny, nz = (int(v) for v in config["grid"])
    span = float(max(nx, ny, nz))
    f = config["field"]

    def field_of(s):
        return gaussian_field(s, int(f["k"]), f["sigma_per_span"] * span,
                              f["scale_per_span"] * span)
    cx, cy, cz = np.meshgrid(np.arange(nx - 1), np.arange(ny - 1),
                             np.arange(nz - 1), indexing="ij")
    cells = np.stack([cx, cy, cz], axis=-1).reshape(-1, 3)
    n_cells = len(cells)
    mask: Optional[dict] = config.get("mask")
    if mask:
        centre = field_of(int(mask["field_seed"]))(cells + 0.5)
        cut = np.quantile(centre, float(mask["drop_below_quantile"]))
        cells = cells[centre >= cut]
    corners = cells[:, None, :] + CORNERS[None, :, :]          # (c, 8, 3)
    cid = (corners[..., 0] * ny + corners[..., 1]) * nz + corners[..., 2]
    tets = np.concatenate([cid[:, list(t)] for t in KUHN_TETS], axis=0)
    used, tets = np.unique(tets, return_inverse=True)
    tets = np.sort(tets.reshape(-1, 4).astype(np.int64), axis=1)
    points = np.stack([used // (ny * nz), (used // nz) % ny, used % nz],
                      axis=1).astype(np.float32)
    return RawMesh(points=points, tets=tets, scalars=field_of(seed)(points),
                   grid=(nx, ny, nz), kept_share=len(cells) / n_cells)


def injective_rank(scalars: np.ndarray) -> np.ndarray:
    """Rank of each vertex under (scalar, raw vertex id): the simulation
    of simplicity that makes the order injective."""
    n = len(scalars)
    order = np.lexsort((np.arange(n), np.asarray(scalars)))
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return rank
