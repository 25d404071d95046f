"""The ``kuhn`` mesh kind: a regular scalar volume, tetrahedralized by the
Kuhn (Freudenthal) split of each voxel into six tets around its main
diagonal, with an optional mask that removes null-valued voxels first
(GALE, arXiv:2507.15230 §5). The field is a sum of seeded Gaussian bumps.
Points are integer grid coordinates."""

from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench.meshgen import RawMesh, gaussian_field

# Kuhn split: six tets per voxel sharing the diagonal from corner 0 to
# corner 7; corners are bit-coded x + 2y + 4z.
KUHN_TETS = ((0, 1, 3, 7), (0, 1, 5, 7), (0, 2, 3, 7),
             (0, 2, 6, 7), (0, 4, 5, 7), (0, 4, 6, 7))
CORNERS = np.array([[b & 1, (b >> 1) & 1, (b >> 2) & 1] for b in range(8)])


def generate(config: dict, seed: int) -> RawMesh:
    """The mesh of ``config`` with the field of ``seed``. Keys read:
    ``grid`` [nx, ny, nz] vertices per axis, ``field`` {``k``,
    ``sigma_per_span``, ``scale_per_span``} with span the largest grid
    extent, and ``mask`` (null, or {``drop_below_quantile`` q,
    ``field_seed`` s}: the voxels where the field of seed s, at the voxel
    centre, lies below the q-quantile of those values are removed). The
    mask's field has a seed of its own, so the mesh is the same for every
    seed and only the analysed field changes with ``seed``. Facts: ``grid``
    and ``kept_share``, the share of voxels the mask kept."""
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
                   facts={"grid": [nx, ny, nz],
                          "kept_share": len(cells) / n_cells})
