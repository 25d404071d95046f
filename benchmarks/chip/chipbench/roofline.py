"""Peaks table and the least bytes a relation kernel has to move.

The peaks (``benchmarks/chip/peaks.json``) are the published per-chip
figures, keyed by ``device_kind``; a device missing from the table is an
error. The relation kernels sort, gather and scan int32 tables, and v5e
publishes no int32 vector rate, so their roofline is the HBM bandwidth
bound alone: the bytes below over the bandwidth, over the kernel's device
time.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Mapping

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")

# table kind of each side of a relation: rows (subject) and columns
SIDES = {"VV": ("V", "V"), "VE": ("V", "E"), "VF": ("V", "F"),
         "VT": ("V", "T"), "EF": ("E", "F"), "ET": ("E", "T"),
         "FT": ("F", "T"), "EE": ("E", "E"), "FF": ("F", "F"),
         "TT": ("T", "T")}
ARITY = {"V": 1, "E": 2, "F": 3, "T": 4}
INT32 = 4


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}") from None


def block_bytes(relation: str, rows: Mapping[str, int], deg: int) -> int:
    """Least bytes to produce one segment's block of ``relation``: read the
    segment's local tables once and write its ``(M, L)`` block once.

    ``rows[k]`` is the padded row count of the segment's local table of
    kind ``k`` (V, E, F, T), as the kernel is launched with. The kernel
    reads the row-side table ``(rows, arity)``, the column-side table
    ``(rows, arity)`` and its local-to-global map ``(rows,)``, and writes
    ``M (rows_x, deg)`` and ``L (rows_x,)``, all int32. VV is computed
    from the tet table alone (vertex pairs sharing a tet) with the vertex
    map as columns. The vertex table has no rows of its own: it is the
    vertex map, read once; a relation of one kind with itself (TT) reads
    its table once."""
    kx, ky = SIDES[relation]
    if relation == "VV":
        reads = rows["T"] * ARITY["T"] + rows["V"]
    else:
        reads = rows[ky] * ARITY[ky] + rows[ky]
        if kx == "V":
            reads += rows["V"]
        elif kx != ky:
            reads += rows[kx] * ARITY[kx]
    writes = rows[kx] * (deg + 1)
    return INT32 * (reads + writes)


def least_bytes(relations, produced: int, n_segments: int,
                rows: Mapping[str, int], deg: Mapping[str, int]) -> int:
    """Least bytes of a pass whose kernels produced ``produced`` segment
    blocks of ``relations``. No relation is produced twice for a segment,
    so at most ``n_segments`` blocks are of one relation: the least is the
    cheapest relations' blocks first, and exact where every relation was
    produced for every segment."""
    total, left = 0, int(produced)
    for b in sorted(block_bytes(r, rows, deg[r]) for r in relations):
        n = min(left, n_segments)
        total += n * b
        left -= n
    return total
