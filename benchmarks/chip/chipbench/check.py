"""The comparison that decides ``correct``.

The program renumbers the mesh (vertices in Morton order, tets by owner
segment, edges and faces enumerated by its preconditioning). :class:`Ids`
maps every program id onto the raw ids of the benchmark's own
:class:`~.reference.Complex` and counts the program's simplices that do not
match the raw mesh. Each ``*_mismatch`` function then counts the answers of
one layer that differ from the reference: relation rows (kernels), completed
rows (completion), and each driver's output. Every count is exact, so every
limit is 0.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Sequence

import numpy as np

from . import reference as ref


def digest(obj) -> str:
    """sha1 over an answer: an array, or a dataclass's array fields in
    field order."""
    h = hashlib.sha1()
    if isinstance(obj, np.ndarray):
        h.update(np.ascontiguousarray(obj).tobytes())
        return h.hexdigest()
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if isinstance(v, np.ndarray):
            h.update(f.name.encode())
            h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def _inverse(perm: np.ndarray, n: int) -> np.ndarray:
    inv = np.full(n, -1, np.int64)
    ok = perm >= 0
    inv[perm[ok]] = np.nonzero(ok)[0]
    return inv


class Ids:
    """Program ids -> raw ids for vertices, tets, and the edges and faces
    where the program enumerated them (the traffic reads them), with
    ``table_mismatch``: the program's simplices that are not simplices of
    the raw mesh, are listed twice, or are missing."""

    def __init__(self, raw, cx: ref.Complex, sm, pre):
        self.cx = cx
        self.E_prog = np.asarray(pre.E)
        v = raw.raw_ids(sm.points)
        bad = int((v < 0).sum())
        bad += int((np.asarray(sm.scalars)[v >= 0]
                    != raw.scalars[v[v >= 0]]).sum())
        self.v = v
        self.t = cx.tet_ids(v[np.asarray(sm.tets)])
        self.e = self.f = None
        kinds = [(self.v, raw.n_vertices), (self.t, len(cx.T))]
        if pre.E is not None:
            self.e = cx.edge_ids(v[np.asarray(pre.E)])
            kinds.append((self.e, len(cx.E)))
        if pre.F is not None:
            self.f = cx.face_ids(v[np.asarray(pre.F)])
            kinds.append((self.f, len(cx.F)))
        for prog, n in kinds:
            hit = prog[prog >= 0]
            bad += int((prog < 0).sum()) + len(hit) - len(np.unique(hit))
            bad += abs(n - len(prog))
        self.table_mismatch = bad
        self.v_of = _inverse(self.v, raw.n_vertices)
        self.t_of = _inverse(self.t, len(cx.T))

    def raw(self, kind: str) -> np.ndarray:
        return {"V": self.v, "E": self.e, "F": self.f, "T": self.t}[kind]

    def cell(self, kind: str, prog_id: int) -> tuple:
        """Sorted raw vertex tuple of one program simplex."""
        i = int(self.raw(kind)[prog_id])
        if kind == "V":
            return (i,)
        rows = {"E": self.cx.E, "F": self.cx.F, "T": self.cx.T}[kind]
        return tuple(int(x) for x in rows[i])


def block_mismatch(blocks: Dict[int, tuple], pre, ids: Ids,
                   relation: str) -> int:
    """Rows of relation blocks that differ from the reference relation (as
    sets of raw ids, with the row count ``L``). ``blocks`` maps a segment
    to its ``(M, L)`` block as the engine returned it. A block is local: it
    relates a segment's own simplices to the simplices of the tets that
    touch one of the segment's vertices (the segments are the program's),
    so a row of tets lists only those tets; completion adds the rest."""
    kx, ky = relation[0], relation[1]
    iv = pre.interval(kx)
    sub, tgt = ids.raw(kx), ids.raw(ky)
    iv_v = pre.interval("V")
    bad = 0
    for s, (M, L) in blocks.items():
        M, L = np.asarray(M), np.asarray(L)
        mine = np.zeros(ids.cx.nv, bool)
        mine[ids.v[iv_v[s]:iv_v[s + 1]]] = True
        for r in range(M.shape[0]):
            row = M[r][M[r] >= 0]
            got = np.sort(tgt[row])
            want = ids.cx.relation_row(relation, int(sub[iv[s] + r]))
            if ky == "T":
                want = want[mine[ids.cx.T[want]].any(1)]
            if int(L[r]) != len(want) or not np.array_equal(got, want):
                bad += 1
        if M.shape[0] != iv[s + 1] - iv[s]:
            bad += 1
    return bad


def completion_mismatch(M: np.ndarray, L: np.ndarray, ids: Ids,
                        relation: str, prog_ids: Sequence[int]) -> int:
    """Completed rows ``(M, L)`` for ``prog_ids`` that differ from the
    reference relation, as sets of raw ids with the row count ``L``."""
    kind = relation[0]
    raw_of = ids.raw(kind)
    q = raw_of[np.asarray(prog_ids, np.int64)]
    want = ids.cx.completed_rows(relation, q)
    M = np.asarray(M)
    got = np.where(M >= 0, raw_of[np.maximum(M, 0)], -1)
    width = max(got.shape[1], want.shape[1])
    got, want = (np.sort(np.pad(a, ((0, 0), (0, width - a.shape[1])),
                                constant_values=-1), axis=1)
                 for a in (got, want))
    bad = (got != want).any(1) | (np.asarray(L) != (want >= 0).sum(1))
    return int(bad.sum())


def type_mismatch(types_prog: np.ndarray, ids: Ids,
                  want: np.ndarray) -> int:
    """Vertices whose type differs from the reference's."""
    got = np.full(len(want), 99, np.int64)
    got[ids.v] = np.asarray(types_prog)[:len(ids.v)]
    return int((got != want).sum())


def gradient_mismatch(grad, ids: Ids, want: dict) -> int:
    """Lower stars (raw vertices) in which the program's gradient pairs
    some cell otherwise than the reference's (:func:`reference.gradient`),
    over every vertex of the mesh."""
    owner = {"V": np.arange(ids.cx.nv), "E": want["owner_e"],
             "F": want["owner_f"], "T": want["owner_t"]}
    bad = np.zeros(ids.cx.nv, bool)
    for name in ref.GRADIENT_FIELDS:
        x = name[-1].upper() if name.startswith("crit") else name[5].upper()
        got_p = np.asarray(getattr(grad, name))
        if name.startswith("crit"):
            got = np.zeros(len(want[name]), bool)
        else:
            y = name[-1].upper()
            got = np.full(len(want[name]), -1, np.int64)
            got_p = np.where(got_p >= 0, ids.raw(y)[np.maximum(got_p, 0)],
                             -1)
        got[ids.raw(x)] = got_p[:len(ids.raw(x))]
        bad[owner[x][got != want[name]]] = True
    return int(bad.sum())


def ms_mismatch(ms, ids: Ids, want: dict) -> Dict[str, int]:
    """The program's Morse-Smale answer against the reference's, per part:
    vertex minima, tet maxima and separatrix ends (both saddle kinds)."""
    dm = np.asarray(ms.dest_min)
    got_min = ids.v[dm[ids.v_of]]
    dx = np.asarray(ms.dest_max)[ids.t_of]
    got_max = np.where(dx >= 0, ids.t[np.maximum(dx, 0)], -1)
    s1 = {}
    for e, m0, m1 in np.asarray(ms.saddle1_ends):
        # the program lists the two ends in its own endpoint order
        p0, p1 = ids.E_prog[e]
        d = {int(ids.v[p0]): int(ids.v[m0]), int(ids.v[p1]): int(ids.v[m1])}
        a, b = ids.cell("E", e)
        s1[int(ids.e[e])] = (d.get(a), d.get(b))
    s2 = {}
    for f, m0, m1 in np.asarray(ms.saddle2_ends):
        ends = [int(ids.t[m]) if m >= 0 else -1 for m in (m0, m1)]
        s2[int(ids.f[f])] = tuple(sorted(ends))
    return {
        "ms_dest_min": int((got_min != want["dest_min"]).sum()),
        "ms_dest_max": int((got_max != want["dest_max"]).sum()),
        "ms_separatrices": _dict_mismatch(s1, want["saddle1"])
        + _dict_mismatch(s2, want["saddle2"]),
    }


def _dict_mismatch(got: dict, want: dict) -> int:
    return sum(got.get(k) != want.get(k) for k in set(got) | set(want))
