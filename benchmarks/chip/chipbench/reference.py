"""Plain reference for the chip benchmark, in numpy and scipy.

Everything here is computed from the benchmark's own raw mesh (points,
tets, scalars and the injective vertex rank): the simplices and relations
of the mesh, the Banchoff type of every vertex, the lower-star pairing of
the discrete gradient (Robins et al. 2011) for chosen vertices, and the
Morse-Smale destinations of a given gradient. It imports nothing of the
program under test; the comparison in :mod:`.check` maps the program's
answers onto these raw ids.

Vertex type codes: regular -1, minimum 0, 1-saddle 1, 2-saddle 2,
maximum 3, degenerate 4.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Tuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

REGULAR, MINIMUM, SADDLE1, SADDLE2, MAXIMUM, DEGENERATE = -1, 0, 1, 2, 3, 4


def _csr(keys: np.ndarray, vals: np.ndarray, n: int):
    """CSR map ``key -> sorted values`` over keys in ``[0, n)``."""
    order = np.lexsort((vals, keys))
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys, minlength=n), out=offs[1:])
    return offs, vals[order]


class Complex:
    """Simplices of a tet mesh, each kind as unique sorted vertex rows in
    lexicographic order (ids are row positions), with the incidence maps
    the reference reads."""

    def __init__(self, tets: np.ndarray, n_vertices: int):
        nv = int(n_vertices)
        if nv >= 2 ** 21:
            raise ValueError("face keys need n_vertices < 2**21")
        self.nv = nv
        T = np.sort(np.asarray(tets, np.int64).reshape(-1, 4), axis=1)
        T = T[np.lexsort(T.T[::-1])]
        keep = np.ones(len(T), bool)
        keep[1:] = (np.diff(T, axis=0) != 0).any(1)
        self.T = T[keep]
        pairs = self.T[:, list(itertools.combinations(range(4), 2))]
        self.e_key = np.unique(pairs[..., 0] * nv + pairs[..., 1])
        self.E = np.stack([self.e_key // nv, self.e_key % nv], axis=1)
        tris = self.T[:, list(itertools.combinations(range(4), 3))]
        self.f_key = np.unique((tris[..., 0] * nv + tris[..., 1]) * nv
                               + tris[..., 2])
        self.F = np.stack([self.f_key // (nv * nv),
                           (self.f_key // nv) % nv, self.f_key % nv], axis=1)
        self.t_key3 = ((self.T[:, 0] * nv + self.T[:, 1]) * nv
                       + self.T[:, 2])
        nt = len(self.T)
        # vertex -> incident tets / edges / faces
        self.vt = _csr(self.T.reshape(-1), np.repeat(np.arange(nt), 4), nv)
        self.ve = _csr(self.E.reshape(-1),
                       np.repeat(np.arange(len(self.E)), 2), nv)
        self.vf = _csr(self.F.reshape(-1),
                       np.repeat(np.arange(len(self.F)), 3), nv)
        # face -> cofacet tets (one or two)
        tf = self.face_ids(tris.reshape(-1, 3))
        self.ft = _csr(tf, np.repeat(np.arange(nt), 4), len(self.F))
        self.tf = tf.reshape(nt, 4)

    def edge_ids(self, pairs: np.ndarray) -> np.ndarray:
        p = np.sort(np.asarray(pairs, np.int64).reshape(-1, 2), axis=1)
        return self._find(self.e_key, p[:, 0] * self.nv + p[:, 1])

    def face_ids(self, tris: np.ndarray) -> np.ndarray:
        t = np.sort(np.asarray(tris, np.int64).reshape(-1, 3), axis=1)
        return self._find(self.f_key,
                          (t[:, 0] * self.nv + t[:, 1]) * self.nv + t[:, 2])

    def tet_ids(self, quads: np.ndarray) -> np.ndarray:
        """Ids of tets given as vertex rows; -1 where absent. ``T`` is
        sorted, so the key of its first three vertices is non-decreasing
        and at most two tets share it."""
        q = np.sort(np.asarray(quads, np.int64).reshape(-1, 4), axis=1)
        nv = self.nv
        k3 = (q[:, 0] * nv + q[:, 1]) * nv + q[:, 2]
        if len(self.T) == 0:
            return np.full(len(q), -1, np.int64)
        lo = np.searchsorted(self.t_key3, k3)
        out = np.full(len(q), -1, np.int64)
        for step in (0, 1):
            at = np.minimum(lo + step, len(self.T) - 1)
            hit = ((self.t_key3[at] == k3) & (self.T[at, 3] == q[:, 3])
                   & (out < 0))
            out[hit] = at[hit]
        return out

    @staticmethod
    def _find(keys: np.ndarray, q: np.ndarray) -> np.ndarray:
        if len(keys) == 0:
            return np.full(len(q), -1, np.int64)
        pos = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
        return np.where(keys[pos] == q, pos, -1)

    @staticmethod
    def _row(csr, i: int) -> np.ndarray:
        offs, vals = csr
        return vals[offs[i]:offs[i + 1]]

    # -- relations, as sorted id arrays -----------------------------------

    def relation_row(self, relation: str, i: int) -> np.ndarray:
        """Ids related to simplex ``i`` under ``relation`` (VV, VE, VF,
        VT, FT or TT), sorted."""
        if relation == "VE":
            return self._row(self.ve, i)
        if relation == "VF":
            return self._row(self.vf, i)
        if relation == "VT":
            return self._row(self.vt, i)
        if relation == "VV":
            e = self.E[self._row(self.ve, i)]
            return np.sort(np.where(e[:, 0] == i, e[:, 1], e[:, 0]))
        if relation == "FT":
            return self._row(self.ft, i)
        if relation == "TT":
            nb = np.concatenate([self._row(self.ft, f) for f in self.tf[i]])
            return np.unique(nb[nb != i])
        raise KeyError(relation)

    def completed_rows(self, relation: str, ids: np.ndarray) -> np.ndarray:
        """Rows of ``relation`` for ``ids``, padded with -1: for TT, the
        tets across each face of the tet (at most four)."""
        if relation != "TT":
            raise KeyError(f"no completed rows of {relation}")
        offs, vals = self.ft
        f = self.tf[ids]                                   # (n, 4)
        c0 = vals[offs[f]]
        c1 = np.where(offs[f + 1] - offs[f] > 1,
                      vals[np.minimum(offs[f] + 1, len(vals) - 1)], -1)
        return np.where(c0 == ids[:, None], c1, c0)


# -- critical points ----------------------------------------------------------

def vertex_types(cx: Complex, rank: np.ndarray) -> np.ndarray:
    """Banchoff type of every vertex from the number of connected
    components of its lower link (``nl``) and upper link (``nu``):
    minimum where ``nl`` is 0, maximum where ``nu`` is 0, 1-saddle where
    ``nl`` >= 2 and ``nu`` <= 1, 2-saddle where ``nl`` <= 1 and ``nu`` >= 2,
    degenerate where both are >= 2 or both are 0 (no link), otherwise
    regular; a vertex with ``nl`` >= 2 and ``nu`` = 0 is a maximum and one
    with ``nl`` = 0 and ``nu`` >= 2 a minimum.

    Each link vertex ``x`` of ``v`` is the directed edge ``(v, x)``; two of
    them are joined when ``v, x, y`` span a face and ``x``, ``y`` lie on the
    same side of ``v``. Components are those of that graph."""
    nv = cx.nv
    E = cx.E
    # directed edges (v, x), keyed v * nv + x, as graph nodes
    dkey = np.sort(np.concatenate([E[:, 0] * nv + E[:, 1],
                                   E[:, 1] * nv + E[:, 0]]))
    src = dkey // nv
    dst = dkey % nv
    lower = rank[dst] < rank[src]
    # link edges: for every face (a, b, c) and each of its vertices v, the
    # other two vertices are joined in link(v)
    F = cx.F
    a_list, b_list = [], []
    for v_col, x_col, y_col in ((0, 1, 2), (1, 0, 2), (2, 0, 1)):
        v, x, y = F[:, v_col], F[:, x_col], F[:, y_col]
        a_list.append(np.searchsorted(dkey, v * nv + x))
        b_list.append(np.searchsorted(dkey, v * nv + y))
    a = np.concatenate(a_list)
    b = np.concatenate(b_list)
    same = lower[a] == lower[b]
    a, b = a[same], b[same]
    n = len(dkey)
    g = coo_matrix((np.ones(len(a), np.int8), (a, b)), shape=(n, n))
    _, label = connected_components(g, directed=False)
    side = lower.astype(np.int64)
    key = np.unique((src * 2 + side) * n + label)
    counts = np.bincount(key // n, minlength=2 * nv)
    nl = counts[1::2]
    nu = counts[0::2]
    t = np.full(nv, REGULAR, np.int32)
    t[(nl >= 2) & (nu >= 2)] = DEGENERATE
    t[(nl >= 2) & (nu <= 1)] = SADDLE1
    t[(nl <= 1) & (nu >= 2)] = SADDLE2
    t[nl == 0] = MINIMUM
    t[nu == 0] = MAXIMUM
    t[(nl == 0) & (nu == 0)] = DEGENERATE
    return t


# -- discrete gradient --------------------------------------------------------

Cell = Tuple[int, ...]          # sorted raw vertex ids


def lower_star_pairs(cx: Complex, rank: np.ndarray, v: int
                     ) -> Dict[Cell, Optional[Cell]]:
    """Gradient pairing of the lower star of vertex ``v``: every cell of
    the star whose other vertices all rank below ``v`` maps to the cell it
    is paired with, or to None where it is critical.

    Cells are ordered by the ranks of their vertices other than ``v``,
    sorted descending and compared lexicographically (a prefix first).
    ``v`` pairs with its least lower edge, or is critical without one.
    Then, until every cell is taken: the least untaken cell with exactly
    one untaken facet in the lower star pairs with that facet; where there
    is none, the least untaken cell with no untaken facet is critical."""
    tets = cx.T[cx._row(cx.vt, v)]
    rv = rank[v]
    cells: Dict[Cell, tuple] = {}
    for t in tets:
        lower = sorted(int(x) for x in t if x != v and rank[x] < rv)
        for k in (1, 2, 3):
            for sub in itertools.combinations(lower, k):
                cell = tuple(sorted((v,) + sub))
                if cell not in cells:
                    cells[cell] = tuple(sorted((int(rank[x]) for x in sub),
                                               reverse=True))
    out: Dict[Cell, Optional[Cell]] = {}
    edges = [c for c in cells if len(c) == 2]
    if not edges:
        out[(v,)] = None
        return out
    first = min(edges, key=cells.__getitem__)
    out[(v,)] = first
    out[first] = (v,)
    taken = {first}

    def facets(c: Cell) -> List[Cell]:
        if len(c) == 2:
            return []
        return [f for f in itertools.combinations(c, len(c) - 1)
                if v in f and f in cells]

    todo = set(cells) - taken
    while todo:
        one = []
        zero = []
        for c in todo:
            free = [f for f in facets(c) if f not in taken]
            if len(free) == 1:
                one.append((cells[c], c, free[0]))
            elif not free:
                zero.append((cells[c], c))
        if one:
            _, c, f = min(one)
            out[c] = f
            out[f] = c
            taken.update((c, f))
            todo.difference_update((c, f))
        else:
            _, c = min(zero)
            out[c] = None
            taken.add(c)
            todo.discard(c)
    return out


def _firsts(cells: np.ndarray, owner: np.ndarray) -> np.ndarray:
    """The first of ``cells`` (ascending positions) of each owner."""
    if len(cells) == 0:
        return cells
    own = owner[cells]
    return cells[np.r_[True, own[1:] != own[:-1]]]


def _expand(offs: np.ndarray, vals: np.ndarray, idx: np.ndarray
            ) -> np.ndarray:
    """The CSR rows of ``idx``, concatenated."""
    lo, hi = offs[idx], offs[idx + 1]
    n = hi - lo
    start = np.repeat(lo - np.cumsum(n) + n, n)
    return vals[start + np.arange(n.sum())]


GRADIENT_FIELDS = ("pair_v2e", "pair_e2v", "pair_e2f", "pair_f2e",
                   "pair_f2t", "pair_t2f", "crit_v", "crit_e", "crit_f",
                   "crit_t")


def gradient(cx: Complex, rank: np.ndarray) -> Dict[str, np.ndarray]:
    """The pairing of :func:`lower_star_pairs` for every vertex at once.

    A cell lies in the lower star of its highest-ranked vertex. All stars
    run the same rule side by side: in each round every star with cells
    left takes one step (its least cell with one free facet pairs with
    that facet, or else its least cell with no free facet is critical).
    Returns the pairing on raw ids as arrays named like the program's
    gradient fields (``pair_v2e`` the edge a vertex is paired with, -1
    for none, ..., ``crit_t``), and ``owner_e``, ``owner_f``, ``owner_t``:
    the vertex whose lower star holds each cell."""
    rank = np.asarray(rank, np.int64)
    nv = cx.nv
    kinds = (cx.E, cx.F, cx.T)
    base = np.cumsum([0] + [len(r) for r in kinds])
    n = int(base[-1])
    owner = np.empty(n, np.int64)
    key = np.full((n, 3), -1, np.int64)
    facets = np.full((n, 3), -1, np.int64)
    for d, rows in enumerate(kinds):
        at = slice(base[d], base[d + 1])
        rk = rank[rows]
        by = np.argsort(-rk, axis=1)
        vs = np.take_along_axis(rows, by, 1)      # highest rank first
        owner[at] = vs[:, 0]
        key[at, :d + 1] = np.take_along_axis(rk, by, 1)[:, 1:]
        if d == 1:
            for j, o in enumerate((1, 2)):
                facets[at, j] = cx.edge_ids(vs[:, [0, o]])
        elif d == 2:
            for j, (a, b) in enumerate(((1, 2), (1, 3), (2, 3))):
                facets[at, j] = base[1] + cx.face_ids(vs[:, [0, a, b]])
    # positions: cells sorted by star, then by key within the star
    order = np.lexsort((key[:, 2], key[:, 1], key[:, 0], owner))
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    own = owner[order]
    dim = np.searchsorted(base, order, side="right") - 1
    fac = facets[order]
    fac = np.where(fac >= 0, pos[np.maximum(fac, 0)], -1)
    free = (fac >= 0).sum(1)
    has = fac.reshape(-1) >= 0
    co_offs, co_vals = _csr(fac.reshape(-1)[has],
                            np.repeat(np.arange(n), 3)[has], n)

    taken = np.zeros(n, bool)
    partner = np.full(n, -1, np.int64)
    crit = np.zeros(n, bool)

    def take(cells):
        taken[cells] = True
        np.add.at(free, _expand(co_offs, co_vals, cells), -1)

    first_e = _firsts(np.nonzero(dim == 0)[0], own)
    take(first_e)
    active = np.nonzero(~taken)[0]
    while len(active):
        one = _firsts(active[free[active] == 1], own)
        busy = np.zeros(nv, bool)
        busy[own[one]] = True
        zero = _firsts(active[(free[active] == 0) & ~busy[own[active]]],
                       own)
        if len(one) + len(zero) == 0:
            raise AssertionError("a lower star with no cell to take")
        fc = fac[one]
        ok = (fc >= 0) & ~taken[np.maximum(fc, 0)]
        f = fc[np.arange(len(one)), np.argmax(ok, 1)]
        partner[one] = f
        partner[f] = one
        crit[zero] = True
        take(np.concatenate([one, f, zero]))
        active = active[~taken[active]]

    kid = order - base[dim]                       # id within its kind
    out = {k: np.full(len(r), -1, np.int64)
           for k, r in (("pair_v2e", range(nv)), ("pair_e2v", cx.E),
                        ("pair_e2f", cx.E), ("pair_f2e", cx.F),
                        ("pair_f2t", cx.F), ("pair_t2f", cx.T))}
    out["pair_v2e"][own[first_e]] = kid[first_e]
    out["pair_e2v"][kid[first_e]] = own[first_e]
    up = np.nonzero(partner >= 0)[0]
    up = up[dim[partner[up]] == dim[up] - 1]         # the cofacet of a pair
    low = partner[up]
    for d, hi_name, lo_name in ((1, "pair_f2e", "pair_e2f"),
                                (2, "pair_t2f", "pair_f2t")):
        sel = dim[up] == d
        out[hi_name][kid[up[sel]]] = kid[low[sel]]
        out[lo_name][kid[low[sel]]] = kid[up[sel]]
    out["crit_v"] = np.ones(nv, bool)
    out["crit_v"][own[first_e]] = False
    for d, name in ((0, "crit_e"), (1, "crit_f"), (2, "crit_t")):
        c = np.zeros(len(kinds[d]), bool)
        sel = crit & (dim == d)
        c[kid[sel]] = True
        out[name] = c
        out["owner_" + name[-1]] = owner[base[d]:base[d + 1]]
    return out


# -- Morse-Smale ----------------------------------------------------------------

def follow(succ: np.ndarray) -> np.ndarray:
    """End of the path from every node under ``succ`` (a node that maps to
    itself ends its path); -2 for a node whose path does not end within
    ``len(succ)`` steps (a cycle)."""
    dest = succ.copy()
    active = np.nonzero(dest[dest] != dest)[0]
    for _ in range(len(succ)):
        if len(active) == 0:
            break
        dest[active] = succ[dest[active]]
        active = active[dest[dest[active]] != dest[active]]
    dest[active] = -2
    return dest


def morse_smale(cx: Complex, v_pair: np.ndarray, t_pair: np.ndarray,
                crit_e: np.ndarray, crit_f: np.ndarray,
                crit_t: np.ndarray) -> dict:
    """Morse-Smale destinations of a gradient given on the raw complex:
    ``v_pair[v]`` the edge vertex ``v`` is paired with (-1: critical),
    ``t_pair[t]`` the face tet ``t`` is paired with (-1: none), and the
    critical edge, face and tet masks.

    Each vertex descends along vertex -> paired edge -> its other vertex to
    a minimum (``dest_min``). Each tet ascends along tet -> paired face ->
    the other tet of that face to a maximum (``dest_max``, -1 where the
    path ends at a tet that is not critical, i.e. leaves through the
    boundary; -2 where a path never ends, which no program answer
    matches). Each critical edge ends at its two vertices' minima
    (``saddle1``: edge id -> (minimum of the lower-id vertex, of the
    other)); each critical face at its cofacets' maxima (``saddle2``: face
    id -> sorted pair, -1 for a missing cofacet)."""
    nv = cx.nv
    nt = len(cx.T)
    ev = cx.E[np.maximum(v_pair, 0)]
    other = np.where(ev[:, 0] == np.arange(nv), ev[:, 1], ev[:, 0])
    succ_v = np.where(v_pair >= 0, other, np.arange(nv))
    dest_min = follow(succ_v)
    offs, vals = cx.ft
    f = np.maximum(t_pair, 0)
    n_cof = offs[f + 1] - offs[f]
    c0 = vals[offs[f]]
    c1 = np.where(n_cof > 1, vals[np.minimum(offs[f] + 1, len(vals) - 1)],
                  -1)
    me = np.arange(nt)
    across = np.where(c0 == me, c1, c0)
    succ_t = np.where((t_pair >= 0) & (across >= 0), across, me)
    dest_t = follow(succ_t)
    dest_max = np.where(dest_t < 0, dest_t,
                        np.where(crit_t[dest_t], dest_t, -1))
    s1 = np.nonzero(crit_e)[0]
    saddle1 = {int(e): (int(dest_min[cx.E[e, 0]]), int(dest_min[cx.E[e, 1]]))
               for e in s1}
    saddle2 = {}
    for fi in np.nonzero(crit_f)[0]:
        cof = vals[offs[fi]:offs[fi + 1]]
        ends = sorted(int(dest_max[t]) for t in cof) + [-1] * (2 - len(cof))
        saddle2[int(fi)] = tuple(sorted(ends))
    return {"dest_min": dest_min, "dest_max": dest_max, "saddle1": saddle1,
            "saddle2": saddle2}
