"""Discrete gradient field via lower-star processing (Robins et al. [37],
the paper's 'DiscreteGradient' benchmark algorithm).

Every simplex belongs to exactly one lower star (that of its highest vertex
under the injective order), so vertices are processed independently — the
paper calls this embarrassingly parallel. Consumes the relations the paper
lists: coboundary **VE, VF, VT** through the data structure (offloaded) and
boundary **EV, FV, TV** (+FE/TF implicitly via slot matching) locally.

TPU adaptation: TTK's per-vertex priority-queue loop (PQzero/PQone) is kept
*algorithmically identical* but executed as a batch of independent state
machines inside one `lax.while_loop` — each iteration performs one PQ
operation for every vertex in the batch simultaneously. Keys are packed into
int64 so the mixed-dimension lexicographic order (desc-sorted vertex ranks)
reduces to integer argmin.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.adjacency import complete_adjacency
from ..core.scheduler import run_partitioned, segment_batches
from ..core.spans import spanned
from ..kernels import ops
from . import consume

_BIG = np.iinfo(np.int32).max


@dataclasses.dataclass
class GradientField:
    """Global discrete gradient: pair arrows point facet -> cofacet."""
    pair_v2e: np.ndarray   # (nv,) edge gid paired with vertex, -1 if none
    pair_e2f: np.ndarray   # (ne,) face gid the edge points to, -1
    pair_f2t: np.ndarray   # (nf,) tet gid the face points to, -1
    # reverse maps (cofacet -> facet), derived, for path tracing
    pair_e2v: np.ndarray   # (ne,) vertex gid the edge is head of, -1
    pair_f2e: np.ndarray   # (nf,)
    pair_t2f: np.ndarray   # (nt,)
    crit_v: np.ndarray     # (nv,) bool
    crit_e: np.ndarray
    crit_f: np.ndarray
    crit_t: np.ndarray

    def counts(self) -> Dict[str, int]:
        return {"crit_v": int(self.crit_v.sum()),
                "crit_e": int(self.crit_e.sum()),
                "crit_f": int(self.crit_f.sum()),
                "crit_t": int(self.crit_t.sum())}

    def euler(self) -> int:
        c = self.counts()
        return c["crit_v"] - c["crit_e"] + c["crit_f"] - c["crit_t"]


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("de", "df", "dt"))
def _lower_star_batch(
    ve_M, vf_M, vt_M,            # (B, de/df/dt) coboundary gids, -1 pad
    row_gid,                     # (B,) vertex gids
    E, F, T,                     # global boundary tables (device)
    rank,                        # (nv,) injective order
    de: int, df: int, dt: int,
):
    B = ve_M.shape[0]
    r_v = rank[row_gid]

    # --- lower-star membership & "others" ----------------------------------
    ev = jnp.where(ve_M[..., None] >= 0, E[jnp.maximum(ve_M, 0)], -1)  # (B,de,2)
    e_other = jnp.where(ev[..., 0] == row_gid[:, None], ev[..., 1], ev[..., 0])
    e_ok = (ve_M >= 0) & (rank[jnp.maximum(e_other, 0)] < r_v[:, None])

    fv = jnp.where(vf_M[..., None] >= 0, F[jnp.maximum(vf_M, 0)], -1)  # (B,df,3)
    big = jnp.iinfo(jnp.int32).max

    def others(sv, gid, keep):  # drop v's slot, keep ascending others
        key = jnp.where((sv == gid[:, None, None]) | (sv < 0), big, sv)
        o = jnp.sort(key, axis=-1)[..., :keep]
        return jnp.where(o == big, -1, o)

    f_oth = others(fv, row_gid, 2)                                  # (B,df,2)
    f_lower = (rank[jnp.maximum(f_oth, 0)] < r_v[:, None, None]) & (f_oth >= 0)
    f_ok = (vf_M >= 0) & f_lower.all(-1)

    tv = jnp.where(vt_M[..., None] >= 0, T[jnp.maximum(vt_M, 0)], -1)  # (B,dt,4)
    t_oth = others(tv, row_gid, 3)                                  # (B,dt,3)
    t_lower = (rank[jnp.maximum(t_oth, 0)] < r_v[:, None, None]) & (t_oth >= 0)
    t_ok = (vt_M >= 0) & t_lower.all(-1)

    # --- facet slot matching ------------------------------------------------
    # face (v,a,b): facets in lower star = edge slots with other == a / b
    def match_edge(target):  # target (B, df) global vid -> edge slot or -1
        eq = (e_other[:, None, :] == target[..., None]) & e_ok[:, None, :]
        return jnp.where(eq.any(-1), jnp.argmax(eq, -1), -1)

    f_fac = jnp.stack([match_edge(f_oth[..., 0]),
                       match_edge(f_oth[..., 1]),
                       jnp.full((B, df), -1, jnp.int32)], axis=-1)

    # tet (v,a,b,c): facets = face slots with others == each sorted pair
    def match_face(pa, pb):  # (B, dt) -> face slot
        eq = ((f_oth[:, None, :, 0] == pa[..., None])
              & (f_oth[:, None, :, 1] == pb[..., None])
              & f_ok[:, None, :])
        return jnp.where(eq.any(-1), jnp.argmax(eq, -1) + de, -1)

    a, b, c = t_oth[..., 0], t_oth[..., 1], t_oth[..., 2]
    t_fac = jnp.stack([match_face(a, b), match_face(a, c), match_face(b, c)],
                      axis=-1)

    # --- unified slot arrays: [edges | faces | tets] ------------------------
    N = de + df + dt
    exists = jnp.concatenate([e_ok, f_ok, t_ok], axis=1)
    # facet slots (absolute), -1 pad; faces offset 0 (edges), tets offset de
    fac = jnp.concatenate([
        jnp.full((B, de, 3), -1, jnp.int32), f_fac, t_fac], axis=1)

    # --- Robins keys: lexicographic on desc-sorted vertex ranks -------------
    # Packed 64-bit keys overflow without x64, so compute a *local* dense
    # rank per lower star via an (N x N) pairwise comparison — N <= ~200.
    re_ = rank[jnp.maximum(e_other, 0)] + 1
    rf = jnp.sort(rank[jnp.maximum(f_oth, 0)] + 1, axis=-1)   # asc: (lo, hi)
    rt = jnp.sort(rank[jnp.maximum(t_oth, 0)] + 1, axis=-1)
    zed = jnp.zeros((B, de), jnp.int32)
    k1 = jnp.concatenate([re_, rf[..., 1], rt[..., 2]], axis=1)
    k2 = jnp.concatenate([zed, rf[..., 0], rt[..., 1]], axis=1)
    k3 = jnp.concatenate([zed, jnp.zeros((B, df), jnp.int32), rt[..., 0]],
                         axis=1)
    big32 = jnp.iinfo(jnp.int32).max
    k1 = jnp.where(exists, k1, big32)
    k2 = jnp.where(exists, k2, big32)
    k3 = jnp.where(exists, k3, big32)

    def lt(i_, j_):  # key_j < key_i elementwise over (B, N, N)
        a1, b1 = k1[:, :, None], k1[:, None, :]
        a2, b2 = k2[:, :, None], k2[:, None, :]
        a3, b3 = k3[:, :, None], k3[:, None, :]
        return ((b1 < a1)
                | ((b1 == a1) & (b2 < a2))
                | ((b1 == a1) & (b2 == a2) & (b3 < a3)))

    key = lt(None, None).sum(-1).astype(jnp.int32)   # local dense rank
    key = jnp.where(exists, key, big32)
    key_e = jnp.where(e_ok, key[:, :de], big32)

    # --- init: pair v with its minimal lower edge ---------------------------
    has_edge = e_ok.any(-1)
    min_e = jnp.argmin(jnp.where(e_ok, key_e, _BIG), axis=-1)
    crit_vertex = ~has_edge
    processed0 = jnp.zeros((B, N), bool)
    processed0 = processed0.at[jnp.arange(B), min_e].max(has_edge)
    pair0 = jnp.full((B, N), -1, jnp.int32)   # slot paired with (absolute)
    pair0 = pair0.at[jnp.arange(B), min_e].set(
        jnp.where(has_edge, -2, -1))          # -2 == paired with the vertex
    crit0 = jnp.zeros((B, N), bool)

    def facet_unprocessed(processed, slots):   # (B,N,3) -> counts + argpick
        ok = slots >= 0
        p = jnp.take_along_axis(
            processed, jnp.maximum(slots, 0).reshape(B, -1), axis=1
        ).reshape(B, N, 3)
        un = ok & ~p
        return un.sum(-1), un

    def body(state):
        processed, pair, crit, _ = state
        avail = exists & ~processed
        cnt, un = facet_unprocessed(processed, fac)
        pq1 = avail & (cnt == 1)
        pq0 = avail & (cnt == 0)

        k1 = jnp.where(pq1, key, _BIG)
        k0 = jnp.where(pq0, key, _BIG)
        a1 = jnp.argmin(k1, axis=-1)
        a0 = jnp.argmin(k0, axis=-1)
        use1 = pq1.any(-1)
        use0 = ~use1 & pq0.any(-1)
        rows = jnp.arange(B)

        # pair α (cofacet) with its single unprocessed facet β
        un_a = un[rows, a1]                      # (B, 3)
        beta = fac[rows, a1, jnp.argmax(un_a, -1)]
        processed = processed.at[rows, a1].max(use1)
        processed = processed.at[rows, jnp.maximum(beta, 0)].max(use1)
        pair = pair.at[rows, a1].set(
            jnp.where(use1, beta, pair[rows, a1]))
        pair = pair.at[rows, jnp.maximum(beta, 0)].set(
            jnp.where(use1, a1, pair[rows, jnp.maximum(beta, 0)]))
        # or: pop PQzero as critical
        processed = processed.at[rows, a0].max(use0)
        crit = crit.at[rows, a0].max(use0)
        return processed, pair, crit, (use1 | use0).any()

    def cond(state):
        return state[3]

    processed, pair, crit, _ = jax.lax.while_loop(
        cond, body, (processed0, pair0, crit0, jnp.array(True)))

    return crit_vertex, min_e, has_edge, pair, crit, exists


def audit_gradient(ds, pre, grad: GradientField,
                   batch: int = 4096, workers: int = 1,
                   shards=None) -> Dict[str, int]:
    """Cross-segment audit of the discrete vector field's matching property.

    Lower stars partition the simplices, so pairing decisions made in
    different segments can never claim the same cell — this audit verifies
    that global invariant across segment boundaries using completed
    adjacency (``core/adjacency.py``), requested in pipelined batches:

    - ``tt_conflicts``: for every face->tet pair ``f -> t``, the *other*
      cofacet of ``f`` (t's completed-TT neighbour across ``f``) must not
      also be paired to ``f``.
    - ``ff_conflicts``: for every edge->face pair ``e -> f``, no other face
      containing ``e`` (an FF neighbour of ``f`` through ``e``) may claim
      ``e`` as its paired edge.
    - ``reverse_mismatch``: forward/reverse pair arrays must agree.

    Requires a data structure with engine-native completion for TT and FF.
    All counts are zero for a valid field."""
    consume.shard_plan(ds, shards)   # validate; completion follows ds's plan
    out = {"tt_conflicts": 0, "ff_conflicts": 0, "reverse_mismatch": 0}
    f_paired = np.nonzero(grad.pair_f2t >= 0)[0]
    out["reverse_mismatch"] += int(
        (grad.pair_t2f[grad.pair_f2t[f_paired]] != f_paired).sum())
    e_paired = np.nonzero(grad.pair_e2f >= 0)[0]
    out["reverse_mismatch"] += int(
        (grad.pair_f2e[grad.pair_e2f[e_paired]] != e_paired).sum())

    if len(f_paired):
        t = grad.pair_f2t[f_paired]
        M, _ = complete_adjacency(ds, "TT", t, batch=batch, workers=workers)
        deg = M.shape[1]
        tf_nb = ds.boundary_TF(np.maximum(M, 0).reshape(-1)) \
            .reshape(len(t), deg, 4)
        across = (tf_nb == f_paired[:, None, None]).any(-1) & (M >= 0)
        nb = np.where(across, M, -1)
        claimed = (nb >= 0) & (grad.pair_t2f[np.maximum(nb, 0)]
                               == f_paired[:, None])
        out["tt_conflicts"] = int(claimed.any(-1).sum())
    if len(e_paired):
        fh = grad.pair_e2f[e_paired]
        M, _ = complete_adjacency(ds, "FF", fh, batch=batch, workers=workers)
        deg = M.shape[1]
        fe_nb = ds.boundary_FE(np.maximum(M, 0).reshape(-1)) \
            .reshape(len(fh), deg, 3)
        through_e = (fe_nb == e_paired[:, None, None]).any(-1) & (M >= 0)
        nb = np.where(through_e, M, -1)
        claimed = (nb >= 0) & (grad.pair_f2e[np.maximum(nb, 0)]
                               == e_paired[:, None])
        out["ff_conflicts"] = int(claimed.any(-1).sum())
    return out


def _scatter_batch(g: GradientField, gid, veM, vfM, vtM,
                   crit_vx, min_e, has_edge, pair, crit,
                   de: int, df: int, dt: int) -> None:
    """Integrate one classified batch into the global gradient field (host
    numpy — the pipeline's final-assembly edge, shared bit-identically by
    the device and host consumer arms). All inputs are host arrays already
    sliced to the batch's real rows."""
    g.crit_v[gid] = crit_vx
    # v -> min edge arrows
    e_gid = np.take_along_axis(veM, min_e[:, None], 1)[:, 0]
    sel = has_edge
    g.pair_v2e[gid[sel]] = e_gid[sel]
    g.pair_e2v[e_gid[sel]] = gid[sel]
    # slot-level pairs/criticals
    slot_gid = np.concatenate([veM, vfM, vtM], axis=1)  # (B, N)
    crit_e_rows = crit[:, :de] & (veM >= 0)
    crit_f_rows = crit[:, de:de + df] & (vfM >= 0)
    crit_t_rows = crit[:, de + df:] & (vtM >= 0)
    g.crit_e[veM[crit_e_rows]] = True
    g.crit_f[vfM[crit_f_rows]] = True
    g.crit_t[vtM[crit_t_rows]] = True
    # face->edge pairs live in slots [de, de+df); a face slot's pair
    # value >= de means it was paired as the *facet of a tet* (recorded
    # via the tet side below), so only values < de are edge pairings.
    fslots = pair[:, de:de + df]
    selF = (fslots >= 0) & (fslots < de) & (vfM >= 0)
    if selF.any():
        rowsF, colsF = np.nonzero(selF)
        e_of = slot_gid[rowsF, fslots[rowsF, colsF]]
        f_of = vfM[rowsF, colsF]
        g.pair_e2f[e_of] = f_of
        g.pair_f2e[f_of] = e_of
    tslots = pair[:, de + df:]
    selT = (tslots >= 0) & (vtM >= 0)
    if selT.any():
        rowsT, colsT = np.nonzero(selT)
        f_of = slot_gid[rowsT, tslots[rowsT, colsT]]
        t_of = vtM[rowsT, colsT]
        g.pair_f2t[f_of] = t_of
        g.pair_t2f[t_of] = f_of


def _download_device_batch(cb, degs, out):
    """Download one device batch's results into the
    :func:`_scatter_batch` argument tuple (the device arm's host edge —
    the scheduler's finalize step); releasing ``cb`` afterwards frees its
    device buffers, so each worker retains at most one batch."""
    de, df, dt = degs
    crit_vx, min_e, has_edge, pair, crit, _ = out
    n = cb.n_rows
    return (cb.gid,
            np.asarray(cb.M["VE"])[:n], np.asarray(cb.M["VF"])[:n],
            np.asarray(cb.M["VT"])[:n],
            np.asarray(crit_vx)[:n], np.asarray(min_e)[:n],
            np.asarray(has_edge)[:n], np.asarray(pair)[:n],
            np.asarray(crit)[:n], de, df, dt)


@spanned("driver.discrete_gradient")
def discrete_gradient(
    ds, pre, rank: np.ndarray, batch_segments: int = 8,
    audit: bool = False, consumer: str = "auto",
    co_prefetch: Tuple[str, ...] = (),
    workers: int = 1, shards=None,
) -> GradientField:
    """Drive the lower-star batches through the data structure (GALE queues
    VE/VF/VT — the paper's 3-queue configuration for this algorithm).

    ``consumer`` selects the consumer arm (docs/DESIGN.md §6): ``"device"``
    feeds :func:`_lower_star_batch` straight from the engine's device block
    pool via :meth:`get_full_dev_many` (zero host block reads, columns at
    the exact per-mesh degree bounds), ``"host"`` is the PR-3
    numpy-assembly path, ``"auto"`` picks "device" whenever ``ds`` exposes
    the batch API. Bit-identical either way.

    ``workers`` is the consumer-thread count (docs/DESIGN.md §8): the
    scheduler partitions the segment-batch stream across that many CPU
    threads, each running the selected arm with its own depth-1 double
    buffer; per-batch results are scattered in segment order on the calling
    thread, so the field is bit-identical for any worker count (lower stars
    partition the simplices, so batch scatters never overlap).

    ``co_prefetch`` names extra engine relations to dispatch alongside each
    batch's VE/VF/VT prefetch (the paper's multi-queue proactive
    precompute): a driver that will consume e.g. completed TT right after
    the gradient (``morse_smale``) passes ``("TT",)`` so those kernels
    execute behind the lower-star state machines instead of serializing
    after them. Relations the data structure does not serve are ignored.

    ``shards`` follows the engine's :class:`ShardPlan` (docs/DESIGN.md §9):
    segment batches restart at shard boundaries and workers are assigned
    shard-affinely, so each worker drives one shard's device pipeline. The
    field stays bit-identical for any shard count.

    With ``audit=True`` (requires engine-native TT/FF completion, see
    :func:`audit_gradient`) the finished field is checked for cross-segment
    matching conflicts and a failure raises ``ValueError``."""
    sm = pre.smesh
    nv, nt = sm.n_vertices, sm.n_tets
    ne, nf = pre.n_edges, pre.n_faces
    mode = consume.consumer_mode(ds, consumer)
    E_dev = jnp.asarray(pre.E.astype(np.int32))
    F_dev = jnp.asarray(pre.F.astype(np.int32))
    T_dev = jnp.asarray(sm.tets.astype(np.int32))
    rank_dev = jnp.asarray(rank)
    rels = ("VE", "VF", "VT")
    cols = consume.degree_cols(pre, rels) if mode == "device" else None

    g = GradientField(
        pair_v2e=np.full(nv, -1, np.int64), pair_e2f=np.full(ne, -1, np.int64),
        pair_f2t=np.full(nf, -1, np.int64), pair_e2v=np.full(ne, -1, np.int64),
        pair_f2e=np.full(nf, -1, np.int64), pair_t2f=np.full(nt, -1, np.int64),
        crit_v=np.zeros(nv, bool), crit_e=np.zeros(ne, bool),
        crit_f=np.zeros(nf, bool), crit_t=np.zeros(nt, bool))

    ns = sm.n_segments
    extra = tuple(r for r in co_prefetch
                  if r in getattr(ds, "relations", co_prefetch))
    plan = consume.shard_plan(ds, shards)
    batches = segment_batches(ns, batch_segments, plan)
    shard_of = ((lambda i: plan.shard_of(batches[i][0]))
                if plan is not None else None)

    prefetch = None
    if hasattr(ds, "prefetch"):
        # dispatched for the worker's next batch before it consumes the
        # current one: VE/VF/VT production (three kernels in flight
        # round-robin — the paper's 3-queue config) plus any co_prefetch
        # relations a later consumer will need, all overlapping the
        # lower-star state machines below
        def prefetch(segs):
            if hasattr(ds, "prefetch_many"):
                ds.prefetch_many({R: segs for R in rels + extra})
            else:
                for R in rels + extra:
                    ds.prefetch(R, segs)

    if mode == "device":
        # device-resident arm: blocks go pool -> fused lower-star jit;
        # batch k's downloads happen only after batch k+1 is dispatched
        # (the scheduler's per-worker depth-1 double buffer), so the host
        # edge hides behind device compute without retaining O(mesh)
        # device arrays
        def consume_batch(i, segs):
            cb = ds.get_full_dev_many(rels, segs, cols=cols)
            de, df, dt = (cb.width(R) for R in rels)
            out = _lower_star_batch(
                cb.M["VE"], cb.M["VF"], cb.M["VT"], cb.gid_dev,
                E_dev, F_dev, T_dev, rank_dev, de=de, df=df, dt=dt)
            return cb, (de, df, dt), out

        def finalize(inter):
            return _download_device_batch(*inter)
    else:
        def consume_batch(i, segs):
            blocks = {R: ds.get_batch(R, segs) for R in rels}
            degs = {R: -32 * (-max(M.shape[1] for M, _ in blocks[R]) // 32)
                    for R in blocks}
            rows = sum(M.shape[0] for M, _ in blocks["VE"])
            rows_pad = ops.bucket_rows(rows)  # stable shapes, ragged tails
            stacked = {R: np.full((rows_pad, degs[R]), -1, np.int32)
                       for R in blocks}
            gid = np.full(rows_pad, -1, dtype=np.int32)
            at = 0
            for i_s, s in enumerate(segs):
                n = blocks["VE"][i_s][0].shape[0]
                for R in blocks:
                    M = blocks[R][i_s][0]
                    stacked[R][at:at + n, :M.shape[1]] = M
                gid[at:at + n] = np.arange(sm.I_V[s], sm.I_V[s] + n)
                at += n
            out = _lower_star_batch(
                jnp.asarray(stacked["VE"]), jnp.asarray(stacked["VF"]),
                jnp.asarray(stacked["VT"]), jnp.asarray(gid),
                E_dev, F_dev, T_dev, rank_dev,
                de=degs["VE"], df=degs["VF"], dt=degs["VT"])
            return gid, rows, stacked, degs, out

        def finalize(inter):
            gid, rows, stacked, degs, out = inter
            crit_vx, min_e, has_edge, pair, crit, _ = out
            return (gid[:rows], stacked["VE"][:rows], stacked["VF"][:rows],
                    stacked["VT"][:rows],
                    np.asarray(crit_vx)[:rows], np.asarray(min_e)[:rows],
                    np.asarray(has_edge)[:rows], np.asarray(pair)[:rows],
                    np.asarray(crit)[:rows],
                    degs["VE"], degs["VF"], degs["VT"])

    def reduce_batch(i, args):
        _scatter_batch(g, *args)

    run_partitioned(batches, consume_batch, reduce_batch, workers=workers,
                    finalize=finalize, prefetch=prefetch, scope=ds,
                    name="discrete_gradient", shard_of=shard_of)
    if audit:
        report = audit_gradient(ds, pre, g, workers=workers, shards=shards)
        if any(report.values()):
            raise ValueError(f"gradient matching audit failed: {report}")
    return g
