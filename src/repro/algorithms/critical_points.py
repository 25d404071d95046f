"""Critical point extraction (paper §5.1 'CriticalPoints').

Classifies every vertex by the connectivity of its lower/upper link
(Banchoff [1]): a vertex is a minimum if its lower link is empty, a maximum
if its upper link is empty, regular if both lower and upper links are single
connected components, and a (multi-)saddle otherwise.

Consumes exactly the relations the paper lists for this algorithm: **VV**
(link vertices) and **VT** (link edges come from co-incident tets: two
neighbors of v are link-adjacent iff they share a tet with v).

TPU adaptation: per-vertex link connectivity is computed as transitive
closure by repeated boolean matrix squaring over (deg × deg) link adjacency
blocks — batch-parallel over vertices, MXU-friendly — instead of the
sequential union-find in TTK's CPU implementation.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.adjacency import complete_adjacency
from ..core.mesh import _FACE_COMBOS
from ..core.scheduler import run_partitioned, segment_batches
from ..kernels import ops
from . import consume

# type codes
REGULAR, MINIMUM, SADDLE1, SADDLE2, MAXIMUM, DEGENERATE = -1, 0, 1, 2, 3, 4


# contract: device-resident
@jax.jit
def _boundary_mask(M: jnp.ndarray,      # (nt, deg) completed TT, -1 pad
                   T: jnp.ndarray,      # (nt, 4) global TV
                   nv_one_hot: jnp.ndarray,  # (nv+1,) zeros — scatter target
                   ) -> jnp.ndarray:
    """Device boundary-vertex mask from completed TT: a face of tet ``t`` is
    interior iff some TT neighbour contains all three of its vertices (a tet
    containing a face's vertex triple shares that face); vertices of the
    remaining faces are boundary. Same faces/vertices as the host arm's
    ``boundary_TF`` id matching — bit-identical mask.

    Works on ``(deg, nt)`` slabs, one vertex column at a time: the long
    axis stays minor, so no ``(nt, deg, 4)`` intermediate pads its short
    minor dims out to full TPU lanes (23 GB of HBM at a million vertices).
    """
    Mt = M.T                                                      # (deg, nt)
    Mc = jnp.maximum(Mt, 0)
    tv = [T[:, c] for c in range(T.shape[1])]                     # (nt,) each
    nb = [col[Mc] for col in tv]                                  # (deg, nt)
    nv = nv_one_hot.shape[0] - 1
    out = nv_one_hot
    for combo in _FACE_COMBOS:
        has_face = Mt >= 0       # neighbour contains all 3 face vertices
        for v in combo:
            fv = tv[v][None, :]
            hit = nb[0] == fv
            for col in nb[1:]:
                hit = hit | (col == fv)
            has_face = has_face & hit
        interior = has_face.any(0)                                # (nt,)
        for v in combo:
            out = out.at[jnp.where(interior, nv, tv[v])].set(True)
    return out[:nv]


def boundary_vertices(ds, pre, batch: int = 4096,
                      consumer: str = "auto", workers: int = 1,
                      shards=None) -> np.ndarray:
    """Boolean mask of mesh-boundary vertices, via completed TT.

    A tet has one completed-TT neighbour per *interior* face, so a tet with
    fewer than 4 neighbours carries at least one boundary face; a face of
    such a tet is boundary iff no TT neighbour also contains it. Banchoff
    link classification is only exact for interior vertices, so callers use
    this mask to qualify critical points on the domain boundary.

    Requires a data structure with engine-native completion (a
    ``RelationEngine`` whose relation set includes TT); TT rows are requested
    in pipelined batches like every other relation. The device consumer arm
    (docs/DESIGN.md §6) keeps the completed rows on the accelerator and
    derives the mask in one fused jit; the host arm is the numpy reference.
    Both arms are bit-identical."""
    sm = pre.smesh
    consume.shard_plan(ds, shards)   # validate; completion follows the plan
    mask = np.zeros(sm.n_vertices, dtype=bool)
    if sm.n_tets == 0:
        return mask
    # the device arm also needs the device completion path (a block pool);
    # the explicit baseline has the batch API but completes through host
    if (consume.consumer_mode(ds, consumer) == "device"
            and hasattr(ds, "get_full_dev")):
        M, _ = complete_adjacency(ds, "TT", np.arange(sm.n_tets),
                                  batch=batch, path="device", out="dev",
                                  workers=workers)
        zeros = jnp.zeros(sm.n_vertices + 1, dtype=bool)
        return np.asarray(_boundary_mask(
            M, jnp.asarray(sm.tets.astype(np.int32)), zeros))
    M, L = complete_adjacency(ds, "TT", np.arange(sm.n_tets), batch=batch,
                              workers=workers)
    cand = np.nonzero(L < 4)[0]            # tets with >= 1 boundary face
    if len(cand) == 0:
        return mask
    Mc = M[cand]
    deg = Mc.shape[1]
    tf_t = ds.boundary_TF(cand)            # (c, 4) the candidates' faces
    tf_nb = ds.boundary_TF(np.maximum(Mc, 0).reshape(-1)) \
        .reshape(len(cand), deg, 4)        # (c, deg, 4) neighbours' faces
    shared = (tf_t[:, :, None, None] == tf_nb[:, None, :, :]).any(-1)
    interior = (shared & (Mc >= 0)[:, None, :]).any(-1)   # (c, 4)
    bf = tf_t[~interior]                   # boundary face ids
    mask[pre.F[bf].reshape(-1)] = True
    return mask


def total_order(scalars: np.ndarray) -> np.ndarray:
    """Injective vertex order (simulation of simplicity): rank under
    (scalar, index)."""
    n = len(scalars)
    order = np.lexsort((np.arange(n), np.asarray(scalars)))
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return rank


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("deg_v", "deg_t"))
def _classify_batch(
    vv_M: jnp.ndarray,    # (B, deg_v) neighbor global ids, -1 pad
    vt_M: jnp.ndarray,    # (B, deg_t) incident tet ids, -1 pad
    row_gid: jnp.ndarray, # (B,) vertex global ids
    tets: jnp.ndarray,    # (nt, 4) global TV
    rank: jnp.ndarray,    # (nv,) injective order
    deg_v: int, deg_t: int,
) -> jnp.ndarray:
    B = vv_M.shape[0]
    valid_n = vv_M >= 0
    r_v = rank[row_gid]                              # (B,)
    r_n = jnp.where(valid_n, rank[jnp.maximum(vv_M, 0)], 0)
    lower = valid_n & (r_n < r_v[:, None])           # (B, deg_v)
    upper = valid_n & ~lower

    # Link edges via shared tets: for each incident tet, the 3 vertices
    # other than v form a triangle in link(v).
    tv = jnp.where(vt_M[..., None] >= 0,
                   tets[jnp.maximum(vt_M, 0)], -1)   # (B, deg_t, 4)
    is_v = tv == row_gid[:, None, None]
    # compact the 3 non-v vertices per tet: sort puts v's slot last
    key = jnp.where(is_v | (tv < 0), jnp.iinfo(jnp.int32).max, tv)
    others = jnp.sort(key, axis=-1)[..., :3]          # (B, deg_t, 3)
    others = jnp.where(others == jnp.iinfo(jnp.int32).max, -1, others)

    # map neighbor global ids -> link positions (index into vv_M row)
    eq = others[..., None] == vv_M[:, None, None, :]  # (B,deg_t,3,deg_v)
    pos = jnp.argmax(eq, axis=-1)                     # (B, deg_t, 3)
    ok = eq.any(axis=-1)                              # padded/-1 -> False

    adj = jnp.zeros((B, deg_v, deg_v), dtype=bool)
    bidx = jnp.arange(B)[:, None]
    for a, b in ((0, 1), (0, 2), (1, 2)):
        pa, pb = pos[:, :, a], pos[:, :, b]           # (B, deg_t)
        good = ok[:, :, a] & ok[:, :, b]
        pa = jnp.where(good, pa, 0)
        pb = jnp.where(good, pb, 0)
        upd = good
        adj = adj.at[bidx, pa, pb].max(upd)
        adj = adj.at[bidx, pb, pa].max(upd)

    def n_components(mask):
        A = adj & mask[:, :, None] & mask[:, None, :]
        A = A | (jnp.eye(deg_v, dtype=bool)[None] & mask[:, :, None])
        # transitive closure by squaring
        n_iter = max(1, int(np.ceil(np.log2(deg_v))))
        for _ in range(n_iter):
            Af = A.astype(jnp.float32)
            A = A | (jnp.einsum("bij,bjk->bik", Af, Af,
                                preferred_element_type=jnp.float32) > 0)
        root = jnp.argmax(A, axis=-1)                 # first reachable = min id
        iota = jnp.arange(deg_v)[None, :]
        return (mask & (root == iota)).sum(axis=-1)   # #components

    nl = n_components(lower)
    nu = n_components(upper)

    t = jnp.full((B,), REGULAR, dtype=jnp.int32)
    t = jnp.where((nl >= 2) & (nu >= 2), DEGENERATE, t)
    t = jnp.where((nl >= 2) & (nu <= 1), SADDLE1, t)
    t = jnp.where((nl <= 1) & (nu >= 2), SADDLE2, t)
    t = jnp.where(nl == 0, MINIMUM, t)
    t = jnp.where(nu == 0, MAXIMUM, t)
    # an isolated vertex (empty link: no lower AND no upper component) has
    # no Banchoff classification — flag DEGENERATE, never MAXIMUM, matching
    # fused_extrema's has_nbr exclusion (core/pipeline.py)
    t = jnp.where((nl == 0) & (nu == 0), DEGENERATE, t)
    return t


def critical_points(
    ds,                      # RelationEngine / ExplicitTriangulation / ...
    pre,
    rank: np.ndarray,
    batch_segments: int = 8,
    lookahead_hint: bool = True,
    flag_boundary: bool = False,
    consumer: str = "auto",
    workers: int = 1,
    shards=None,
) -> Tuple[np.ndarray, Dict[str, int]]:
    """Run the algorithm over all segments through data structure ``ds``.

    The traversal is the paper's embarrassingly-parallel vertex sweep: for
    each batch of segments the consumer requests VV and VT blocks (the
    producer precomputes ahead via the engine's lookahead) and classifies the
    batch on-device.

    ``consumer`` selects the consumer arm (docs/DESIGN.md §6): ``"device"``
    feeds :func:`_classify_batch` straight from the engine's device block
    pool (one :meth:`get_full_dev_many` batch per step — zero host block
    reads, columns trimmed to the exact per-mesh degree bounds), ``"host"``
    is the PR-3 numpy-assembly path, and ``"auto"`` picks "device" whenever
    ``ds`` exposes the batch API. Results are bit-identical either way.

    ``workers`` is the consumer-thread count (docs/DESIGN.md §8): the
    segment-batch stream is partitioned across ``workers`` CPU threads by
    the scheduler (``core/scheduler.py``), each running the selected
    consumer arm with its own depth-1 double buffer; per-batch
    classifications are reduced in segment order, so the result is
    bit-identical for any worker count.

    With ``flag_boundary=True`` (requires a data structure with TT
    completion, see :func:`boundary_vertices`) the counts gain a
    ``boundary_critical`` entry: non-regular vertices lying on the domain
    boundary, where the interior link classification is only approximate.

    ``shards`` validates against the data structure's
    :class:`~repro.distributed.sharding.ShardPlan` (sharding is fixed at
    engine construction); on a sharded engine the batch stream aligns to
    shard boundaries and workers partition shard-affinely, both of which
    preserve bit-identity (docs/DESIGN.md §9)."""
    sm = pre.smesh
    ns = sm.n_segments
    mode = consume.consumer_mode(ds, consumer)
    plan = consume.shard_plan(ds, shards)
    tets_dev = jnp.asarray(sm.tets.astype(np.int32))
    rank_dev = jnp.asarray(rank)
    types = np.empty(sm.n_vertices, dtype=np.int32)
    cols = consume.degree_cols(pre, ("VV", "VT")) if mode == "device" else None

    batches = segment_batches(ns, batch_segments, plan)
    shard_of = ((lambda i: plan.shard_of(batches[i][0]))
                if plan is not None else None)

    prefetch = None
    if lookahead_hint and hasattr(ds, "prefetch"):
        # dispatched for the worker's NEXT batch before it consumes the
        # current one, so the kernels execute behind the classification
        # (double-buffering through the engine's in-flight futures table)
        def prefetch(segs):
            if hasattr(ds, "prefetch_many"):
                ds.prefetch_many({"VV": segs, "VT": segs})
            else:
                for R in ("VV", "VT"):
                    ds.prefetch(R, segs)

    if mode == "device":
        # device-resident arm: blocks go pool -> fused classify jit with
        # no host copy; batch k's types download only after batch k+1
        # is dispatched (the scheduler's per-worker depth-1 double buffer),
        # hiding the host edge behind device compute without retaining
        # O(mesh) device arrays
        def consume_batch(i, segs):
            cb = ds.get_full_dev_many(("VV", "VT"), segs, cols=cols)
            t = _classify_batch(cb.M["VV"], cb.M["VT"], cb.gid_dev,
                                tets_dev, rank_dev,
                                deg_v=cb.width("VV"), deg_t=cb.width("VT"))
            return cb.gid, cb.n_rows, t
    else:
        def consume_batch(i, segs):
            vv = ds.get_batch("VV", segs) if hasattr(ds, "get_batch") else [
                ds.get("VV", s) for s in segs]
            vt = ds.get_batch("VT", segs) if hasattr(ds, "get_batch") else [
                ds.get("VT", s) for s in segs]
            deg_v = -32 * (-max(M.shape[1] for M, _ in vv) // 32)
            deg_t = -32 * (-max(M.shape[1] for M, _ in vt) // 32)

            rows = sum(M.shape[0] for M, _ in vv)
            rows_pad = ops.bucket_rows(rows)  # stable shapes, ragged tails
            vvM = np.full((rows_pad, deg_v), -1, dtype=np.int32)
            vtM = np.full((rows_pad, deg_t), -1, dtype=np.int32)
            gid = np.full(rows_pad, -1, dtype=np.int32)
            at = 0
            for s, (Mv, _), (Mt, _) in zip(segs, vv, vt):
                n = Mv.shape[0]
                vvM[at:at + n, :Mv.shape[1]] = Mv
                vtM[at:at + n, :Mt.shape[1]] = Mt
                gid[at:at + n] = np.arange(sm.I_V[s], sm.I_V[s] + n)
                at += n
            t = _classify_batch(jnp.asarray(vvM), jnp.asarray(vtM),
                                jnp.asarray(gid), tets_dev, rank_dev,
                                deg_v=deg_v, deg_t=deg_t)
            return gid[:rows], rows, t

    def finalize(inter):
        gid, n, t = inter
        return gid, np.asarray(t)[:n]

    def reduce_batch(i, res):
        gid, t = res
        types[gid] = t

    run_partitioned(batches, consume_batch, reduce_batch, workers=workers,
                    finalize=finalize, prefetch=prefetch, scope=ds,
                    name="critical_points", shard_of=shard_of)

    counts = {
        "minima": int((types == MINIMUM).sum()),
        "saddles1": int((types == SADDLE1).sum()),
        "saddles2": int((types == SADDLE2).sum()),
        "maxima": int((types == MAXIMUM).sum()),
        "degenerate": int((types == DEGENERATE).sum()),
        "regular": int((types == REGULAR).sum()),
    }
    if flag_boundary:
        on_bd = boundary_vertices(ds, pre, consumer=consumer,
                                  workers=workers, shards=shards)
        counts["boundary_critical"] = int((on_bd & (types != REGULAR)).sum())
    return types, counts
