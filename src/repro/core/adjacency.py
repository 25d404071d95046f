"""Cross-segment completion of adjacency relations (EE / FF / TT).

A segment-local kernel sees only the segment's internal+external tets, so an
adjacency row for simplex sigma can miss neighbours that share only the
sub-simplex *not* containing the owner segment's vertex (docs/DESIGN.md §5).
The complete answer is the union of sigma's row over the owner segments of
each of its boundary (k-1)-faces — every neighbour shares one of those faces,
and both simplices contain that face's minimum vertex, hence appear in that
owner's local tables.

This module assembles that union through the engine as a batched pipeline
with a plan/execute split:

  - :func:`plan_completion` vectorizes the boundary-face -> owner-segment
    fan-out for the whole query batch, resolves every (segment, query) pair
    to a local block row through the inverse maps built at table time
    (``SegmentTables.inverse`` — no per-query table scans), and issues ONE
    :meth:`RelationEngine.prefetch_many` for every block the batch needs, so
    production overlaps with whatever the consumer does next.
  - :func:`execute_completion_device` — the GALE path — keeps the gather on
    the accelerator: it stacks the consulted blocks from the engine's device
    block pool (:meth:`RelationEngine.get_full_dev`), re-resolves every
    (segment, gid) pair to its row by batched binary search over the DEVICE
    inverse maps, and unions/dedups/compacts on device
    (``kernels/completion_gather.py``) — ONE host round trip per chunk.
  - :func:`execute_completion` is the host reference: one
    :meth:`RelationEngine.get_full` per distinct segment, union as
    vectorized numpy ops. Kept for the A/B benchmark and for data
    structures without a device pool (e.g. the explicit baseline).

:func:`complete_adjacency` drives plan + execute in pipelined chunks
(chunk k+1's blocks are prefetched before chunk k executes), which is how
the algorithm drivers request completed adjacency. ``path=`` selects the
execute arm ("device" by default on engines exposing ``get_full_dev``,
"host" otherwise). The device arm sizes its chunks from the shape of the
work (:func:`device_chunk`): the largest power of two of queries whose
candidate matrix, queries x boundary faces x ``deg[relation]`` i32 entries,
fits :data:`CHUNK_ENTRIES` — 8,192 TT, 2,048 EE and 1,024 FF queries at
the default widths. Every chunk pays a fixed set of host round trips
(pool assembly, pair uploads, the width check's sync), so the chunk is as
large as the device's transient budget allows. An explicit ``batch=``
overrides the rule; the host arm chunks only by ``batch=``. The device arm
releases each block from the engine's device pool after the last chunk
that consults it (:meth:`RelationEngine.release_dev`). All arms are
bit-identical for any chunking. Completion work is accounted in
``EngineStats`` (``completion_chunks``, ``completion_queries``,
``completion_fanout_blocks``, ``completion_raw_neighbors`` /
``completion_neighbors`` and the derived ``completion_dedup_ratio``), and
traced as ``completion.*`` spans (``core/spans.py``): the whole call, each
chunk's plan, each execute, and each execute's first wait on the device
(``completion.width_check``).

:func:`complete_adjacency_scalar` is the one-simplex-at-a-time reference kept
for the A/B benchmark (``benchmarks/bench_adjacency.py``) and the
bit-identical regression test.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .engine import RelationEngine, RelationWidthError
from .spans import span, spanned

ADJ_COMPLETION_RELATIONS = ("EE", "FF", "TT")


@dataclasses.dataclass
class CompletionPlan:
    """Resolved fan-out of one completion batch: which block rows to union.

    ``pair_*`` arrays describe the deduplicated (query, segment) pairs, each
    carrying the query simplex's local row inside that segment's full block.
    """

    relation: str
    ids: np.ndarray         # (n,) i64 query global ids
    pair_query: np.ndarray  # (P,) i64 index into ids
    pair_seg: np.ndarray    # (P,) i64 segment whose block is consulted
    pair_row: np.ndarray    # (P,) i32 row of the query in that full block
    segments: np.ndarray    # distinct consulted segments, ascending


def _boundary_owner_segments(eng: RelationEngine, relation: str,
                             ids: np.ndarray) -> np.ndarray:
    """Owner segments of each query's boundary (k-1)-faces: (n, k+1)."""
    kind = relation[0]
    pre = eng.pre
    if kind == "E":
        verts = pre.E[ids]                            # (n, 2) vertices
        return pre.smesh.seg_of_vertex[verts].astype(np.int64)
    if kind == "F":
        fe = eng.boundary_FE(ids)                     # (n, 3) edge ids
        return pre.owner_segment("E", fe).astype(np.int64)
    tf = eng.boundary_TF(ids)                         # (n, 4) face ids
    return pre.owner_segment("F", tf).astype(np.int64)


@spanned("completion.plan")
def plan_completion(eng: RelationEngine, relation: str,
                    ids: Sequence[int], prefetch: bool = True
                    ) -> CompletionPlan:
    """Vectorized fan-out planning for a whole query batch.

    Dedups the (query, owner-segment) pairs, resolves each pair's local block
    row via the inverse maps, and (by default) prefetches every distinct
    ``(relation, segment)`` block in one non-blocking ``prefetch_many`` so
    the producer runs while the consumer proceeds."""
    assert relation in ADJ_COMPLETION_RELATIONS
    if relation not in eng.relations:
        raise ValueError(
            f"completion of {relation!r} needs it in the engine's relation "
            f"set (got {eng.relations}); construct the RelationEngine with "
            f"it so the producer has a queue to serve the fan-out from")
    kind = relation[0]
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    n = len(ids)
    ns = eng.smesh.n_segments
    if n == 0:
        eng.stat_bump(completion_chunks=1)
        empty = np.zeros(0, dtype=np.int64)
        return CompletionPlan(relation, ids, empty, empty,
                              empty.astype(np.int32), empty)

    owners = _boundary_owner_segments(eng, relation, ids)   # (n, k+1)
    w = owners.shape[1]
    qidx = np.repeat(np.arange(n, dtype=np.int64), w)
    # dedup (query, segment) pairs across boundary faces in one unique pass
    ukey = np.unique(qidx * ns + owners.reshape(-1))
    pair_query = ukey // ns
    pair_seg = ukey % ns
    pair_row = eng.local_rows(kind, pair_seg, ids[pair_query])
    # completion invariant (docs/DESIGN.md §5): every boundary-face owner's
    # table contains the query simplex; tolerate (and skip) violations so
    # the batched path degrades exactly like the scalar one
    ok = pair_row >= 0
    if not ok.all():
        pair_query, pair_seg, pair_row = (
            pair_query[ok], pair_seg[ok], pair_row[ok])
    segments = np.unique(pair_seg)

    eng.stat_bump(completion_chunks=1, completion_queries=n,
                  completion_fanout_blocks=len(segments))
    plan = CompletionPlan(relation, ids, pair_query, pair_seg,
                          pair_row.astype(np.int32), segments)
    if prefetch:
        _prefetch(eng, plan)
    return plan


def _prefetch(eng, plan: CompletionPlan) -> None:
    """One non-blocking ``prefetch_many`` for every block ``plan``
    consults."""
    eng.prefetch_many({plan.relation: [int(s) for s in plan.segments]})


@spanned("completion.execute")
def execute_completion(eng: RelationEngine, plan: CompletionPlan
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Gather + union the planned rows into padded ``(M, L)`` arrays.

    Reads each distinct segment block once through ``get_full`` (blocking
    only if the prefetched launch is still in flight), then performs the
    union / self-removal / dedup / compaction as vectorized numpy ops.
    Rows come out ascending — bit-identical to the scalar reference."""
    n = len(plan.ids)
    P = len(plan.pair_seg)
    if P == 0:
        return (np.full((n, 1), -1, dtype=np.int64),
                np.zeros(n, dtype=np.int32))

    # one gather per consulted segment (pairs pre-grouped by segment: the
    # plan's unique-key pass sorted them by (query, segment); re-sort by
    # segment so each block is sliced exactly once)
    order = np.argsort(plan.pair_seg, kind="stable")
    seg_sorted = plan.pair_seg[order]
    lo = np.searchsorted(seg_sorted, plan.segments, side="left")
    hi = np.searchsorted(seg_sorted, plan.segments, side="right")
    deg = eng.deg[plan.relation]
    vals = np.full((P, deg), -1, dtype=np.int64)
    lens = np.zeros(P, dtype=np.int64)
    for s, a, b in zip(plan.segments, lo, hi):
        Mf, Lf = eng.get_full(plan.relation, int(s))
        sel = order[a:b]
        rows = plan.pair_row[sel]
        width = min(deg, Mf.shape[1])
        vals[sel, :width] = Mf[rows, :width]
        lens[sel] = np.minimum(Lf[rows], width)

    # flatten valid entries -> (query, neighbor) pairs
    col = np.arange(deg, dtype=np.int64)
    valid = (col[None, :] < lens[:, None]) & (vals >= 0)
    nb = vals[valid]
    q = np.broadcast_to(plan.pair_query[:, None], (P, deg))[valid]
    raw = len(nb)
    # remove the query simplex itself, then dedup per query (sorted)
    keep = nb != plan.ids[q]
    nb, q = nb[keep], q[keep]
    if len(nb):
        srt = np.lexsort((nb, q))
        nb, q = nb[srt], q[srt]
        first = np.ones(len(nb), dtype=bool)
        first[1:] = (q[1:] != q[:-1]) | (nb[1:] != nb[:-1])
        nb, q = nb[first], q[first]

    counts = np.bincount(q, minlength=n) if len(nb) else np.zeros(n, np.int64)
    width = max(int(counts.max()) if len(counts) else 0, 1)
    M = np.full((n, width), -1, dtype=np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    M[q, np.arange(len(nb)) - offsets[q]] = nb
    L = counts.astype(np.int32)

    eng.stat_bump(completion_raw_neighbors=raw,
                  completion_neighbors=len(nb))
    return M, L


# Max (query, segment) pairs per query = number of boundary (k-1)-faces.
_PAIR_WIDTH = {"E": 2, "F": 3, "T": 4}

# The device arm's per-chunk budget: i32 entries of the candidate matrix
# (queries x pair width x deg), 1 MiB. Not a parameter: a chunk's HBM
# temporaries (the gathered candidates, padded to 128 lanes on a TPU, and
# the union's two sorts) grow with it beside the resident mesh, and
# peak_hbm_gb may move by 8% at most. At 2 MiB a TT chunk's gather on the
# 100^3 grid compiles to 35 MB of temporaries, at 1 MiB to 18 MB
# (tests/test_tpu_compile.py).
CHUNK_ENTRIES = 1 << 18


def device_chunk(relation: str, deg: int) -> int:
    """Queries in one device-arm completion chunk: the largest power of two
    whose candidate matrix fits :data:`CHUNK_ENTRIES`, and at least one."""
    fit = CHUNK_ENTRIES // (_PAIR_WIDTH[relation[0]] * deg)
    return 1 << max(fit.bit_length() - 1, 0)


_pow2 = ops.bucket_rows


# contract: device-resident
@spanned("completion.execute")
def execute_completion_device(eng: RelationEngine, plan: CompletionPlan,
                              out: str = "host"
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Device-side gather + union of the planned rows (the GALE path).

    Stacks the consulted blocks from the engine's device block pool
    (``get_full_dev`` — blocking only on launches still in flight),
    re-resolves every (segment, gid) pair to its block row by batched binary
    search over the DEVICE inverse maps, and performs the union /
    self-removal / dedup / compaction on the accelerator
    (``kernels/completion_gather.py``, backend per ``eng.backend``). One
    host round trip per batch; bit-identical to :func:`execute_completion`.

    With ``out="dev"`` the completed rows STAY on the accelerator: the
    return value is device ``(M (n, deg) i32, L (n,) i32)`` arrays for a
    device-resident consumer (docs/DESIGN.md §6) and the batch pays no host
    round trip at all (the overflow check reduces ``L`` to one scalar).

    Raises :class:`RelationWidthError` if a completed row would overflow
    ``deg[relation]`` (the preallocated relation-array width)."""
    if not hasattr(eng, "get_full_dev"):
        raise TypeError(
            "the device completion path needs a RelationEngine (device "
            "block pool + device inverse maps); use path='host' for "
            f"{type(eng).__name__}")
    n = len(plan.ids)
    P = len(plan.pair_seg)
    if P == 0:
        if out == "dev":   # width stays deg so chunked device concat lines up
            return (jnp.full((n, eng.deg[plan.relation]), -1,
                             dtype=jnp.int32),
                    jnp.zeros(n, dtype=jnp.int32))
        return (np.full((n, 1), -1, dtype=np.int64),
                np.zeros(n, dtype=np.int32))
    relation = plan.relation
    kind = relation[0]
    deg = eng.deg[relation]
    w = _PAIR_WIDTH[kind]

    # device block pool, padded to a power-of-two slot count (padding
    # repeats slot 0; no pair references it) so jit sees stable shapes
    pool_M, pool_L = eng.get_full_dev_batch(
        relation, plan.segments, pad_to=_pow2(len(plan.segments)))

    slot = np.searchsorted(plan.segments, plan.pair_seg).astype(np.int32)
    # per-query pair positions (pairs come sorted by query from the plan's
    # unique pass) -> the (n, w) pair_at gather map
    counts_p = np.bincount(plan.pair_query, minlength=n)
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts_p, out=off[1:])
    pos = np.arange(P, dtype=np.int64) - off[plan.pair_query]
    pair_at = np.full((_pow2(n), w), -1, dtype=np.int32)
    pair_at[plan.pair_query, pos] = np.arange(P, dtype=np.int32)

    # pad pairs to a power-of-two bucket with inert entries (slot == -1)
    P_pad = _pow2(P)
    pad = P_pad - P
    pair_slot = np.concatenate([slot, np.full(pad, -1, np.int32)])
    pair_seg = np.concatenate(
        [plan.pair_seg.astype(np.int32), np.zeros(pad, np.int32)])
    pair_gid = np.concatenate(
        [plan.ids[plan.pair_query].astype(np.int32),
         np.full(pad, -1, np.int32)])

    inv_seg, inv_gid, inv_row, inv_key, n_glob = eng.dev_inverse(kind)
    M_dev, L_dev, raw, kept = ops.completion_gather(
        pool_M, pool_L, inv_seg, inv_gid, inv_row,
        jnp.asarray(pair_slot), jnp.asarray(pair_seg),
        jnp.asarray(pair_gid), jnp.asarray(pair_at),
        deg_out=deg, backend=eng.backend, inv_key=inv_key, n_global=n_glob)

    return _checked_rows(eng, relation, n, M_dev, L_dev, raw, kept, out)


def _checked_rows(eng: RelationEngine, relation: str, n: int, M_dev, L_dev, raw,
            kept, out: str):
    """The tail of both device execute arms: count the chunk's neighbours,
    check the widest row against ``deg[relation]``, and return the rows on
    the device (``out="dev"``) or as host arrays trimmed to the widest row.
    The first device read here waits for the chunk's gather, so the reads
    run inside the ``completion.width_check`` span."""
    deg = eng.deg[relation]
    with span("completion.width_check"):
        eng.stat_bump(completion_raw_neighbors=int(raw),
                      completion_neighbors=int(kept))
        if out == "dev":
            # device-resident consumers take the padded (n, deg) rows
            # as-is; the overflow check costs one scalar reduce, not a
            # block download
            worst = int(jnp.max(L_dev[:n])) if n else 0
        else:
            # the chunk's documented ONE host round trip (DESIGN.md §6)
            Mh = np.asarray(M_dev)[:n]
            Lh = np.asarray(L_dev)[:n]
            worst = int(Lh.max()) if n else 0
    if worst > deg:
        raise RelationWidthError(
            f"completed {relation!r} row has {worst} neighbours but the "
            f"preallocated width is deg[{relation!r}]={deg}; construct the "
            f"engine with deg={{{relation!r}: {worst}}} (or larger).")
    if out == "dev":
        return M_dev[:n], L_dev[:n]
    return Mh[:, :max(worst, 1)].astype(np.int64), Lh.astype(np.int32)


@spanned("completion.execute")
def execute_completion_sharded(eng: RelationEngine, plan: CompletionPlan,
                               out: str = "host"
                               ) -> Tuple[np.ndarray, np.ndarray]:
    """Cross-device completion exchange for sharded engines (DESIGN.md §9).

    Each (query, segment) pair is owned by exactly one shard — the one whose
    device produced and retains the consulted segment's block. Per shard the
    ``(segment, gid)`` resolve + pool gather of the device path runs over
    the shard's OWN blocks only (``kernels.completion_gather.
    gather_candidates``), with non-owned pairs masked to exact zeros; an
    elementwise integer sum across the shard axis
    (``distributed.sharding.all_sum_shards`` — a ``psum`` over the
    ``("data",)`` mesh when shards sit on distinct devices, stack+sum
    otherwise) then reconstructs the single-pool candidate matrix
    bit-for-bit, and the shared union epilogue runs once. Bit-identical to
    :func:`execute_completion_device` with one host round trip per chunk
    (the final result download)."""
    from ..distributed.sharding import all_sum_shards
    splan = eng.shard_plan
    n = len(plan.ids)
    P = len(plan.pair_seg)
    if P == 0:
        if out == "dev":
            return (jnp.full((n, eng.deg[plan.relation]), -1,
                             dtype=jnp.int32),
                    jnp.zeros(n, dtype=jnp.int32))
        return (np.full((n, 1), -1, dtype=np.int64),
                np.zeros(n, dtype=np.int32))
    relation = plan.relation
    kind = relation[0]
    deg = eng.deg[relation]
    w = _PAIR_WIDTH[kind]

    # shared pair metadata (identical on every shard)
    counts_p = np.bincount(plan.pair_query, minlength=n)
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts_p, out=off[1:])
    pos = np.arange(P, dtype=np.int64) - off[plan.pair_query]
    pair_at = np.full((_pow2(n), w), -1, dtype=np.int32)
    pair_at[plan.pair_query, pos] = np.arange(P, dtype=np.int32)
    P_pad = _pow2(P)
    pad = P_pad - P
    pair_seg = np.concatenate(
        [plan.pair_seg.astype(np.int32), np.zeros(pad, np.int32)])
    pair_gid = np.concatenate(
        [plan.ids[plan.pair_query].astype(np.int32),
         np.full(pad, -1, np.int32)])
    pair_shard = splan.shard_of_array(plan.pair_seg)

    # per-shard local gathers: each shard consults only its own contiguous
    # slice of the planned segments, served from ITS device pool
    parts = []
    part_devs = []
    seg_lo = np.searchsorted(plan.segments, splan.bounds[:-1], side="left")
    seg_hi = np.searchsorted(plan.segments, splan.bounds[1:], side="left")
    pair_seg_dev = jnp.asarray(pair_seg)
    pair_gid_dev = jnp.asarray(pair_gid)
    for k in range(splan.n_shards):
        segs_k = plan.segments[seg_lo[k]:seg_hi[k]]
        sel = pair_shard == k
        if len(segs_k) == 0 or not sel.any():
            continue
        pool_M, pool_L = eng.get_full_dev_batch(
            relation, segs_k, pad_to=_pow2(len(segs_k)))
        slot_k = np.where(
            sel, np.searchsorted(segs_k, plan.pair_seg).astype(np.int32),
            np.int32(-1))
        pair_slot = np.concatenate([slot_k, np.full(pad, -1, np.int32)])
        inv_seg, inv_gid, inv_row, inv_key, n_glob = eng.dev_inverse(
            kind, shard=k)
        from ..kernels import completion_gather as _cg
        cand, clen = _cg.gather_candidates(
            pool_M, pool_L, inv_seg, inv_gid, inv_row,
            jnp.asarray(pair_slot), pair_seg_dev, pair_gid_dev,
            inv_key=inv_key, n_global=n_glob)
        parts.append((cand, clen))
        part_devs.append(splan.devices[k])

    if not parts:   # no pair resolved anywhere: all-empty rows
        if out == "dev":
            return (jnp.full((n, deg), -1, dtype=jnp.int32),
                    jnp.zeros(n, dtype=jnp.int32))
        return (np.full((n, 1), -1, dtype=np.int64),
                np.zeros(n, dtype=np.int32))

    from ..kernels import completion_gather as _cg
    cand, clen, exchange = all_sum_shards(parts, part_devs)
    if exchange != "none":
        eng.stat_bump(**{f"exchange_{exchange}": 1})
    if splan.multi_device:
        # commit every chunk's summed matrix to shard 0's device: the psum
        # output is replicated over THIS chunk's participant mesh, which
        # varies chunk to chunk, and out="dev" concatenates across chunks
        home = splan.devices[0]
        cand = jax.device_put(cand, home)
        clen = jax.device_put(clen, home)
    M_dev, L_dev, raw, kept = _cg.union_pairs(
        cand, clen, pair_gid_dev, jnp.asarray(pair_at), deg)

    return _checked_rows(eng, relation, n, M_dev, L_dev, raw, kept, out)


@spanned("completion.complete")
def complete_adjacency(
    eng: RelationEngine, relation: str, ids: Sequence[int],
    batch: Optional[int] = None, path: Optional[str] = None,
    out: str = "host", workers: int = 1, shards: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Complete EE/FF/TT rows for global simplex ids. Returns padded (M, L).

    ``path`` selects the execute arm: ``"device"`` gathers/unions on the
    accelerator (:func:`execute_completion_device`), ``"host"`` in numpy
    (:func:`execute_completion`); ``None`` auto-selects "device" when the
    data structure exposes a device block pool (``get_full_dev``) AND a
    real accelerator backs the arrays — on CPU-only jax the device arm
    would only pay XLA dispatch overhead, so the host arm stays the
    default there. Both arms are bit-identical.

    ``out="dev"`` (device execute arm only) keeps the completed rows on the
    accelerator: device ``(M (n, deg[relation]) i32, L (n,) i32)`` arrays
    for device-resident consumers (docs/DESIGN.md §6) — rows stay at the
    full preallocated width instead of being trimmed to the realized
    maximum, and no host round trip happens.

    The query list is processed in pipelined chunks: chunk i+1's blocks are
    prefetched *before* chunk i is executed, so relation production
    overlaps the gather/union work — the same produce-ahead idiom the
    algorithm drivers use for every other relation. The device arm's chunk
    is :func:`device_chunk` of the relation and its width; ``batch=k`` sets
    it to k queries instead, and is the host arm's only chunking (one chunk
    without it). ``workers=N`` partitions the chunk stream across N
    consumer threads through the scheduler (docs/DESIGN.md §8), each
    keeping the plan-ahead pipelining for its own chunks; chunk results
    are assembled in chunk order. The result is bit-identical for any
    ``batch`` and any ``workers``.

    ``shards=`` is a validation knob: sharding follows the *engine's*
    :class:`~repro.distributed.sharding.ShardPlan` automatically (the
    device arm becomes the cross-device exchange of
    :func:`execute_completion_sharded` when the engine has more than one
    shard); passing a ``shards`` count that does not match the engine's
    plan raises instead of silently running a different topology. The
    result is bit-identical for any shard count."""
    n_shards = getattr(getattr(eng, "shard_plan", None), "n_shards", 1)
    if shards is not None and int(shards) != n_shards:
        raise ValueError(
            f"shards={shards} requested but the engine's shard plan has "
            f"{n_shards} shard(s); construct the RelationEngine with "
            f"shards={shards}")
    if path is None:
        path = ("device" if hasattr(eng, "get_full_dev")
                and (out == "dev" or jax.default_backend() != "cpu")
                else "host")
    if path not in ("host", "device"):
        raise ValueError(f"path must be 'host' or 'device', got {path!r}")
    if out == "dev" and path != "device":
        raise ValueError("out='dev' needs the device execute arm "
                         f"(got path={path!r})")
    if path == "device":
        arm = (execute_completion_sharded if n_shards > 1
               else execute_completion_device)

        def execute(e, p):
            return arm(e, p, out=out)
    else:
        execute = execute_completion
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    if batch is None and path == "device":
        batch = device_chunk(relation, eng.deg[relation])
    if batch is None or batch <= 0 or batch >= len(ids):
        rows = execute(eng, plan_completion(eng, relation, ids))
    else:
        rows = _run_chunks(eng, relation, ids, batch, execute, out, workers,
                           release=path == "device")
    if path == "device":
        # a block is read in the chunks that consult it; held past them it
        # keeps device memory from later chunks and the rows' consumers
        eng.release_dev(relation)
    return rows


def _run_chunks(eng, relation: str, ids: np.ndarray, batch: int, execute,
                out: str, workers: int, release: bool):
    """Plan and execute ``ids`` in chunks of ``batch`` queries, producing
    chunk i+1's blocks before executing chunk i, and assemble the rows in
    chunk order. With ``release``, a block leaves the device pool once the
    last chunk that consults it has executed."""
    chunks = [ids[i:i + batch] for i in range(0, len(ids), batch)]
    outs: list = [None] * len(chunks)
    if workers and workers > 1:
        from .scheduler import run_partitioned

        def consume_chunk(i, chunk):       # plan + prefetch (non-blocking)
            return plan_completion(eng, relation, chunk)

        def finalize_chunk(plan):          # gather/union one chunk
            return execute(eng, plan)

        def reduce_chunk(i, res):
            outs[i] = res

        run_partitioned(chunks, consume_chunk, reduce_chunk,
                        workers=workers, finalize=finalize_chunk,
                        scope=eng, name=f"completion/{relation}")
    else:
        # plans are host work independent of production: plan every chunk
        # first, so each block's last consulting chunk is known
        plans = [plan_completion(eng, relation, c, prefetch=False)
                 for c in chunks]
        last = np.full(eng.smesh.n_segments, -1, dtype=np.int64)
        for i, p in enumerate(plans):
            last[p.segments] = i
        _prefetch(eng, plans[0])
        for i, p in enumerate(plans):
            if i + 1 < len(plans):   # produce ahead of the execute
                _prefetch(eng, plans[i + 1])
            outs[i] = execute(eng, p)
            if release:
                eng.release_dev(relation, p.segments[last[p.segments] == i])
    if out == "dev":
        # chunk widths are all deg[relation]: one device concat, no host copy
        return (jnp.concatenate([Mc for Mc, _ in outs]),
                jnp.concatenate([Lc for _, Lc in outs]))
    width = max(max(M.shape[1] for M, _ in outs), 1)
    M = np.full((len(ids), width), -1, dtype=np.int64)
    L = np.concatenate([Lc for _, Lc in outs])
    at = 0
    for Mc, Lc in outs:
        M[at:at + len(Lc), : Mc.shape[1]] = Mc
        at += len(Lc)
    return M, L


def complete_adjacency_scalar(
    eng: RelationEngine, relation: str, ids: Sequence[int]
) -> Tuple[np.ndarray, np.ndarray]:
    """One-simplex-at-a-time reference for the batched pipeline.

    Same union over boundary-face owner segments, but resolved with Python
    sets and one blocking block read per (query, segment) pair. Kept for the
    A/B benchmark and the bit-identical regression test; row lookups go
    through the inverse maps, not table scans."""
    assert relation in ADJ_COMPLETION_RELATIONS
    kind = relation[0]
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    owners = (_boundary_owner_segments(eng, relation, ids)
              if len(ids) else np.zeros((0, 1), np.int64))
    rows = []
    for i, gid in enumerate(ids):
        acc: set = set()
        for s in sorted(set(int(x) for x in owners[i])):
            r = int(eng.local_rows(kind, np.array([s]), np.array([gid]))[0])
            if r < 0:
                continue
            Mf, Lf = eng.get_full(relation, s)
            acc |= set(int(x) for x in Mf[r][: Lf[r]] if x >= 0)
        acc.discard(int(gid))
        rows.append(sorted(acc))
    deg = max((len(r) for r in rows), default=1)
    M = np.full((len(rows), max(deg, 1)), -1, dtype=np.int64)
    L = np.zeros(len(rows), dtype=np.int32)
    for i, r in enumerate(rows):
        M[i, : len(r)] = r
        L[i] = len(r)
    return M, L
