"""Jit'd wrappers around the segment-relation kernels: backend dispatch,
relation predicates, and compaction of dense count blocks into the paper's
padded ``(M, L)`` relation arrays.

Backends:
  - ``"xla"``               : one fused jit per launch, specialized per
                              relation with sparse entry assembly — the
                              default, and the arm that compiles for the
                              TPU for every relation
  - ``"pallas"``            : pl.pallas_call compiled for the TPU — sparse
                              entry assembly emitting (M, L) directly, with
                              a one-hot counts fallback for EE/FF and
                              oversize keys (docs/DESIGN.md §4). A kernel
                              the compiler refuses raises
                              :class:`~repro.errors.KernelCompileError`
                              naming the relation; nothing falls back
  - ``"pallas_interpret"``  : the same kernels in Pallas's interpreter (CPU
                              correctness validation) — the only arm that
                              interprets

Both backend families fork sparse/dense under the shared guards in
:func:`sparse_arm_ok`; ``assembly="dense"`` forces the legacy dense
epilogue for the benchmark A/B.
"""

from __future__ import annotations

import contextlib
import functools
import re
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import compile_on_devices
from ..errors import KernelCompileError
from . import ref
from .segment_relations import (
    relation_counts_meet_pallas,
    relation_counts_vv_pallas,
    relation_entries_pallas,
)

# Maximum relation-list width (the paper's preallocated relation-array width).
# Generous bounds for Freudenthal-style and irregular tet meshes; the engine
# enforces no overflow at runtime: RelationEngine._integrate raises
# RelationWidthError (naming the deg= override) whenever a produced row's
# true count L exceeds this width, instead of silently truncating M.
DEFAULT_DEG = {
    "VV": 32, "VE": 32, "VF": 96, "VT": 64,
    "EF": 16, "ET": 16, "FT": 4, "TT": 8, "EE": 64, "FF": 48,
}

def bucket_rows(n: int, floor: int = 1) -> int:
    """Round a batch-sized leading dimension up to a power-of-two bucket.

    Every jit whose input carries a batch-sized leading dim (kernel launch
    batches, stacked consumer rows, completion pair lists) pads to this
    bucket so ragged tails produce O(log n) distinct shapes instead of one
    recompile per tail size. ``floor`` sets the minimum bucket."""
    return 1 << max(int(max(n, floor, 1)) - 1, 0).bit_length()


# (shared count k, exact match?) — see core.segtables.RELATION_PREDICATE.
PREDICATE = {
    "VE": (1, True), "VF": (1, True), "VT": (1, True),
    "EF": (2, True), "ET": (2, True), "FT": (3, True),
    "VV": (1, False), "EE": (1, True), "FF": (2, True), "TT": (3, True),
}


# What the Pallas TPU path raises for a kernel it cannot lower or compile,
# told apart from a bug in the kernel wrappers by its type and wording: a
# Mosaic compile failure (``MosaicError`` and its ``VerificationError``, or
# the runtime error Mosaic raised), a lowering rule Mosaic lacks ("Only 2D
# gather is supported", "Unimplemented primitive ..."), a block shape it
# rejects, and a CPU backend, which runs Pallas only in interpret mode.
_REFUSAL_TYPES = (ValueError, NotImplementedError, jax.errors.JaxRuntimeError)
_REFUSAL_WORDING = re.compile(
    r"Mosaic|Pallas TPU lowering|Unimplemented|not supported|is supported"
    r"|interpret mode")


def _is_pallas_refusal(e: BaseException) -> bool:
    """Whether ``e`` is the Pallas TPU compiler refusing a kernel."""
    if any(t.__name__ == "MosaicError" for t in type(e).__mro__):
        return True
    return (isinstance(e, _REFUSAL_TYPES)
            and _REFUSAL_WORDING.search(str(e)) is not None)


@contextlib.contextmanager
def pallas_refusal(what: str, backend: str = "pallas"):
    """Under ``backend="pallas"``, re-raise a Pallas lowering/compile
    refusal inside the block as :class:`KernelCompileError` naming ``what``
    (a relation, or ``"completion gather"``), with the compiler's error
    chained. Any other error, and every error under another backend (the
    interpreter included), propagates unchanged."""
    try:
        yield
    except Exception as e:
        if backend != "pallas" or not _is_pallas_refusal(e):
            raise
        raise KernelCompileError(
            f"backend='pallas' cannot compile the {what} kernel: "
            f"{type(e).__name__}: {e}",
            relation=what if what in PREDICATE else None) from e


def counts_meet(tabX: jnp.ndarray, tabY: jnp.ndarray, nvl: int,
                backend: str = "xla",
                block_x: int = 256, block_y: int = 256) -> jnp.ndarray:
    """Shared-vertex counts C (B, NX, NY). Tables are (B, N, arity)."""
    if backend == "xla":
        return _counts_meet_xla(tabX, tabY, nvl)
    interp = backend == "pallas_interpret"
    tx = jnp.swapaxes(tabX, 1, 2)
    ty = jnp.swapaxes(tabY, 1, 2)
    return relation_counts_meet_pallas(
        tx, ty, nvl=nvl, block_x=block_x, block_y=block_y, interpret=interp)


def counts_vv(T_local: jnp.ndarray, nvl: int, backend: str = "xla",
              block: int = 128) -> jnp.ndarray:
    """Shared-tet counts C (B, nvl, nvl). T_local is (B, NT, 4)."""
    if backend == "xla":
        return _counts_vv_xla(T_local, nvl)
    interp = backend == "pallas_interpret"
    tt = jnp.swapaxes(T_local, 1, 2)
    return relation_counts_vv_pallas(tt, nvl=nvl, block=block,
                                     interpret=interp)


@functools.partial(jax.jit, static_argnames=("nvl",))
def _counts_meet_xla(tabX, tabY, nvl):
    return ref.relation_counts_meet(tabX, tabY, nvl)


@functools.partial(jax.jit, static_argnames=("nvl",))
def _counts_vv_xla(T_local, nvl):
    return ref.relation_counts_vv(T_local, nvl)


@functools.partial(jax.jit, static_argnames=("deg",))
def compact(mask: jnp.ndarray, col_global: jnp.ndarray, deg: int
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact boolean relation rows into padded index lists.

    mask:       (B, R, N) bool — relation holds between row r and local col n
    col_global: (B, N) int32   — local -> global id map (-1 for padding)
    returns M (B, R, deg) int32 global ids (-1 padded, ascending local order)
            L (B, R) int32 counts (saturating at deg is the caller's check)

    Nonzero columns get descending scores in ascending column order, so
    top_k yields "all set columns, ascending" — the paper's M array order.
    """
    return _compact_impl(mask, col_global, deg)


@functools.partial(jax.jit, static_argnames=("k", "exact", "exclude_diag"))
def predicate(C: jnp.ndarray, k: int, exact: bool,
              exclude_diag: bool) -> jnp.ndarray:
    """Counts -> boolean relation block."""
    return _predicate_impl(C, k, exact, exclude_diag)


def _predicate_impl(C, k, exact, exclude_diag):
    m = (C == k) if exact else (C >= k)
    if exclude_diag:
        n = min(C.shape[1], C.shape[2])
        eye = jnp.eye(n, dtype=bool)
        pad = jnp.zeros((C.shape[1], C.shape[2]), dtype=bool).at[:n, :n].set(eye)
        m = jnp.logical_and(m, ~pad[None])
    return m


def _compact_impl(mask, col_global, deg):
    B, R, N = mask.shape
    iota = jnp.arange(N, dtype=jnp.int32)
    scores = jnp.where(mask, N - iota, 0).astype(jnp.int32)
    # top_k caps k at the column count; narrow tables (prime-sized tails)
    # can have N < deg, in which case M right-pads with -1 columns
    k = min(deg, N)
    vals, idx = jax.lax.top_k(scores, k)              # (B, R, k)
    valid = vals > 0
    gathered = jnp.take_along_axis(
        jnp.broadcast_to(col_global[:, None, :], (B, R, N)), idx, axis=2)
    M = jnp.where(valid, gathered, -1)
    if k < deg:
        M = jnp.pad(M, ((0, 0), (0, 0), (0, deg - k)), constant_values=-1)
    L = mask.sum(axis=2).astype(jnp.int32)
    return M, L


_BIG = np.int32(np.iinfo(np.int32).max)


def _invert_entries(row, order, val, valid, R: int, O: int, deg: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse relation assembly: turn per-batch entry lists into the padded
    ``(M (B, R, deg), L (B, R))`` block — the xla backend's replacement for
    the dense mask + top_k compaction (O(entries·log entries) instead of
    O(R·N) — the launch epilogue used to dominate the producer).

    ``row``/``order``/``val``/``valid``: (B, E) int32 entry columns — the
    block row, the intra-row sort key (the old compaction's local column
    index, so M rows keep the exact same ascending-local order), and the
    global id to store. Entries sharing ``(row, order)`` are stored/counted
    once (they always carry the same ``val``); ``L`` is the TRUE count, so
    overflow past ``deg`` stays detectable by the engine's width check."""
    B, E = row.shape
    key = jnp.where(valid, row * O + order, _BIG)
    key, val = jax.lax.sort((key, val), num_keys=1)
    valid_s = key != _BIG
    rows_s = jnp.where(valid_s, key // O, R)
    ones = jnp.ones((B, 1), dtype=bool)
    uniq = valid_s & jnp.concatenate(
        [ones, key[:, 1:] != key[:, :-1]], axis=1)
    first = jnp.concatenate(
        [ones, rows_s[:, 1:] != rows_s[:, :-1]], axis=1)
    cum = jnp.cumsum(uniq.astype(jnp.int32), axis=1)     # inclusive rank
    # exclusive unique-rank at each row group's start, propagated across
    # the group (ranks are nondecreasing, so cummax carries them forward)
    excl = jax.lax.cummax(
        jnp.where(first, cum - uniq.astype(jnp.int32), -1), axis=1)
    pos = cum - 1 - excl
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    r_idx = jnp.minimum(rows_s, R)
    p_idx = jnp.where(valid_s & (pos < deg), pos, deg)
    M = jnp.full((B, R + 1, deg + 1), -1, dtype=jnp.int32)
    M = M.at[bidx, r_idx, p_idx].set(val)[:, :R, :deg]
    L = jnp.zeros((B, R + 1), dtype=jnp.int32)
    L = L.at[bidx, r_idx].add(uniq.astype(jnp.int32))[:, :R]
    return M, L


def _block_member_v(tabY, col_global, nvl: int, deg: int):
    """VE/VF/VT block via entry inversion: local vertex ``v`` relates to
    simplex ``y`` iff ``v ∈ verts(y)`` (the exact ``C == 1`` predicate — a
    simplex lists distinct vertices), so the ``(B, NY, arity)`` table IS
    the entry list."""
    B, NY, a = tabY.shape
    ok = tabY >= 0
    yid = jnp.broadcast_to(
        jnp.arange(NY, dtype=jnp.int32)[None, :, None], (B, NY, a))
    val = jnp.broadcast_to(col_global[:, :, None], (B, NY, a))
    return _invert_entries(
        jnp.maximum(tabY, 0).reshape(B, -1), yid.reshape(B, -1),
        val.reshape(B, -1).astype(jnp.int32), ok.reshape(B, -1),
        R=nvl, O=NY, deg=deg)


def _block_vv(T_local, col_global, nvl: int, deg: int):
    """VV block via entry inversion: ``v ~ w`` iff some local tet contains
    both (the ``C >= 1`` off-diagonal predicate). The 12 ordered vertex
    pairs of each tet are the entries; a tet's vertices are distinct, so
    the diagonal never appears, and repeated pairs from different tets
    dedup inside :func:`_invert_entries`."""
    B, NT, arity = T_local.shape
    rows, orders, vals, valids = [], [], [], []
    for a in range(arity):
        for b in range(arity):
            if a == b:
                continue
            va, vb = T_local[..., a], T_local[..., b]
            ok = (va >= 0) & (vb >= 0)
            rows.append(jnp.maximum(va, 0))
            orders.append(jnp.maximum(vb, 0))
            vals.append(jnp.take_along_axis(
                col_global, jnp.maximum(vb, 0), axis=1))
            valids.append(ok)
    cat = lambda xs: jnp.concatenate(xs, axis=1)
    return _invert_entries(cat(rows), cat(orders),
                           cat(vals).astype(jnp.int32), cat(valids),
                           R=nvl, O=nvl, deg=deg)


_TET_FACES = ((0, 1, 2), (0, 1, 3), (0, 2, 3), (1, 2, 3))


def _block_tt(T_local, col_global, nvl: int, deg: int):
    """TT block via a sort join on canonical face keys: two distinct tets
    relate iff they share exactly three vertices — a common face (the
    exact ``C == 3`` predicate). Each local tet contributes its four sorted
    vertex triples; after one lane-wise sort, equal adjacent keys are the
    shared faces (a face has at most two cofacet tets), yielding both
    directed entries."""
    B, NT, _ = T_local.shape
    w = jnp.sort(T_local, axis=-1)                    # ascending vertices
    valid_t = (T_local >= 0).all(-1)                  # (B, NT)
    keys = [((w[..., i] * nvl + w[..., j]) * nvl + w[..., k])
            for i, j, k in _TET_FACES]
    fkey = jnp.stack(keys, axis=-1).reshape(B, 4 * NT)
    tid = jnp.broadcast_to(
        jnp.arange(NT, dtype=jnp.int32)[None, :, None],
        (B, NT, 4)).reshape(B, 4 * NT)
    fkey = jnp.where(jnp.repeat(valid_t, 4, axis=1), fkey, _BIG)
    fkey, tid = jax.lax.sort((fkey, tid), num_keys=1)
    eq = (fkey[:, :-1] == fkey[:, 1:]) & (fkey[:, :-1] != _BIG)
    t0, t1 = tid[:, :-1], tid[:, 1:]
    row = jnp.concatenate([t0, t1], axis=1)
    order = jnp.concatenate([t1, t0], axis=1)
    valid = jnp.concatenate([eq, eq], axis=1)
    val = jnp.take_along_axis(col_global, order, axis=1)
    return _invert_entries(row, order, val.astype(jnp.int32), valid,
                           R=NT, O=NT, deg=deg)


def _block_sub_join(tabX, tabY, col_global, nvl: int, deg: int):
    """EF/ET/FT block via a sort join: subject ``x`` relates to ``y`` iff
    every vertex of ``x`` lies in ``y`` (the exact ``C == arity(x)``
    predicate — x then is a boundary sub-simplex of y). X rows contribute
    their canonical sorted vertex key once; each y contributes the keys of
    all its arity(x)-vertex subsets. After one lane-wise sort (x entries
    ordered before equal-key y entries), every y entry resolves its x row
    from the latest x entry seen — local tables list every sub-simplex of
    every local simplex, so the group is never orphaned, and a cross-group
    mismatch is caught by re-checking the key."""
    import itertools

    B, NX, ax = tabX.shape
    _, NY, ay = tabY.shape
    wx = jnp.sort(tabX, axis=-1)
    kx = wx[..., 0]
    for i in range(1, ax):
        kx = kx * nvl + wx[..., i]
    kx = jnp.where((tabX >= 0).all(-1), kx * 2, _BIG)      # is_y = 0
    wy = jnp.sort(tabY, axis=-1)
    oky = (tabY >= 0).all(-1)
    ykeys = []
    for comb in itertools.combinations(range(ay), ax):
        k = wy[..., comb[0]]
        for c in comb[1:]:
            k = k * nvl + wy[..., c]
        ykeys.append(k)
    nyk = len(ykeys)
    ky = jnp.stack(ykeys, axis=-1).reshape(B, NY * nyk)
    ky = jnp.where(jnp.repeat(oky, nyk, axis=1), ky * 2 + 1, _BIG)
    yid = jnp.broadcast_to(
        jnp.arange(NY, dtype=jnp.int32)[None, :, None],
        (B, NY, nyk)).reshape(B, NY * nyk)

    key = jnp.concatenate([kx, ky], axis=1)
    payload = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(NX, dtype=jnp.int32)[None, :], (B, NX)),
         yid], axis=1)
    is_y = jnp.concatenate(
        [jnp.zeros((B, NX), jnp.int32), jnp.ones((B, NY * nyk), jnp.int32)],
        axis=1)
    key, payload, is_y = jax.lax.sort((key, payload, is_y), num_keys=1)
    iota = jnp.arange(key.shape[1], dtype=jnp.int32)[None, :]
    lastX = jax.lax.cummax(jnp.where(is_y == 0, iota, -1), axis=1)
    take = jnp.maximum(lastX, 0)
    xkey = jnp.take_along_axis(key, take, axis=1)
    ok = ((is_y == 1) & (lastX >= 0) & (key != _BIG)
          & (xkey == key - 1))
    row = jnp.take_along_axis(payload, take, axis=1)
    val = jnp.take_along_axis(
        col_global, jnp.where(ok, payload, 0), axis=1)
    return _invert_entries(row, jnp.where(ok, payload, 0),
                           val.astype(jnp.int32), ok,
                           R=NX, O=NY, deg=deg)


def _counts_pairwise(tabX: jnp.ndarray, tabY: jnp.ndarray) -> jnp.ndarray:
    """Shared-vertex counts by direct slot comparison: C[b, x, y] = number
    of ``tabX[b, x]`` vertices appearing in ``tabY[b, y]`` — the meet-mode
    contract of ``ref.relation_counts_meet`` without the ``nvl``-wide
    one-hot inner dimension (arity passes of ``(B, NX, NY, ay)``
    comparisons instead of a ``(B, NX, nvl, NY)`` matmul)."""
    C = jnp.zeros(tabX.shape[:2] + (tabY.shape[1],), dtype=jnp.int32)
    for i in range(tabX.shape[2]):
        xi = tabX[:, :, i]                                    # (B, NX)
        m = (xi[:, :, None, None] == tabY[:, None, :, :]).any(-1)
        m = m & (xi >= 0)[:, :, None]
        C = C + m.astype(jnp.int32)
    return C


def sparse_arm_ok(relation: str, tabX, tabY, nvl: int) -> bool:
    """True when ``relation`` has a sparse entry-assembly arm AND its entry
    keys fit int32. Shared by the xla fused dispatch and the Pallas entry
    kernels so both backends take the sparse/dense fork under identical
    conditions: EE/FF (count predicates, not membership) and oversize-key
    meshes fall back to the pairwise/one-hot dense arm on BOTH."""
    if relation == "VV":
        return nvl * nvl + nvl < 2 ** 31
    if relation in ("VE", "VF", "VT"):
        NY = tabY.shape[1]
        return nvl * NY + NY < 2 ** 31
    if relation == "TT":
        NT = tabX.shape[1]
        return nvl ** 3 < 2 ** 31 and NT * NT + NT < 2 ** 31
    if relation in ("EF", "ET", "FT"):
        NX, NY = tabX.shape[1], tabY.shape[1]
        ax = tabX.shape[2]
        return nvl ** ax * 2 < 2 ** 31 and NX * NY + NY < 2 ** 31
    return False


@functools.partial(
    jax.jit, static_argnames=("relation", "nvl", "deg", "assembly"))
def _relation_block_fused(relation, tabX, tabY, col_global, nvl, deg,
                          assembly="sparse"):
    """counts/entries -> (M, L) fused into ONE jitted computation, so the
    engine pays a single dispatch per launch and the whole epilogue is one
    in-flight future (async producer contract, see core/engine.py).

    Per-relation specialization: the driver hot-path relations
    (VV/VE/VF/VT/TT/EF/ET/FT) are assembled sparsely by entry inversion /
    sort join — O(table entries) instead of the O(rows·cols) dense mask +
    top_k compaction — and the remaining relations count shared vertices by
    direct slot comparison. All arms are algebraically identical to the
    one-hot counts + predicate + compaction, hence bit-identical (M, L).
    ``assembly="dense"`` forces the dense tail for every relation — the
    benchmark A/B arm (bench_kernel_params.py), never the engine default."""
    colg = col_global.astype(jnp.int32)
    if assembly == "sparse" and sparse_arm_ok(relation, tabX, tabY, nvl):
        if relation == "VV":
            return _block_vv(tabX, colg, nvl, deg)
        if relation in ("VE", "VF", "VT"):
            return _block_member_v(tabY, colg, nvl, deg)
        if relation == "TT":
            return _block_tt(tabX, colg, nvl, deg)
        return _block_sub_join(tabX, tabY, colg, nvl, deg)
    k, exact = PREDICATE[relation]
    if relation == "VV":
        C = ref.relation_counts_vv(tabX, nvl)
        mask = _predicate_impl(C, k, exact, exclude_diag=True)
    else:
        C = _counts_pairwise(tabX, tabY)
        mask = _predicate_impl(C, k, exact, exclude_diag=False)
    return _compact_impl(mask, colg, deg)


def relation_block(
    relation: str,
    tabX: jnp.ndarray,          # (B, NX, ax) rows table (or T_local for VV)
    tabY: jnp.ndarray,          # (B, NY, ay) cols table (ignored for VV)
    col_global: jnp.ndarray,    # (B, NY) local->global map for columns
    nvl: int,
    deg: Optional[int] = None,
    backend: str = "xla",
    block_x: int = 256,
    block_y: int = 256,
    vv_block: Optional[int] = None,
    assembly: str = "sparse",
    compile_on: Sequence = (),
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full pipeline: entries (or counts -> predicate) -> (M, L).

    For VV, pass ``tabX = tabY = T_local`` and ``col_global = LV_global``;
    rows/cols are local vertices. Returns (M, L) with global ids. The xla
    backend runs the whole pipeline as one fused jit dispatch; the pallas
    backends emit (M, L) directly from the sparse entry-assembly kernels
    (``relation_entries_pallas``) under the SAME per-relation guards as the
    xla arm, falling back to the one-hot counts kernel + jitted epilogue
    for EE/FF and oversize keys. ``assembly="dense"`` forces the old dense
    epilogue everywhere (the benchmark A/B arm); ``vv_block`` overrides the
    VV counts-kernel block (defaults to ``block_x``) — both are autotune
    surface (launch/autotune.py). ``compile_on`` lists devices the xla arm
    is first compiled for, all at once, at these shapes (the sharded
    engine's shard devices, see
    :func:`~repro.distributed.sharding.compile_on_devices`)."""
    k, exact = PREDICATE[relation]
    deg = DEFAULT_DEG[relation] if deg is None else deg
    if backend == "xla":
        args = (relation, tabX, tabY, col_global, nvl, deg, assembly)
        if compile_on:
            compile_on_devices(_relation_block_fused, args, compile_on)
        return _relation_block_fused(*args)
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown backend {backend!r}")
    with pallas_refusal(relation, backend):
        if assembly == "sparse" and sparse_arm_ok(relation, tabX, tabY, nvl):
            return relation_entries_pallas(
                relation, tabX, tabY, col_global, nvl=nvl, deg=deg,
                interpret=backend == "pallas_interpret")
        if relation == "VV":
            C = counts_vv(tabX, nvl, backend=backend,
                          block=vv_block if vv_block else block_x)
            mask = predicate(C, k, exact, exclude_diag=True)
        else:
            C = counts_meet(tabX, tabY, nvl, backend=backend,
                            block_x=block_x, block_y=block_y)
            mask = predicate(C, k, exact, exclude_diag=False)
    return compact(mask, col_global.astype(jnp.int32), deg)


def _counts_vv_host(T_local: np.ndarray, nvl: int) -> np.ndarray:
    """Host mirror of ``ref.relation_counts_vv``: shared-tet counts
    C (B, nvl, nvl) via per-batch one-hot incidence matmul."""
    B, NT, arity = T_local.shape
    onehot = np.zeros((B, NT, nvl), dtype=np.int32)
    for a in range(arity):
        v = T_local[:, :, a]
        bi, ti = np.nonzero(v >= 0)
        onehot[bi, ti, v[bi, ti]] = 1
    return np.einsum("btv,btw->bvw", onehot, onehot).astype(np.int32)


def _counts_pairwise_host(tabX: np.ndarray, tabY: np.ndarray) -> np.ndarray:
    """Host mirror of :func:`_counts_pairwise`: C[b, x, y] = number of
    ``tabX[b, x]`` slots whose vertex appears in ``tabY[b, y]``."""
    B, NX, ax = tabX.shape
    NY = tabY.shape[1]
    C = np.zeros((B, NX, NY), dtype=np.int32)
    for i in range(ax):
        xi = tabX[:, :, i]                                    # (B, NX)
        m = np.zeros((B, NX, NY), dtype=bool)
        for j in range(tabY.shape[2]):
            m |= xi[:, :, None] == tabY[:, None, :, j]
        m &= (xi >= 0)[:, :, None]
        C += m.astype(np.int32)
    return C


def relation_block_host(
    relation: str,
    tabX: np.ndarray,
    tabY: np.ndarray,
    col_global: np.ndarray,
    nvl: int,
    deg: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-numpy host arm of :func:`relation_block` (docs/DESIGN.md §12).

    The degraded-production path when a relation's circuit breaker is
    open: dense counts -> predicate -> compaction entirely on the host,
    algebraically identical to the device arms and therefore bit-identical
    (M, L) — the chaos fuzz hashes degraded runs against fault-free ones.
    ``L`` is the TRUE per-row count (it may exceed ``deg``), so the
    engine's :class:`RelationWidthError` overflow check still fires."""
    k, exact = PREDICATE[relation]
    deg = DEFAULT_DEG[relation] if deg is None else deg
    tabX = np.asarray(tabX)
    tabY = np.asarray(tabY)
    colg = np.asarray(col_global).astype(np.int32)
    if relation == "VV":
        C = _counts_vv_host(tabX, nvl)
        mask = (C == k) if exact else (C >= k)
        n = min(C.shape[1], C.shape[2])
        mask[:, np.arange(n), np.arange(n)] = False
    else:
        C = _counts_pairwise_host(tabX, tabY)
        mask = (C == k) if exact else (C >= k)
    B, R, N = mask.shape
    M = np.full((B, R, deg), -1, dtype=np.int32)
    L = mask.sum(axis=2).astype(np.int32)
    for b in range(B):
        for r in np.nonzero(L[b])[0]:
            cols = np.flatnonzero(mask[b, r])[:deg]   # ascending local order
            M[b, r, :len(cols)] = colg[b, cols]
    return M, L


def completion_gather(
    pool_M: jnp.ndarray,
    pool_L: jnp.ndarray,
    inv_seg: jnp.ndarray,
    inv_gid: jnp.ndarray,
    inv_row: jnp.ndarray,
    pair_slot: jnp.ndarray,
    pair_seg: jnp.ndarray,
    pair_gid: jnp.ndarray,
    pair_at: jnp.ndarray,
    deg_out: int,
    backend: str = "xla",
    inv_key: Optional[jnp.ndarray] = None,
    n_global: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-side cross-segment completion gather (docs/DESIGN.md §5).

    Resolves ``(segment, gid)`` pairs to block rows by batched binary search
    over the engine's device inverse maps, gathers the rows from the stacked
    block pool, and unions/dedups/compacts them into padded ``(M, L)`` — all
    on the accelerator. Backend dispatch mirrors :func:`relation_block`:
    ``"xla"`` is one fused jit (``jnp.searchsorted`` oracle); ``"pallas"`` /
    ``"pallas_interpret"`` run the resolve+gather as a Pallas grid with the
    union epilogue jitted. See ``kernels/completion_gather.py``."""
    from .completion_gather import gather_union
    return gather_union(pool_M, pool_L, inv_seg, inv_gid, inv_row,
                        pair_slot, pair_seg, pair_gid, pair_at,
                        deg_out=deg_out, backend=backend,
                        inv_key=inv_key, n_global=n_global)
