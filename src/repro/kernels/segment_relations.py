"""Pallas TPU kernels for segment-local topological relation extraction.

This is the TPU-native replacement for GALE's CUDA worker-producer kernels
(paper §4.6, Algorithms 1-2). Two kernel families live here:

**Sparse entry assembly** (the producer hot path, mirroring the xla arm in
``ops.py``): per-relation kernels emit the paper's padded ``(M, L)``
relation arrays directly. Each grid step handles one batched segment: it
generates the relation's entry list in VMEM (table-as-entries for VE/VF/VT,
ordered tet vertex pairs for VV, canonical-face / sub-simplex sort joins for
TT and EF/ET/FT), lane-sorts it with an in-kernel bitonic network, dedups
equal ``(row, order)`` keys, and resolves per-row segment boundaries with a
vectorized binary search — no dense ``(rows, cols)`` counts block and no
``top_k`` epilogue ever materialize. Bit-identical to ``ops.py``'s
``_invert_entries`` pipeline for every relation.

**One-hot counts** (the dense fallback, and the EE/FF arm): each grid step
builds one-hot vertex-incidence blocks in VMEM and contracts them on the
MXU:

    meet mode:  C = Ax · Ayᵀ    Ax[x, v] = 1 iff local vertex v ∈ tabX[x]
    vv   mode:  C = Av · Avᵀ    Av[i, t] = 1 iff local vertex i ∈ tet t

``C[x, y]`` is the shared-vertex count (meet) or shared-tet count (vv); a
cheap predicate epilogue outside the kernel (``ops.py``) turns counts into
boolean relations and compacts them into ``(M, L)`` via ``top_k``.
Deduplication is inherent to counting — the role played by ``atomicCAS`` on
the GPU.

Counts grid: ``(segment, row_block, col_block)``; tables are passed
transposed, ``(B, arity, N)``, so the last (lane) dimension is the simplex
axis. Entry-assembly grid: ``(segment,)`` with whole-table blocks. Inputs
need NOT be multiples of 128: the counts wrappers pad the simplex axes up to
a 128 multiple with ``-1`` rows and slice the result (the tail block is
explicit padding, never an over-covering grid step), and the entry kernels
pad their entry lanes to a power of two with explicit ``_BIG`` sentinel
masks.

On a TPU v5e the counts kernels compile; the entry-assembly kernels do not
(their binary search and ``M`` fill gather across the whole lane vector,
and Mosaic gathers only within one (8, 128) vreg), so
``ops.relation_block`` raises ``KernelCompileError`` for them on
``backend="pallas"``.

Block sizes are the TPU analogue of the paper's ``t_s``/``t_b``/``n_b``
kernel parameters; ``launch/autotune.py`` derives candidates from
the roofline model and ``benchmarks/bench_kernel_params.py`` measures them.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG = np.int32(np.iinfo(np.int32).max)


def _round_up128(n: int) -> int:
    return ((max(int(n), 1) + 127) // 128) * 128


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _pick_block(n: int, target: int) -> int:
    """Largest multiple of 128 that divides the 128-padded ``n`` and is
    <= ``target``. ``n`` need not be a multiple of 128 (or of the block):
    the counts wrappers pad the simplex axis to ``_round_up128(n)`` before
    launching, so the grid covers the padded extent exactly and the tail
    never over-covers unpadded memory."""
    n = _round_up128(n)
    best = 128
    b = 128
    while b <= min(n, target):
        if n % b == 0:
            best = b
        b += 128
    return best


# ---------------------------------------------------------------------------
# One-hot counts kernels (dense fallback arm).

def _meet_kernel(tabx_ref, taby_ref, out_ref, *, nvl: int, ax: int, ay: int):
    """One (row_block x col_block) tile of shared-vertex counts."""
    def build(tab_ref, arity, nrows):
        acc = None
        for c in range(arity):
            col = tab_ref[0, c, :]  # (nrows,) local vertex ids, -1 padded
            eq = col[:, None] == jax.lax.broadcasted_iota(
                jnp.int32, (nrows, nvl), 1)
            acc = eq if acc is None else jnp.logical_or(acc, eq)
        return acc.astype(jnp.float32)

    Ax = build(tabx_ref, ax, tabx_ref.shape[2])  # (NXb, nvl)
    Ay = build(taby_ref, ay, taby_ref.shape[2])  # (NYb, nvl)
    C = jax.lax.dot_general(
        Ax, Ay, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[0, :, :] = C.astype(jnp.int32)


def _vv_kernel(tet_ref, out_ref, *, blk: int):
    """One (vertex_block x vertex_block) tile of shared-tet counts."""
    i0 = pl.program_id(1) * blk
    j0 = pl.program_id(2) * blk
    nt = tet_ref.shape[2]

    def build(base):
        acc = None
        ids = base + jax.lax.broadcasted_iota(jnp.int32, (blk, nt), 0)
        for c in range(4):
            row = tet_ref[0, c, :]  # (NT,)
            eq = ids == row[None, :]
            acc = eq if acc is None else jnp.logical_or(acc, eq)
        return acc.astype(jnp.float32)

    Ai = build(i0)  # (blk, NT)
    Aj = build(j0)
    C = jax.lax.dot_general(
        Ai, Aj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[0, :, :] = C.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("nvl", "block_x", "block_y", "interpret"))
def relation_counts_meet_pallas(
    tabX_t: jnp.ndarray,   # (B, ax, NX) int32, transposed table, -1 padded
    tabY_t: jnp.ndarray,   # (B, ay, NY)
    *, nvl: int, block_x: int = 256, block_y: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """C (B, NX, NY) int32 shared-vertex counts."""
    B, ax, NX = tabX_t.shape
    _, ay, NY = tabY_t.shape
    # explicit tail masking: pad the simplex axes to a 128 multiple with -1
    # (never a valid vertex) and slice the padded rows/cols back off below
    NXp, NYp = _round_up128(NX), _round_up128(NY)
    if NXp != NX:
        tabX_t = jnp.pad(tabX_t, ((0, 0), (0, 0), (0, NXp - NX)),
                         constant_values=-1)
    if NYp != NY:
        tabY_t = jnp.pad(tabY_t, ((0, 0), (0, 0), (0, NYp - NY)),
                         constant_values=-1)
    bx = _pick_block(NXp, block_x)
    by = _pick_block(NYp, block_y)
    grid = (B, NXp // bx, NYp // by)
    kernel = functools.partial(_meet_kernel, nvl=nvl, ax=ax, ay=ay)
    C = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ax, bx), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, ay, by), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bx, by), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, NXp, NYp), jnp.int32),
        interpret=interpret,
    )(tabX_t, tabY_t)
    return C[:, :NX, :NY]


@functools.partial(
    jax.jit, static_argnames=("nvl", "block", "interpret"))
def relation_counts_vv_pallas(
    T_local_t: jnp.ndarray,  # (B, 4, NT) int32 transposed tet table
    *, nvl: int, block: int = 128, interpret: bool = False,
) -> jnp.ndarray:
    """C (B, nvl, nvl) int32 shared-tet counts between local vertices."""
    B, four, NT = T_local_t.shape
    assert four == 4
    # explicit tail masking: pad the vertex axis to a 128 multiple; local
    # vertex ids are < nvl, so the padded rows/cols count zero shared tets
    nvlp = _round_up128(nvl)
    blk = _pick_block(nvlp, block)
    grid = (B, nvlp // blk, nvlp // blk)
    kernel = functools.partial(_vv_kernel, blk=blk)
    C = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 4, NT), lambda b, i, j: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, nvlp, nvlp), jnp.int32),
        interpret=interpret,
    )(T_local_t)
    return C[:, :nvl, :nvl]


# ---------------------------------------------------------------------------
# Sparse entry-assembly kernels (docs/DESIGN.md §4).
#
# In-kernel building blocks. Everything operates on (1, E) int32 lane
# vectors with E a power of two; invalid lanes carry the _BIG sentinel.
# The TPU has no sort/scan/scatter primitives inside Pallas, so:
#   - sorting is a bitonic compare-exchange network whose partner exchange
#     (lane XOR j) is two lane rotations and a select, not a gather;
#   - the segmented scan of ops._invert_entries becomes a per-row binary
#     search over the sorted keys (same idiom as completion_gather.py);
#   - scatter-free placement: row r's entries sit at sorted positions
#     [starts[r], starts[r+1]), so M fills with one clamped gather.


def _gather_lanes(src: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """src (N,) gathered at idx (1, Q) -> (1, Q)."""
    return jnp.take(src, idx.reshape(-1)).reshape(1, -1)


def _pad_lanes(x: jnp.ndarray, E: int, fill) -> jnp.ndarray:
    n = x.shape[-1]
    if n == E:
        return x
    return jnp.concatenate(
        [x, jnp.full((1, E - n), fill, x.dtype)], axis=1)


def _roll_lanes(x: jnp.ndarray, shift: int) -> jnp.ndarray:
    """``jnp.roll(x, shift, axis=1)`` on (1, E) lanes as a TPU lane
    rotation: ``out[i] = x[(i - shift) mod E]``."""
    return pltpu.roll(x, shift % x.shape[1], 1)


def _bitonic_sort_lanes(key, payloads):
    """Bitonic sort of (1, E) lanes by ``key`` ascending (E a power of two);
    ``payloads`` ride along. The partner of lane i is lane ``i XOR j``:
    ``i + j`` where bit j of i is clear, ``i - j`` where it is set, so the
    exchange is two lane rotations and a select — no gathers. Ties keep
    both lanes in place; every key family sorted here is either tie-free
    or tie-insensitive (equal keys always carry equal payloads, or only
    sentinel lanes tie), so an unstable network is bit-identical to
    ``jax.lax.sort`` downstream."""
    _, E = key.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    k = 2
    while k <= E:
        up = (lane & k) == 0
        j = k // 2
        while j >= 1:
            low = (lane & j) == 0        # this lane is the pair's low index

            def partner(x, j=j, low=low):
                return jnp.where(low, _roll_lanes(x, E - j),
                                 _roll_lanes(x, j))
            pk = partner(key)
            take_min = low == up
            want = jnp.where(take_min, pk < key, pk > key)
            key = jnp.where(want, pk, key)
            payloads = [jnp.where(want, partner(p), p) for p in payloads]
            j //= 2
        k *= 2
    return key, payloads


def _cummax_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running max along lanes (values >= -1), via log2(E)
    shift-and-max steps — the in-kernel stand-in for jax.lax.cummax."""
    _, E = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    d = 1
    while d < E:
        shifted = jnp.where(lane >= d, _roll_lanes(x, d), jnp.int32(-1))
        x = jnp.maximum(x, shifted)
        d *= 2
    return x


def _lower_bound_lanes(keys: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Per-lane lower bound: first index i with keys[0, i] >= q, for keys
    (1, E) ascending and queries q (1, Q). Vectorized bisection with one
    lane gather per step (completion_gather.py idiom)."""
    _, E = keys.shape
    lo = jnp.zeros(q.shape, jnp.int32)
    hi = jnp.full(q.shape, E, jnp.int32)
    for _ in range(int(E).bit_length() + 1):
        mid = (lo + hi) // 2
        kv = _gather_lanes(keys[0, :], jnp.clip(mid, 0, E - 1))
        # freeze closed intervals: once lo == hi the clamped gather would
        # re-read keys[E-1] and walk lo past E on a fully-valid lane vector
        go = lo < hi
        right = go & (kv < q)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(go & ~right, mid, hi)
    return lo


def _emit_entries(key, val, M_ref, L_ref, *, R: int, O: int, deg: int):
    """In-kernel port of ``ops._invert_entries``: entry lanes -> one
    segment's ``(M (R, deg), L (R))`` block.

    ``key = row * O + order`` for valid entries, ``_BIG`` otherwise (the
    caller guarantees ``R * O + O < 2**31`` — the same oversize-key guards
    as the xla arm). Pipeline: sort by key; mark duplicate adjacent keys
    (entries sharing ``(row, order)`` store/count once) and resort them to
    the back as ``_BIG``; binary-search the R+1 row boundaries ``r * O``;
    ``L`` is the TRUE per-row count (boundary difference, overflow past
    ``deg`` stays detectable by the engine's width check) and ``M[r, d]``
    gathers ``val[starts[r] + d]`` for ``d < min(L[r], deg)`` — ascending
    local order, exactly the xla arm's scatter."""
    _, E = key.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    key, (val,) = _bitonic_sort_lanes(key, [val])
    dup = (lane > 0) & (key == _roll_lanes(key, 1))
    key = jnp.where(dup, _BIG, key)
    key, (val,) = _bitonic_sort_lanes(key, [val])

    queries = jax.lax.broadcasted_iota(jnp.int32, (1, R + 1), 1) * O
    starts = _lower_bound_lanes(key, queries)       # (1, R+1)
    L = starts[:, 1:] - starts[:, :-1]              # (1, R) true counts
    L_ref[0, :, :] = L

    d2 = jax.lax.broadcasted_iota(jnp.int32, (R, deg), 1)
    st = starts[0, :R].reshape(R, 1)
    cnt = L[0, :].reshape(R, 1)
    idx = jnp.clip(st + d2, 0, E - 1)
    vals = jnp.take(val[0, :], idx.reshape(-1)).reshape(R, deg)
    M_ref[0, :, :] = jnp.where(d2 < jnp.minimum(cnt, deg), vals, -1)


def _sort2(a, b):
    return jnp.minimum(a, b), jnp.maximum(a, b)


def _sort3(a, b, c):
    a, b = _sort2(a, b)
    b, c = _sort2(b, c)
    a, b = _sort2(a, b)
    return a, b, c


def _sort4(a, b, c, d):
    a, b = _sort2(a, b)
    c, d = _sort2(c, d)
    a, c = _sort2(a, c)
    b, d = _sort2(b, d)
    b, c = _sort2(b, c)
    return a, b, c, d


def _sort_rows(rows):
    if len(rows) == 1:
        return rows
    if len(rows) == 2:
        return list(_sort2(*rows))
    if len(rows) == 3:
        return list(_sort3(*rows))
    return list(_sort4(*rows))


_TET_FACES = ((0, 1, 2), (0, 1, 3), (0, 2, 3), (1, 2, 3))


def _member_entries_kernel(taby_ref, colg_ref, M_ref, L_ref, *,
                           NY: int, ay: int, E: int, nvl: int, deg: int):
    """VE/VF/VT: the (NY, arity) table IS the entry list — local vertex v
    relates to simplex y iff v ∈ verts(y) (exact C == 1: a simplex lists
    distinct vertices)."""
    order = jax.lax.broadcasted_iota(jnp.int32, (1, NY), 1)
    colg = colg_ref[0, 0, :].reshape(1, NY)
    keys, vals = [], []
    for c in range(ay):
        v = taby_ref[0, c, :].reshape(1, NY)
        keys.append(jnp.where(v >= 0, v * NY + order, _BIG))
        vals.append(colg)
    key = _pad_lanes(jnp.concatenate(keys, axis=1), E, _BIG)
    val = _pad_lanes(jnp.concatenate(vals, axis=1), E, 0)
    _emit_entries(key, val, M_ref, L_ref, R=nvl, O=NY, deg=deg)


def _vv_entries_kernel(tet_ref, colg_ref, M_ref, L_ref, *,
                       NT: int, E: int, nvl: int, deg: int):
    """VV: the 12 ordered vertex pairs of each tet are the entries (C >= 1
    off-diagonal — a tet's vertices are distinct, so the diagonal never
    appears; repeated pairs from different tets dedup in _emit_entries)."""
    colg = colg_ref[0, 0, :]
    rows4 = [tet_ref[0, c, :].reshape(1, NT) for c in range(4)]
    keys, vals = [], []
    for a in range(4):
        for b in range(4):
            if a == b:
                continue
            va, vb = rows4[a], rows4[b]
            ok = (va >= 0) & (vb >= 0)
            keys.append(jnp.where(ok, va * nvl + vb, _BIG))
            vals.append(_gather_lanes(colg, jnp.maximum(vb, 0)))
    key = _pad_lanes(jnp.concatenate(keys, axis=1), E, _BIG)
    val = _pad_lanes(jnp.concatenate(vals, axis=1), E, 0)
    _emit_entries(key, val, M_ref, L_ref, R=nvl, O=nvl, deg=deg)


def _tt_entries_kernel(tet_ref, colg_ref, M_ref, L_ref, *,
                       NT: int, EJ: int, E: int, nvl: int, deg: int):
    """TT via a sort join on canonical face keys: two distinct tets relate
    iff they share a face (exact C == 3). Each tet contributes its four
    sorted vertex triples; after the lane sort, equal adjacent keys are the
    shared faces (a face has at most two cofacet tets), yielding both
    directed entries."""
    w = _sort_rows([tet_ref[0, c, :].reshape(1, NT) for c in range(4)])
    valid = w[0] >= 0                  # -1 padding sorts first
    tid = jax.lax.broadcasted_iota(jnp.int32, (1, NT), 1)
    fkeys, tids = [], []
    for i, j, k in _TET_FACES:
        fk = (w[i] * nvl + w[j]) * nvl + w[k]
        fkeys.append(jnp.where(valid, fk, _BIG))
        tids.append(tid)
    fkey = _pad_lanes(jnp.concatenate(fkeys, axis=1), EJ, _BIG)
    tjd = _pad_lanes(jnp.concatenate(tids, axis=1), EJ, 0)
    fkey, (tjd,) = _bitonic_sort_lanes(fkey, [tjd])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, EJ), 1)
    nk = _roll_lanes(fkey, -1)
    nt = _roll_lanes(tjd, -1)
    eq = (fkey == nk) & (fkey != _BIG) & (lane < EJ - 1)
    colg = colg_ref[0, 0, :]
    k1 = jnp.where(eq, tjd * NT + nt, _BIG)
    v1 = _gather_lanes(colg, jnp.maximum(nt, 0))
    k2 = jnp.where(eq, nt * NT + tjd, _BIG)
    v2 = _gather_lanes(colg, jnp.maximum(tjd, 0))
    key = _pad_lanes(jnp.concatenate([k1, k2], axis=1), E, _BIG)
    val = _pad_lanes(jnp.concatenate([v1, v2], axis=1), E, 0)
    _emit_entries(key, val, M_ref, L_ref, R=NT, O=NT, deg=deg)


def _sub_entries_kernel(tabx_ref, taby_ref, colg_ref, M_ref, L_ref, *,
                        NX: int, NY: int, ax: int, ay: int,
                        combos: tuple, E: int, nvl: int, deg: int):
    """EF/ET/FT via a sort join: x relates to y iff every vertex of x lies
    in y (exact C == arity(x) — x is a boundary sub-simplex of y). X rows
    contribute their canonical sorted key once (LSB 0); each y contributes
    the keys of its arity(x)-vertex subsets (LSB 1, sorting after the equal
    x key). Every y entry resolves its x row from the latest x entry seen
    (running max over lanes) and re-checks the key."""
    xs = _sort_rows([tabx_ref[0, c, :].reshape(1, NX) for c in range(ax)])
    kx = xs[0]
    for i in range(1, ax):
        kx = kx * nvl + xs[i]
    kx = jnp.where(xs[0] >= 0, kx * 2, _BIG)
    px = jax.lax.broadcasted_iota(jnp.int32, (1, NX), 1)

    ys = _sort_rows([taby_ref[0, c, :].reshape(1, NY) for c in range(ay)])
    oky = ys[0] >= 0
    py = jax.lax.broadcasted_iota(jnp.int32, (1, NY), 1)
    keys, pays = [kx], [px]
    for comb in combos:
        k = ys[comb[0]]
        for c in comb[1:]:
            k = k * nvl + ys[c]
        keys.append(jnp.where(oky, k * 2 + 1, _BIG))
        pays.append(py)
    key = _pad_lanes(jnp.concatenate(keys, axis=1), E, _BIG)
    payload = _pad_lanes(jnp.concatenate(pays, axis=1), E, 0)
    key, (payload,) = _bitonic_sort_lanes(key, [payload])

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    is_x = (key != _BIG) & (key % 2 == 0)     # key parity encodes the side
    lastX = _cummax_lanes(jnp.where(is_x, lane, -1))
    take = jnp.maximum(lastX, 0)
    xkey = _gather_lanes(key[0, :], take)
    ok = (~is_x) & (key != _BIG) & (lastX >= 0) & (xkey == key - 1)
    row = _gather_lanes(payload[0, :], take)
    order = jnp.where(ok, payload, 0)
    val = _gather_lanes(colg_ref[0, 0, :], order)
    ekey = jnp.where(ok, row * NY + order, _BIG)
    _emit_entries(ekey, val, M_ref, L_ref, R=NX, O=NY, deg=deg)


@functools.partial(
    jax.jit, static_argnames=("relation", "nvl", "deg", "interpret"))
def relation_entries_pallas(
    relation: str,
    tabX: jnp.ndarray,          # (B, NX, ax) rows table (T_local for VV/TT)
    tabY: jnp.ndarray,          # (B, NY, ay) cols table (ignored for VV/TT)
    col_global: jnp.ndarray,    # (B, NY) local -> global map for columns
    *, nvl: int, deg: int, interpret: bool = False,
) -> tuple:
    """Sparse Pallas producer: ``(M (B, R, deg), L (B, R))`` emitted
    directly, one batched segment per grid step, bit-identical to the xla
    arm (``ops._relation_block_fused``) for every dispatched relation.
    Callers (``ops.relation_block``) route EE/FF and oversize-key cases to
    the one-hot counts fallback, mirroring the xla guards."""
    B = tabX.shape[0]
    # (B, N) operands travel as (B, 1, N): a TPU block's last two dims
    # must be (8, 128)-aligned or span the array, and (1, N) spans it
    colg = col_global.astype(jnp.int32)[:, None, :]
    if relation in ("VE", "VF", "VT"):
        _, NY, ay = tabY.shape
        E = _next_pow2(ay * NY)
        kernel = functools.partial(
            _member_entries_kernel, NY=NY, ay=ay, E=E, nvl=nvl, deg=deg)
        ins = [jnp.swapaxes(tabY, 1, 2), colg]
        in_specs = [pl.BlockSpec((1, ay, NY), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, NY), lambda b: (b, 0, 0))]
        R = nvl
    elif relation == "VV":
        _, NT, four = tabX.shape
        E = _next_pow2(12 * NT)
        kernel = functools.partial(
            _vv_entries_kernel, NT=NT, E=E, nvl=nvl, deg=deg)
        ins = [jnp.swapaxes(tabX, 1, 2), colg]
        in_specs = [pl.BlockSpec((1, four, NT), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, colg.shape[2]), lambda b: (b, 0, 0))]
        R = nvl
    elif relation == "TT":
        _, NT, four = tabX.shape
        EJ = _next_pow2(4 * NT)
        E = _next_pow2(2 * EJ)
        kernel = functools.partial(
            _tt_entries_kernel, NT=NT, EJ=EJ, E=E, nvl=nvl, deg=deg)
        ins = [jnp.swapaxes(tabX, 1, 2), colg]
        in_specs = [pl.BlockSpec((1, four, NT), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, NT), lambda b: (b, 0, 0))]
        R = NT
    elif relation in ("EF", "ET", "FT"):
        _, NX, ax = tabX.shape
        _, NY, ay = tabY.shape
        combos = tuple(itertools.combinations(range(ay), ax))
        E = _next_pow2(NX + NY * len(combos))
        kernel = functools.partial(
            _sub_entries_kernel, NX=NX, NY=NY, ax=ax, ay=ay,
            combos=combos, E=E, nvl=nvl, deg=deg)
        ins = [jnp.swapaxes(tabX, 1, 2), jnp.swapaxes(tabY, 1, 2), colg]
        in_specs = [pl.BlockSpec((1, ax, NX), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, ay, NY), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, NY), lambda b: (b, 0, 0))]
        R = NX
    else:
        raise KeyError(f"no sparse entry kernel for relation {relation!r}")
    M, L = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, R, deg), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 1, R), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, R, deg), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, R), jnp.int32)],
        interpret=interpret,
    )(*ins)
    return M, L[:, 0, :]
