"""Device-side cross-segment completion gather (docs/DESIGN.md §5).

The host completion pipeline (`core/adjacency.py`) used to read every
consulted block back through ``np.asarray`` and union rows in numpy. This
module keeps the whole gather on the accelerator: given the engine's
device-resident inverse maps and a stacked pool of produced relation blocks,
it

  1. resolves every planned ``(segment, global id)`` pair to its local block
     row by **batched binary search** over the sorted inverse maps,
  2. gathers the pair's ``(M, L)`` row from the block pool, and
  3. performs the union / self-removal / dedup / compaction into the paper's
     padded ``(M, L)`` layout with two lane-wise sorts,

returning one device array per completion batch — a single host round trip
instead of one per consulted block.

Backends (the engine's existing ``backend`` knob):

  - ``"xla"``              : fused jit — the row resolve is a
                             ``jnp.searchsorted`` oracle over precomputed
                             combined i32 keys when they fit (``inv_key``),
                             else an i32-safe lexicographic binary search.
  - ``"pallas"`` /
    ``"pallas_interpret"`` : the resolve+gather runs as a Pallas grid over
                             pair blocks (inverse maps and block pool
                             resident in VMEM), with the union epilogue as a
                             shared jitted computation — the same split as
                             ``segment_relations.py``. The TPU compiler
                             refuses this kernel (in-kernel gathers across
                             vregs, and a whole-pool VMEM block), so
                             ``"pallas"`` raises ``KernelCompileError``.

All ids are i32 (the inverse maps are staged as split ``(seg, gid, row)``
columns precisely so no x64 is needed on device). ``BIG`` (i32 max) is the
in-flight sentinel for removed/invalid entries; it sorts last, so two
ascending sorts with a duplicate-mask pass in between yield "all unique
neighbours, ascending" — the role ``top_k`` plays in ``ops.compact``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .ops import pallas_refusal

BIG = np.int32(np.iinfo(np.int32).max)


def _bisect_steps(n: int) -> int:
    """Iterations for a vectorized bisection over n sorted keys."""
    return int(np.ceil(np.log2(max(n, 2)))) + 1


def _resolve_key(inv_key, inv_row, seg, gid, n_global):
    """Combined-i32-key row resolve: one jnp.searchsorted over the sorted
    ``seg * n_global + gid`` keys. Shared by resolve_rows and the fused
    xla completion pipeline."""
    q = seg * jnp.int32(n_global) + gid
    pos = jnp.searchsorted(inv_key, q)
    pos_c = jnp.minimum(pos, inv_key.shape[0] - 1)
    return jnp.where(inv_key[pos_c] == q, inv_row[pos_c], -1)


def _resolve_lex(inv_seg, inv_gid, inv_row, seg, gid):
    """Lexicographic (segment, gid) binary search — i32-safe for any mesh
    size. Shared trace between the xla fallback and tests."""
    K = inv_seg.shape[0]
    lo = jnp.zeros_like(seg)
    hi = jnp.full_like(seg, K)
    for _ in range(_bisect_steps(K)):
        mid = (lo + hi) // 2
        mid_c = jnp.minimum(mid, K - 1)
        ks = inv_seg[mid_c]
        kg = inv_gid[mid_c]
        less = (ks < seg) | ((ks == seg) & (kg < gid))
        upd = mid < hi
        lo = jnp.where(upd & less, mid + 1, lo)
        hi = jnp.where(upd & ~less, mid, hi)
    pos = jnp.minimum(lo, K - 1)
    found = (lo < K) & (inv_seg[pos] == seg) & (inv_gid[pos] == gid)
    return jnp.where(found, inv_row[pos], -1)


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("n_global",))
def _resolve_jit(inv_seg, inv_gid, inv_row, inv_key, seg, gid, n_global):
    if inv_key is not None:
        return _resolve_key(inv_key, inv_row, seg, gid, n_global)
    return _resolve_lex(inv_seg, inv_gid, inv_row, seg, gid)


def resolve_rows(inv_seg, inv_gid, inv_row, seg, gid,
                 inv_key=None, n_global: int = 0) -> jnp.ndarray:
    """Batched ``(segment, gid) -> local block row`` on device (-1 absent).

    With ``inv_key`` (combined i32 keys, only staged when
    ``n_segments * n_global < 2**31``) this is one ``jnp.searchsorted``;
    without it, a lexicographic binary search over the split columns."""
    if inv_seg.shape[0] == 0:
        return jnp.full(seg.shape, -1, jnp.int32)
    return _resolve_jit(inv_seg, inv_gid, inv_row, inv_key, seg, gid,
                        int(n_global))


# -- union / self-removal / dedup / compaction epilogue ----------------------


def _union_impl(cand, cand_len, pair_gid, pair_at, deg_out):
    """cand (P, degp) gathered rows, cand_len (P,) their valid lengths,
    pair_at (n, w) pair index per query slot (-1 empty). Returns
    (M (n, deg_out), L (n,), raw, kept) — L is the TRUE unique count (may
    exceed deg_out; the caller raises on that overflow)."""
    degp = cand.shape[1]
    col = jnp.arange(degp, dtype=jnp.int32)[None, :]
    valid = (col < cand_len[:, None]) & (cand >= 0)
    raw = valid.sum()
    vals = jnp.where(valid & (cand != pair_gid[:, None]), cand, BIG)
    buck = jnp.where(pair_at[..., None] >= 0,
                     vals[jnp.clip(pair_at, 0)], BIG)     # (n, w, degp)
    flat = jnp.sort(buck.reshape(buck.shape[0], -1), axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((flat.shape[0], 1), bool), flat[:, 1:] == flat[:, :-1]],
        axis=1)
    flat = jnp.sort(jnp.where(dup, BIG, flat), axis=1)
    L = (flat < BIG).sum(axis=1).astype(jnp.int32)
    M = flat[:, :deg_out]
    M = jnp.where(M == BIG, -1, M)
    return M, L, raw, L.sum()


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("deg_out",))
def _union_jit(cand, cand_len, pair_gid, pair_at, deg_out):
    return _union_impl(cand, cand_len, pair_gid, pair_at, deg_out)


# -- per-shard gather (the sharded exchange's local half) --------------------


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("n_global",))
def _gather_candidates_xla(pool_M, pool_L, inv_seg, inv_gid, inv_row,
                           inv_key, pair_slot, pair_seg, pair_gid, n_global):
    S, R, degp = pool_M.shape
    if inv_key is not None:
        rows = _resolve_key(inv_key, inv_row, pair_seg, pair_gid, n_global)
    else:
        rows = _resolve_lex(inv_seg, inv_gid, inv_row, pair_seg, pair_gid)
    ok = (pair_slot >= 0) & (rows >= 0)
    flat = jnp.clip(pair_slot, 0) * R + jnp.clip(rows, 0, R - 1)
    cand = pool_M.reshape(S * R, degp)[flat]
    # non-owned pairs contribute EXACT zeros (both values and length) so an
    # integer sum across shards reconstructs the single-pool gather
    # bit-for-bit — each pair has exactly one owning shard
    cand = jnp.where(ok[:, None], cand, 0)
    cand_len = jnp.where(ok, pool_L.reshape(S * R)[flat], 0)
    return cand, cand_len


def gather_candidates(pool_M, pool_L, inv_seg, inv_gid, inv_row,
                      pair_slot, pair_seg, pair_gid,
                      inv_key=None, n_global: int = 0):
    """One shard's half of the sharded completion gather (DESIGN.md §9):
    resolve ``(segment, gid)`` pairs against the global inverse maps and
    gather candidate rows from THIS shard's block pool, with pairs the
    shard does not own (``pair_slot == -1``) masked to exact zeros.

    The returned ``(cand (P, degp), cand_len (P,))`` are summed elementwise
    across shards (``distributed.sharding.all_sum_shards``) and fed to
    :func:`union_pairs` — together bit-identical to :func:`gather_union`
    over one combined pool."""
    return _gather_candidates_xla(pool_M, pool_L, inv_seg, inv_gid, inv_row,
                                  inv_key, pair_slot, pair_seg, pair_gid,
                                  int(n_global))


def union_pairs(cand, cand_len, pair_gid, pair_at, deg_out: int):
    """The shared union / self-removal / dedup / compaction epilogue over an
    explicit candidate matrix — the second half of the sharded exchange.
    Returns ``(M, L, raw, kept)`` exactly like :func:`gather_union`."""
    return _union_jit(cand, cand_len, pair_gid, pair_at, deg_out)


# -- xla backend: one fused dispatch -----------------------------------------


# contract: device-resident
@functools.partial(jax.jit, static_argnames=("deg_out", "n_global"))
def _gather_union_xla(pool_M, pool_L, inv_seg, inv_gid, inv_row, inv_key,
                      pair_slot, pair_seg, pair_gid, pair_at,
                      deg_out, n_global):
    S, R, degp = pool_M.shape
    if inv_key is not None:
        rows = _resolve_key(inv_key, inv_row, pair_seg, pair_gid, n_global)
    else:
        rows = _resolve_lex(inv_seg, inv_gid, inv_row, pair_seg, pair_gid)
    ok = (pair_slot >= 0) & (rows >= 0)
    flat = jnp.clip(pair_slot, 0) * R + jnp.clip(rows, 0, R - 1)
    cand = pool_M.reshape(S * R, degp)[flat]
    cand_len = jnp.where(ok, pool_L.reshape(S * R)[flat], 0)
    return _union_impl(cand, cand_len, pair_gid, pair_at, deg_out)


# -- pallas backend: resolve+gather kernel + shared epilogue -----------------


def _gather_kernel(invs_ref, invg_ref, invr_ref, seg_ref, gid_ref, slot_ref,
                   poolM_ref, poolL_ref, cand_ref, clen_ref,
                   *, K: int, R: int):
    """One pair-block of batched binary-search row resolve + pool gather.

    The sorted inverse maps and the flattened block pool are VMEM-resident;
    each grid step serves one block of (seg, gid, slot) pair columns."""
    qs = seg_ref[0, :]
    qg = gid_ref[0, :]
    slot = slot_ref[0, :]
    lo = jnp.zeros_like(qs)
    hi = jnp.full_like(qs, K)
    for _ in range(_bisect_steps(K)):
        mid = (lo + hi) // 2
        mid_c = jnp.minimum(mid, K - 1)
        ks = jnp.take(invs_ref[0, :], mid_c)
        kg = jnp.take(invg_ref[0, :], mid_c)
        less = (ks < qs) | ((ks == qs) & (kg < qg))
        upd = mid < hi
        lo = jnp.where(upd & less, mid + 1, lo)
        hi = jnp.where(upd & jnp.logical_not(less), mid, hi)
    pos = jnp.minimum(lo, K - 1)
    found = ((lo < K) & (jnp.take(invs_ref[0, :], pos) == qs)
             & (jnp.take(invg_ref[0, :], pos) == qg))
    row = jnp.where(found, jnp.take(invr_ref[0, :], pos), -1)
    ok = (row >= 0) & (slot >= 0)
    flat = jnp.clip(slot, 0) * R + jnp.clip(row, 0, R - 1)
    cand_ref[:, :] = jnp.take(poolM_ref[:, :], flat, axis=0)
    clen_ref[0, :] = jnp.where(ok, jnp.take(poolL_ref[0, :], flat), 0)


# contract: device-resident
@functools.partial(jax.jit,
                   static_argnames=("K", "interpret", "block_pairs"))
def _resolve_gather_pallas(pool_M, pool_L, inv_seg2, inv_gid2, inv_row2,
                           pair_seg2, pair_gid2, pair_slot2,
                           K, interpret, block_pairs):
    S, R, degp = pool_M.shape
    P = pair_seg2.shape[1]
    bp = min(block_pairs, P)
    grid = (P // bp,)
    kernel = functools.partial(_gather_kernel, K=K, R=R)
    full = lambda i: (0, 0)
    cand, clen = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(inv_seg2.shape, full),
            pl.BlockSpec(inv_gid2.shape, full),
            pl.BlockSpec(inv_row2.shape, full),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
            pl.BlockSpec((S * R, degp), full),
            pl.BlockSpec((1, S * R), full),
        ],
        out_specs=[
            pl.BlockSpec((bp, degp), lambda i: (i, 0)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, degp), jnp.int32),
            jax.ShapeDtypeStruct((1, P), jnp.int32),
        ],
        interpret=interpret,
    )(inv_seg2, inv_gid2, inv_row2, pair_seg2, pair_gid2, pair_slot2,
      pool_M.reshape(S * R, degp), pool_L.reshape(1, S * R))
    return cand, clen[0]


def _pad_pow2_1d(a: jnp.ndarray, fill) -> jnp.ndarray:
    n = a.shape[0]
    n_pad = max(128, 1 << (int(n) - 1).bit_length())
    if n_pad == n:
        return a
    return jnp.concatenate(
        [a, jnp.full((n_pad - n,), fill, a.dtype)])


# -- public entry -------------------------------------------------------------


def gather_union(
    pool_M: jnp.ndarray,        # (S, R, degp) i32 stacked full blocks
    pool_L: jnp.ndarray,        # (S, R) i32 row lengths
    inv_seg: jnp.ndarray,       # (K,) i32 sorted lexicographically with
    inv_gid: jnp.ndarray,       # (K,) i32   inv_gid (docs/DESIGN.md §2)
    inv_row: jnp.ndarray,       # (K,) i32 local row per appearance
    pair_slot: jnp.ndarray,     # (P,) i32 pool slot per pair (-1 padding)
    pair_seg: jnp.ndarray,      # (P,) i32 segment per pair (row resolve)
    pair_gid: jnp.ndarray,      # (P,) i32 query gid per pair
    pair_at: jnp.ndarray,       # (n, w) i32 pair index per query (-1 empty)
    deg_out: int,
    backend: str = "xla",
    inv_key: Optional[jnp.ndarray] = None,
    n_global: int = 0,
    block_pairs: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-side completion gather: resolve rows, gather, union, compact.

    Returns ``(M (n, deg_out) i32, L (n,) i32, raw, kept)`` — all device
    arrays; ``L`` is the TRUE unique-neighbour count and may exceed
    ``deg_out``, in which case ``M`` is truncated and the caller must raise
    (the engine's preallocated-width contract). ``raw``/``kept`` are the
    gathered-entry counters feeding ``EngineStats``."""
    if backend == "xla":
        return _gather_union_xla(pool_M, pool_L, inv_seg, inv_gid, inv_row,
                                 inv_key, pair_slot, pair_seg, pair_gid,
                                 pair_at, deg_out, int(n_global))
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown backend {backend!r}")
    K = int(inv_seg.shape[0])
    # pad the inverse maps with +inf-like keys so the bisection never lands
    # in padding, and pairs to a 128-lane multiple for the kernel grid
    inv_seg2 = _pad_pow2_1d(inv_seg, BIG).reshape(1, -1)
    inv_gid2 = _pad_pow2_1d(inv_gid, BIG).reshape(1, -1)
    inv_row2 = _pad_pow2_1d(inv_row, -1).reshape(1, -1)
    pair_seg2 = _pad_pow2_1d(pair_seg, 0).reshape(1, -1)
    pair_gid2 = _pad_pow2_1d(pair_gid, -1).reshape(1, -1)
    pair_slot2 = _pad_pow2_1d(pair_slot, -1).reshape(1, -1)
    P = pair_seg.shape[0]
    with pallas_refusal("completion gather", backend):
        cand, cand_len = _resolve_gather_pallas(
            pool_M, pool_L, inv_seg2, inv_gid2, inv_row2,
            pair_seg2, pair_gid2, pair_slot2,
            K, backend == "pallas_interpret", block_pairs)
    return _union_jit(cand[:P], cand_len[:P], pair_gid, pair_at, deg_out)
