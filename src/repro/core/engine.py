"""The GALE relation engine: task-parallel localized relation computation
(paper §4.4–4.6), adapted to JAX/TPU.

Roles, mapped from the paper:

  consumer        -> the analysis algorithm calling :meth:`get` /
                     :meth:`get_batch` (and the boundary-relation helpers,
                     which never touch the accelerator — paper §4.4)
  leader producer -> :meth:`_dispatch`: drains the per-relation queue
                     (multi-queue design, §4.5), extends the batch with
                     *lookahead* segments along the traversal order (the
                     paper's ``n_b·t_b/t_s`` proactive precompute), and
                     launches ONE batched kernel per relation type
  worker producer -> the Pallas grid (``kernels/segment_relations.py``)

Asynchronous consumer contract
------------------------------

With ``async_dispatch=True`` (the default) the producer NEVER blocks: a
kernel launch returns immediately and its not-yet-ready device arrays are
recorded in an **in-flight futures table** keyed by ``(relation, segment)``.

  - :meth:`prefetch` / :meth:`prefetch_many` enqueue traversal-order hints
    and dispatch launches round-robin across relations (several relation
    kernels in flight at once), returning immediately.
  - :meth:`get` / :meth:`get_batch` block only when they read a block that
    is still computing; the wait is accounted in ``stats.t_sync`` (the
    paper's Fig. 10 "waiting" metric). ``stats.t_dispatch`` records only
    the host-side dispatch cost, so ``t_sync`` vs ``t_dispatch`` quantifies
    how much of the kernel execution was hidden behind consumer work.
  - A segment is never produced twice: requests are de-duplicated against
    the cache, the in-flight table, and the pending queues.

With ``async_dispatch=False`` every launch is synced immediately after
dispatch (the pre-async blocking behaviour, used by the ACTOPO/TopoCluster
baselines); the wait still lands in ``t_sync`` so the two modes are
directly comparable.

Multi-consumer thread safety (docs/DESIGN.md §8)
------------------------------------------------

The paper's CPU side is *multi-consumer*: several host threads execute the
analysis algorithm concurrently (``core/scheduler.py``). The engine
serializes all shared-state mutation behind ONE lock + condition variable
(``self._cond``): every public consumer method acquires it once at entry,
and every internal step (queues, cache, in-flight table, device block
pool, stats) runs with it held. The only wait that releases the lock is
the device sync: the first consumer needing a launch becomes its *syncer*
(``launch.syncing``), drops the lock for ``jax.block_until_ready``, then
re-acquires and integrates exactly once; other consumers needing the same
launch wait on the condition variable until ``launch.done``. Consequences:

  - a block is still never produced twice — request de-dup, dispatch and
    integration are atomic under the lock for ANY thread interleaving;
  - stat updates can never be lost (all go through :meth:`_bump` under the
    lock) and are additionally attributed to the calling worker
    (:meth:`worker_scope`), so ``merged_worker_stats()`` always equals
    ``stats``;
  - results remain bit-identical for any number of consumer threads — the
    existing any-scheduling contract extended to concurrency.

The engine also keeps the paper's accounting (Table 5/6/7): per-phase wait
times (enqueue / queue / prepare / kernel dispatch / sync / integrate) and
cache statistics. The prepare, dispatch, sync and integrate times are the
host intervals of the ``engine.dispatch`` / ``engine.sync`` /
``engine.integrate`` spans (``core/spans.py``), so a profiler trace shows
each one on the device's clock.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ShardPlan
from ..errors import (
    DeviceLostError,
    KernelCompileError,
    PoolUploadError,
    RelationError,
    RelationPoisonedError,
    RelationWidthError,
    SyncTimeoutError,
)
from ..kernels import ops
from .blockstore import BlockStore, DevBlockPool, SegmentCache
from .faults import FaultPolicy
from .segtables import (
    OFFLOADED_RELATIONS,
    Preconditioned,
    RELATION_TABLES,
)
from .spans import span, spanned


@dataclasses.dataclass
class EngineStats:
    """Engine accounting (paper Tables 5/6/7 + Fig. 10). Counter semantics:

    - ``requests``: simplex-block reads issued through :meth:`RelationEngine.
      get` / ``get_batch`` / ``get_full`` (one per (relation, segment) read).
    - ``cache_hits`` / ``cache_misses``: whether a read found its block
      already produced (or in flight — ``inflight_hits`` is that subset).
    - ``kernel_launches`` / ``segments_produced``: producer-side dispatch
      counts. A segment is never produced twice for the same relation, so
      ``segments_produced`` is also the number of distinct blocks computed.
    - ``completion_*``: cross-segment adjacency completion
      (``core/adjacency.py``): completed queries, fan-out block
      consultations (distinct per plan; a chunked completion that consults
      the same block from several chunks counts it once per chunk), and raw
      vs deduplicated neighbor entries (the dedup ratio quantifies how much
      cross-segment overlap the union removed).
    """

    requests: int = 0
    kernel_launches: int = 0
    segments_produced: int = 0
    cache_hits: int = 0
    inflight_hits: int = 0   # subset of cache_hits served from in-flight
    cache_misses: int = 0
    evictions: int = 0
    # Device block pool (get_full_dev): reads served from still-device-
    # resident launch results vs host-cache blocks re-uploaded to device.
    devpool_hits: int = 0
    devpool_uploads: int = 0
    # Fault recovery (docs/DESIGN.md §12). ``retries`` counts launch AND
    # sync re-attempts; ``failed_*`` counts launches abandoned after a
    # fault (their dispatch-time ``kernel_launches``/``segments_produced``
    # bumps are reversed, so "produced == distinct blocks" still holds);
    # ``degraded_*`` counts host-arm production/reads while a relation's
    # circuit breaker is open.
    retries: int = 0
    sync_timeouts: int = 0
    failed_launches: int = 0
    failed_segments: int = 0
    breaker_trips: int = 0
    breaker_recoveries: int = 0
    degraded_launches: int = 0
    degraded_segments: int = 0
    degraded_reads: int = 0
    shards_lost: int = 0
    rehomed_segments: int = 0
    # Cross-segment adjacency completion (core/adjacency.py).
    completion_chunks: int = 0         # plan_completion calls (chunks)
    completion_queries: int = 0        # simplex ids completed
    completion_fanout_blocks: int = 0  # block consultations (see docstring)
    completion_raw_neighbors: int = 0  # gathered entries before dedup/self
    completion_neighbors: int = 0      # entries in the final completed rows
    # Sharded completion exchange (distributed.sharding.all_sum_shards):
    # chunks summed by a psum over the shards' own devices vs stacked and
    # summed on one device (shards sharing a device).
    exchange_psum: int = 0
    exchange_stack: int = 0
    # Waiting-time breakdown (seconds), paper Fig. 10 phases.
    t_enqueue: float = 0.0
    t_queue: float = 0.0
    t_prepare: float = 0.0
    t_dispatch: float = 0.0  # host-side kernel dispatch time only
    t_sync: float = 0.0      # time the consumer waited on in-flight results
    t_integrate: float = 0.0

    @property
    def completion_dedup_ratio(self) -> float:
        """Raw gathered entries per surviving completed entry (>= 1.0 once
        any completion ran; 0.0 before)."""
        if self.completion_neighbors == 0:
            return 0.0
        return self.completion_raw_neighbors / self.completion_neighbors

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["completion_dedup_ratio"] = self.completion_dedup_ratio
        return d

    def bump(self, **deltas) -> None:
        """Add counter deltas in place. The engine routes every stat update
        through this (under its lock), so concurrent consumers never lose
        increments."""
        for k, v in deltas.items():
            setattr(self, k, getattr(self, k) + v)

    @staticmethod
    def merged(parts: Iterable["EngineStats"]) -> "EngineStats":
        """Sum every field over ``parts`` into a fresh ``EngineStats``.

        Deterministic for a fixed iteration order — callers pass workers in
        sorted-key order (:meth:`StatsHost.merged_worker_stats`) so the
        float sums are reproducible run to run. Int counters merge exactly;
        the per-worker breakdown of a run therefore round-trips to the
        global stats."""
        out = EngineStats()
        for p in parts:
            out.bump(**dataclasses.asdict(p))
        return out


class StatsHost:
    """Thread-safe stats accounting shared by :class:`RelationEngine` and
    the explicit baseline: a single lock/condition (``self._cond``) guards
    every counter update, and each update is attributed to the calling
    *worker thread* (:meth:`worker_scope`) so ``worker_stats`` carries the
    per-consumer breakdown of docs/DESIGN.md §8. The invariant
    ``merged_worker_stats() == stats`` holds at all times (exactly for int
    counters, up to float-summation order for the ``t_*`` phases)."""

    # producer-side counters attributed per segment shard (each update also
    # lands on the global/worker stats via _bump, so the §8 worker
    # invariant is untouched; docs/DESIGN.md §9)
    _SHARD_FIELDS = ("kernel_launches", "segments_produced",
                     "devpool_hits", "devpool_uploads", "t_dispatch")

    def _init_stats(self) -> None:
        self.stats = EngineStats()
        self.worker_stats: Dict[str, EngineStats] = {}
        self.shard_stats: Dict[int, EngineStats] = {}
        self._cond = threading.Condition()
        self._tl = threading.local()

    @contextlib.contextmanager
    def worker_scope(self, name: str):
        """Attribute this thread's stat updates to worker ``name`` (the
        scheduler wraps each worker loop in one; unscoped updates land on
        the ``"main"`` worker)."""
        prev = getattr(self._tl, "worker", None)
        self._tl.worker = str(name)
        try:
            yield
        finally:
            self._tl.worker = prev

    def _bump(self, **deltas) -> None:
        # contract: holds-lock
        """Stat update; the caller must hold ``self._cond``."""
        w = getattr(self._tl, "worker", None) or "main"
        ws = self.worker_stats.get(w)
        if ws is None:
            ws = self.worker_stats[w] = EngineStats()
        self.stats.bump(**deltas)
        ws.bump(**deltas)

    @contextlib.contextmanager
    def _timed(self, name: str, counter: str, relation: Optional[str] = None,
               shard: Optional[int] = None):
        # contract: holds-lock
        """Run the block inside ``span(name)`` and add its host interval to
        the ``counter`` phase time (and to shard ``shard``'s, where given),
        so with the profiler on a ``t_*`` counter is the sum of its spans.
        ``relation`` is the span's one argument. The caller holds
        ``self._cond`` when the block starts and when it ends. The clock is
        read just inside the span's two ends and the counters are bumped
        after it closes: the bump allocates, and a garbage collection it
        sets off would lengthen the span and not the counter."""
        dt = 0.0
        try:
            with (span(name, relation=relation) if relation else span(name)):
                t0 = time.perf_counter()
                try:
                    yield
                finally:
                    dt = time.perf_counter() - t0
        finally:
            self._bump(**{counter: dt})
            if shard is not None:
                self._bump_shard(shard, **{counter: dt})

    def stat_bump(self, **deltas) -> None:
        """Thread-safe counter update for out-of-engine accounting (the
        completion pipeline in ``core/adjacency.py``)."""
        with self._cond:
            self._bump(**deltas)

    def _bump_shard(self, shard: int, **deltas) -> None:
        # contract: holds-lock
        """Producer-side stat update attributed to segment shard ``shard``
        (in addition to the global/worker landing the caller does via
        :meth:`_bump`). The caller must hold ``self._cond``."""
        ss = self.shard_stats.get(shard)
        if ss is None:
            ss = self.shard_stats[shard] = EngineStats()
        ss.bump(**deltas)

    def reset_stats(self) -> None:
        """Zero every counter (global + per-worker + per-shard) under the
        lock — the sanctioned way for benchmarks to separate warmup from
        timed runs. Rebinding ``.stats`` directly would bypass the lock and
        orphan the per-worker breakdown (the ``merged_worker_stats() ==
        stats`` invariant); contractcheck's lock-discipline rule rejects
        it."""
        with self._cond:
            self.stats = EngineStats()
            self.worker_stats = {}
            self.shard_stats = {}

    def merged_worker_stats(self) -> EngineStats:
        """Deterministic merge of the per-worker breakdown (sorted worker
        key order); equals ``stats`` — the scheduler tests assert it."""
        with self._cond:
            return EngineStats.merged(
                self.worker_stats[k] for k in sorted(self.worker_stats))

    def merged_shard_stats(self) -> EngineStats:
        """Deterministic merge of the per-shard producer breakdown (sorted
        shard order); equals ``stats`` on the producer counters
        (``_SHARD_FIELDS``): ints exactly, ``t_dispatch`` up to float
        summation order. The sharded-engine tests assert it, and per-shard
        ``segments_produced`` proves no segment was produced on more than
        one shard."""
        with self._cond:
            return EngineStats.merged(
                self.shard_stats[k] for k in sorted(self.shard_stats))


# RelationWidthError historically lived here; it moved into the structured
# error taxonomy (src/repro/errors.py, docs/DESIGN.md §12) and stays
# importable from this module — it is re-exported by the import block above.
assert issubclass(RelationWidthError, ValueError)


# The block-storage layer (host segment cache + launch-granularity device
# pools behind one LRU core) lives in core/blockstore.py; the old private
# names stay importable for external code that grew around them.
_SegmentCache = SegmentCache
_DevBlockPool = DevBlockPool


@dataclasses.dataclass
class ConsumerBatch:
    """Device-resident view of one consumer batch (docs/DESIGN.md §6): the
    *internal* relation rows of a batch of segments, stacked across several
    relations that share a subject simplex kind, served straight from the
    producer's device block pool.

    Rows are the segments' internal simplices in traversal order (segment by
    segment, ascending global id within each — exactly the layout the host
    consumers used to assemble in numpy), padded to a power-of-two row
    bucket (``ops.bucket_rows``) so the consumer jits see O(log n) shapes.
    Padding rows carry ``gid == -1`` and all-(-1) relation entries; their
    classification results are the caller's to discard.

    ``M``/``L`` are fused-gather outputs — fresh device buffers, NOT
    aliases of the pooled launch arrays — so they are safe jit inputs, but
    they also live OUTSIDE the ``dev_pool_segments`` bound: consumers must
    release each batch before materializing the next-plus-one (the drivers'
    depth-1 double buffer), or device memory grows with the mesh
    (docs/DESIGN.md §6)."""

    kind: str                      # subject simplex kind (V/E/F/T)
    segments: Tuple[int, ...]      # segment ids served, in row order
    n_rows: int                    # real rows (before bucket padding)
    gid: np.ndarray                # (n_rows,) host global ids for scatter
    gid_dev: jnp.ndarray           # (rows_pad,) device gids, -1 padding
    M: Dict[str, jnp.ndarray]      # relation -> (rows_pad, width) device
    L: Dict[str, jnp.ndarray]      # relation -> (rows_pad,) device counts

    def width(self, relation: str) -> int:
        return self.M[relation].shape[1]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _place_rows(pool_M, pool_L, M, L, idx, pos):
    # contract: device-resident
    """``pool[pos] = launch[idx]`` for one retained launch's blocks, in
    place (the output pool is donated). Padded entries repeat a real
    ``(idx, pos)`` pair, so they write the same values twice."""
    return (pool_M.at[pos].set(jnp.take(M, idx, axis=0)),
            pool_L.at[pos].set(jnp.take(L, idx, axis=0)))


@functools.partial(jax.jit, static_argnames=("w",))
def _gather_internal(pool_M, pool_L, flat, gid, w: int):
    # contract: device-resident
    """One fused device gather per (relation, batch): pick the internal
    rows (``flat`` indexes the flattened slot-rows), trim columns to the
    static width ``w``, and mask bucket-padding rows (``gid == -1``) to the
    documented all-(-1) / zero-count padding."""
    Mr = jnp.take(pool_M.reshape(-1, pool_M.shape[-1]), flat, axis=0)[:, :w]
    Lr = jnp.take(pool_L.reshape(-1), flat, axis=0)
    return (jnp.where(gid[:, None] >= 0, Mr, -1),
            jnp.where(gid >= 0, Lr, 0))


class _Launch:
    """One dispatched batched kernel whose results may not be ready yet."""

    __slots__ = ("relation", "segments", "M", "L", "n_rows", "done",
                 "syncing", "shard", "host", "error", "hang_until",
                 "sync_attempts")

    def __init__(self, relation, segments, M, L, n_rows, shard=0,
                 host=False):
        self.relation = relation
        self.segments = segments      # real (unpadded) segment ids
        self.M = M                    # (B_padded, R, deg) device array
        self.L = L                    # (B_padded, R) device array
        self.n_rows = n_rows          # per-segment internal row counts
        self.done = False
        self.syncing = False          # a consumer thread owns the sync wait
        self.shard = shard            # owning segment shard (stats, re-home)
        self.host = host              # degraded host-arm launch (not pooled)
        self.error = None             # terminal fault (docs/DESIGN.md §12)
        self.hang_until = 0.0         # injected sync hang deadline (faults)
        self.sync_attempts = 0        # watchdog timeouts consumed so far

    def is_ready(self) -> bool:
        if self.hang_until and time.monotonic() < self.hang_until:
            return False              # injected hang: results stay un-ready
        return self.M.is_ready() and self.L.is_ready()


class RelationEngine(StatsHost):
    """GALE: GPU(TPU)-Aided Localized data structurE.

    Safe for concurrent use by multiple consumer threads (module docstring
    + docs/DESIGN.md §8): every public consumer method acquires the engine
    lock exactly once; internal ``_``-prefixed steps assume it is held."""

    @spanned("engine.init")
    def __init__(
        self,
        pre: Preconditioned,
        relations: Sequence[str],
        backend: str = "xla",
        lookahead: int = 8,
        batch_max: Optional[int] = None,
        cache_segments: int = 512,
        block_x: Optional[int] = None,
        block_y: Optional[int] = None,
        deg: Optional[Dict[str, int]] = None,
        async_dispatch: bool = True,
        inflight_max: int = 8,
        dev_pool_segments: int = 256,
        shards: int = 1,
        shard_plan: Optional[ShardPlan] = None,
        fault_policy: Optional[FaultPolicy] = None,
        sync_timeout_s: Optional[float] = None,
        tune: str = "auto",
        assembly: str = "sparse",
    ):
        if pre.tables is None:
            raise ValueError("precondition(..., build_tables=True) required")
        # Fault-recovery policy (docs/DESIGN.md §12): defaults come from
        # $REPRO_FAULT_SPEC (CI chaos jobs) when no explicit policy is
        # passed; sync_timeout_s= overrides the policy's watchdog knob.
        if fault_policy is None:
            fault_policy = FaultPolicy.from_env()
        if sync_timeout_s is not None:
            fault_policy = dataclasses.replace(
                fault_policy, sync_timeout_s=float(sync_timeout_s))
        self._fault_policy = fault_policy
        self._injector = fault_policy.injector
        # per-relation circuit breaker: consecutive device-arm failures,
        # open-until deadline, and the last fault (docs/DESIGN.md §12)
        self._breaker: Dict[str, Dict] = {}
        # relations that permanently failed under degrade=False: every
        # later consumer call raises RelationPoisonedError immediately
        self._poisoned: Dict[str, BaseException] = {}
        self._lost_shards: set = set()
        self.pre = pre
        self.smesh = pre.smesh
        self.tables = pre.tables
        self.backend = backend
        self.lookahead = lookahead
        # Kernel-parameter resolution (docs/DESIGN.md §4): explicit argument
        # > tuned table entry (tune="auto" or a path) > built-in default.
        # tune="off" skips the table so today's defaults are reproduced
        # bit-for-bit; only entries tuned on this device kind apply.
        dev0 = (shard_plan.devices[0] if shard_plan is not None
                and shard_plan.devices[0] is not None else jax.devices()[0])
        tuned = self._load_tuned_config(tune, backend,
                                        pre.smesh.n_segments,
                                        dev0.device_kind)
        self.batch_max = int(batch_max if batch_max is not None
                             else tuned.get("batch_max", 64))
        self.block_x = int(block_x if block_x is not None
                           else tuned.get("block_x", 256))
        self.block_y = int(block_y if block_y is not None
                           else tuned.get("block_y", 256))
        vvb = tuned.get("vv_block")
        self.vv_block: Optional[int] = int(vvb) if vvb else None
        self.bucket_floor = max(1, int(tuned.get("bucket_floor", 1)))
        self.assembly = assembly
        batch_max = self.batch_max
        self.async_dispatch = async_dispatch
        self.inflight_max = max(1, inflight_max)
        self.relations = tuple(r for r in relations if r in OFFLOADED_RELATIONS)
        self.deg = dict(ops.DEFAULT_DEG)
        if deg:
            self.deg.update(deg)

        # Segment shards over the ("data",) device mesh (docs/DESIGN.md §9):
        # shard k owns the contiguous segment range plan.shard_bounds(k),
        # produces exactly those blocks on plan.devices[k], and retains them
        # in its own device pool. shards=1 (the default) is the unsharded
        # engine, bit-for-bit.
        ns = self.smesh.n_segments
        if shard_plan is None:
            shard_plan = ShardPlan.make(ns, shards)
        elif shard_plan.n_segments != ns:
            raise ValueError(
                f"shard_plan covers {shard_plan.n_segments} segments but the "
                f"mesh has {ns}")
        self.shard_plan = shard_plan
        self.n_shards = shard_plan.n_shards
        # commit arrays to shard devices only when shards actually sit on
        # distinct devices; logical sharding on one device stays placement-
        # free (so tier-1 single-device runs are byte-identical to shards=1)
        self._multi_dev = shard_plan.multi_device
        self._seg_shard = shard_plan.shard_of_array(np.arange(ns))
        # kernel shapes already compiled on every shard device at once
        self._compiled_on_all: set = set()

        # Multi-queue: one pending-request queue per offloaded relation
        # (paper §4.5 'Justification of design choices').
        self.queues: Dict[str, List[int]] = {r: [] for r in self.relations}
        # Block storage (core/blockstore.py): one host segment cache + one
        # device block pool PER SHARD (docs/DESIGN.md §5/§9). Pool entries
        # reference retained launch arrays (idx row) or one-block uploads
        # (idx None); each pool is bounded by backing launches —
        # ``dev_pool_segments`` is a per-device segment budget converted at
        # launch granularity, so the device-memory bound is honest even
        # though one entry can pin a whole ``batch_max``-segment launch.
        # Evictions only drop device references; the host cache keeps the
        # data.
        self.store = BlockStore(
            cache_segments,
            max(1, dev_pool_segments // max(1, batch_max)),
            n_shards=self.n_shards,
            shard_of=lambda s: int(self._seg_shard[s]))
        self.cache = self.store.cache
        self._dev_pool = self.store   # shard-routed DevBlockPool surface
        # In-flight futures: (relation, segment) -> _Launch whose device
        # arrays may still be computing. Launches retire into the cache at
        # the first read that needs them (or opportunistically when ready).
        self._inflight: Dict[Tuple[str, int], _Launch] = {}
        self._flights: "collections.deque[_Launch]" = collections.deque()
        self._init_stats()   # stats + per-worker/per-shard breakdown + lock

        # Device-resident stacked tables (copied once, like the paper copying
        # initialized arrays to GPU global memory). Sharded engines slice the
        # stacked tables per shard — each device holds only its own
        # segments' rows, indexed by shard-local segment id (docs §9).
        self._shard_tables: List[Dict[str, jnp.ndarray]] = [
            self._stage_shard_tables(*shard_plan.shard_bounds(k),
                                     shard_plan.devices[k]
                                     if self._multi_dev else None)
            for k in range(self.n_shards)]
        # legacy single-device view: with one shard the full tables double as
        # shard 0's slice (same arrays); sharded engines keep only the
        # inverse maps here
        self._dev: Dict[str, jnp.ndarray] = (
            dict(self._shard_tables[0]) if self.n_shards == 1 else {})
        # per-(kind, shard) inverse-map replicas, staged lazily on first
        # sharded resolve (dev_inverse(kind, shard=k))
        self._inv_shard: Dict[Tuple[str, int], tuple] = {}
        # Device-resident inverse maps (docs/DESIGN.md §5): per-kind sorted
        # (segment, gid) appearance lists mirroring tables.inverse, stored as
        # i32 (seg, gid, row) columns so accelerator-side gathers can resolve
        # cross-segment rows without x64. The device completion gather path
        # (kernels/completion_gather.py) binary-searches these; when the
        # combined key ``seg * n_global + gid`` fits i32 it is additionally
        # staged as ``inv_key_*`` so the xla oracle is one jnp.searchsorted.
        self._inv_nglob: Dict[str, int] = {}
        t = self.tables
        if t.inverse:
            for kind, (keys, rows, n_glob) in t.inverse.items():
                if kind == "V":   # completion only spans E/F/T kinds
                    continue
                self._dev[f"inv_seg_{kind}"] = jnp.asarray(
                    (keys // n_glob).astype(np.int32))
                self._dev[f"inv_gid_{kind}"] = jnp.asarray(
                    (keys % n_glob).astype(np.int32))
                self._dev[f"inv_row_{kind}"] = jnp.asarray(rows)
                self._inv_nglob[kind] = int(n_glob)
                if len(keys) == 0 or int(keys[-1]) < 2 ** 31:
                    self._dev[f"inv_key_{kind}"] = jnp.asarray(
                        keys.astype(np.int32))

    def _stage_shard_tables(self, lo: int, hi: int, dev
                            ) -> Dict[str, jnp.ndarray]:
        """Stage one shard's sliced tables onto ``dev`` (``None`` = default
        placement). Used at construction for every shard and again by
        :meth:`_rehome_shard` to move a lost shard's slice onto a surviving
        device (docs/DESIGN.md §12)."""
        t = self.tables
        if dev is not None:
            put = (lambda a: jax.device_put(
                np.ascontiguousarray(a[lo:hi]), dev))
        else:
            put = (lambda a: jnp.asarray(a[lo:hi]))
        tabs: Dict[str, jnp.ndarray] = {}
        tabs["T_local"] = put(t.T_local)
        tabs["LT_global"] = put(t.LT_global)
        tabs["LV_global"] = put(t.LV_global)
        if t.E_local is not None:
            tabs["E_local"] = put(t.E_local)
            tabs["LE_global"] = put(t.LE_global)
        if t.F_local is not None:
            tabs["F_local"] = put(t.F_local)
            tabs["LF_global"] = put(t.LF_global)
        return tabs

    @staticmethod
    def _load_tuned_config(tune: str, backend: str, n_segments: int,
                           device_kind: str) -> Dict:
        """Resolve the autotuned kernel-parameter dict for this engine.

        ``tune="off"`` returns ``{}`` (built-in defaults); ``"auto"`` looks
        up the default on-disk table (``launch/autotune.py``); any other
        string is a path to an explicit table. Only an entry recorded for
        this ``device_kind`` applies; a missing table is ``{}``, and an
        unreadable one raises ``autotune.TuneTableError``."""
        if tune == "off":
            return {}
        from ..launch import autotune
        cfg = autotune.lookup(backend, n_segments, device_kind,
                              path=None if tune == "auto" else tune)
        return cfg.to_dict() if cfg is not None else {}

    # -- consumer-side API --------------------------------------------------

    @contextlib.contextmanager
    def _consumer_entry(self, method: str):
        """Public consumer-method entry: rejects re-entrant entry, then
        acquires the engine lock exactly once.

        The lock is a plain (non-reentrant) ``threading.Condition``, so a
        nested public call from a thread already inside one — consumer code
        invoked from the producer's dispatch path, or a callback fired under
        the lock — would deadlock silently, with no traceback until the
        scheduler-stress job's hard timeout SIGABRTs it. The thread-local
        entry marker turns that hang into an immediate ``RuntimeError``
        naming both methods. Lock-free table accessors (``local_rows``,
        ``boundary_*``, ``dev_inverse``) stay legal anywhere."""
        held = getattr(self._tl, "engine_method", None)
        if held is not None:
            raise RuntimeError(
                f"re-entrant call into RelationEngine.{method}() from "
                f"RelationEngine.{held}() on the same thread: the engine "
                f"lock (docs/DESIGN.md §8) is not re-entrant, so this call "
                f"would deadlock. Finish the {held}() call first, or use "
                f"the lock-free table accessors (local_rows, boundary_*).")
        self._tl.engine_method = method
        try:
            with self._cond:
                yield
        finally:
            self._tl.engine_method = None

    def request(self, relation: str, segments: Sequence[int]) -> None:
        """Non-blocking enqueue (consumer -> leader queue).

        Never blocks on the device and never launches a kernel: it only
        appends traversal hints to the per-relation pending queue. De-dup
        guarantee: a segment already cached, in flight, or pending is not
        enqueued again, so a block is never produced twice no matter how
        often it is requested."""
        with self._consumer_entry("request"):
            self._request(relation, segments)

    def _request(self, relation: str, segments: Sequence[int]) -> None:
        # contract: holds-lock
        self._check_poisoned(relation)
        t0 = time.perf_counter()
        q = self.queues[relation]
        qs = set(q)
        for s in segments:
            s = int(s)
            if ((relation, s) not in self.cache
                    and (relation, s) not in self._inflight
                    and s not in qs):
                q.append(s)
                qs.add(s)
        self._bump(t_enqueue=time.perf_counter() - t0)

    def clear_cache(self) -> int:
        """Drop every retained block — host segment cache and all shard
        device pools — under the engine lock. Benchmarks use this to model
        cold caches (the old ``eng.cache._store.clear()`` peek, now a
        contractcheck violation).

        In-flight launches are retired (synced and integrated) first so a
        launch dispatched before the clear cannot resurrect dropped blocks
        afterwards; the wait lands in ``stats.t_sync`` as usual. Returns the
        total number of entries dropped."""
        with self._consumer_entry("clear_cache"):
            while self._flights:
                self._sync(self._flights.popleft())
            return self.store.clear_cache()

    def release_dev(self, relation: str,
                    segments: Optional[Sequence[int]] = None) -> int:
        """Drop ``relation``'s blocks (those of ``segments`` only, where
        given) from the device pools under the engine lock, freeing the
        device memory of every launch array left with no pooled block. The
        host cache is untouched: a later device read of such a block
        uploads it from there (``devpool_uploads``), or produces it again
        if the cache has evicted it. For a consumer done with those blocks.
        Returns the number of entries dropped."""
        with self._consumer_entry("release_dev"):
            return self.store.release(relation, segments)

    def cache_nbytes(self) -> int:
        """Bytes retained across the host segment cache and every shard's
        device pool (shard-aware via ``BlockStore.shard_occupancy()``),
        under the engine lock. This is the public replacement for the
        benchmarks' memory-accounting peek at ``cache._store``."""
        with self._consumer_entry("cache_nbytes"):
            return self.store.cache_nbytes()

    def get(self, relation: str, segment: int) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch the (M, L) relation block for one segment.

        Rows are the segment's *internal* simplices of the relation's subject
        kind, in global-id order starting at ``interval[kind][segment]``.

        Blocking behavior: returns immediately on a cache hit; on an
        in-flight hit it blocks only until that launch's device arrays are
        ready (the wait lands in ``stats.t_sync``); on a miss it queue-jumps
        the segment, dispatches one batched launch, and waits for it.
        De-dup guarantee: a miss never re-produces segments that are cached
        or in flight — only genuinely missing ones enter the launch."""
        with self._consumer_entry("get"):
            segment = int(segment)
            self._bump(requests=1)
            self._count(relation, segment)
            return self._fetch(relation, segment)

    def get_full(self, relation: str, segment: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Like :meth:`get`, but returns ALL local rows of the block —
        internal simplices first (global-id order), then the segment's
        external simplices, then table padding (rows with ``L == 0``).

        Cross-segment adjacency completion reads external rows through this
        method, so misses take the normal dispatch path and are counted in
        ``stats.cache_misses`` (never silently served as empty). Blocking
        behavior and de-dup guarantee are identical to :meth:`get`."""
        with self._consumer_entry("get_full"):
            segment = int(segment)
            self._bump(requests=1)
            self._count(relation, segment)
            return self._fetch(relation, segment, full=True)

    def get_full_dev(self, relation: str, segment: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Like :meth:`get_full`, but returns DEVICE arrays — the block stays
        on the accelerator for the device completion gather path
        (``kernels/completion_gather.py``), with no ``np.asarray`` round
        trip.

        Blocks still resident from their launch are served from the device
        block pool (``stats.devpool_hits``); blocks only present in the host
        cache are uploaded once and pooled (``stats.devpool_uploads``).
        Misses take the normal dispatch path and are counted exactly like
        :meth:`get_full`; blocking behavior and de-dup guarantee are
        identical."""
        with self._consumer_entry("get_full_dev"):
            M, L, i = self._dev_entry(relation, int(segment))
        return (M, L) if i is None else (M[i], L[i])

    @spanned("consumer.read_dev")
    def get_full_dev_batch(self, relation: str, segments: Sequence[int],
                           pad_to: Optional[int] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Stacked full device blocks ``(M (S, R, deg), L (S, R))`` for
        several segments, rows in the given order (optionally padded to
        ``pad_to`` slots by repeating the first block — padding slots are
        the caller's to ignore).

        Blocking behavior, de-dup guarantee and counting are one
        :meth:`get_full_dev` per segment, but blocks sharing a retained
        launch are placed with ONE jitted gather-and-place per launch into
        a donated output pool instead of one slice per segment, and a whole
        launch asked for in its own order is returned as is — the
        completion gather path's pool builder."""
        with self._consumer_entry("get_full_dev_batch"):
            segments = [int(s) for s in segments]
            ents = [self._dev_entry(relation, s) for s in segments]
            return self._stack_entries(ents, pad_to)

    def _stack_entries(self, ents, pad_to: Optional[int]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Stack resolved device-pool entries into ``(S, R, deg)`` /
        ``(S, R)`` arrays — shared by :meth:`get_full_dev_batch` and the
        mixed-launch arm of :meth:`get_full_dev_many`.

        One jitted gather-and-place (:func:`_place_rows`) per retained
        launch, with its row lists padded to a power-of-two bucket, so the
        compiled shapes depend only on (launch shape, bucket, ``pad_to``)
        and not on how a batch happens to split across launches. A single
        launch asked for whole, in its own order and unpadded, is returned
        without a copy."""
        S = len(ents)
        pad_to = S if pad_to is None else max(pad_to, S)
        M0, L0, i0 = ents[0]
        if (pad_to == S and i0 is not None and M0.shape[0] == S
                and all(M is M0 and i == k
                        for k, (M, _, i) in enumerate(ents))):
            return M0, L0
        # group output slots by source device array (same retained launch)
        groups: Dict[int, Tuple[jnp.ndarray, jnp.ndarray, list, list]] = {}
        for out_pos, (M, L, i) in enumerate(ents):
            if i is None:      # uploaded full block: make it a 1-batch group
                M, L, i = M[None], L[None], 0
            g = groups.setdefault(id(M), (M, L, [], []))
            g[2].append(i)
            g[3].append(out_pos)
        first = next(iter(groups.values()))
        first[2].extend([first[2][0]] * (pad_to - S))  # padding repeats
        first[3].extend(range(S, pad_to))              # the first block
        parts = list(groups.values())
        dev = None
        if self._multi_dev:
            # a batch spanning shard boundaries mixes devices: normalize all
            # parts onto one (lowest-id) device — pure data movement
            devs = {d.id: d for M, _, _, _ in parts for d in M.devices()}
            dev = devs[min(devs)]
            if len(devs) > 1:
                parts = [(jax.device_put(M, dev), jax.device_put(L, dev),
                          idx, pos) for M, L, idx, pos in parts]
        M0, L0 = parts[0][0], parts[0][1]
        pool_M = jnp.zeros((pad_to,) + M0.shape[1:], M0.dtype, device=dev)
        pool_L = jnp.zeros((pad_to,) + L0.shape[1:], L0.dtype, device=dev)
        for M, L, idx, pos in parts:
            b = ops.bucket_rows(len(idx))
            idx = np.asarray(idx + [idx[0]] * (b - len(idx)), np.int32)
            pos = np.asarray(pos + [pos[0]] * (b - len(pos)), np.int32)
            pool_M, pool_L = _place_rows(pool_M, pool_L, M, L,
                                         jnp.asarray(idx, device=dev),
                                         jnp.asarray(pos, device=dev))
        return pool_M, pool_L

    def _dev_entry(self, relation: str, segment: int):
        # contract: holds-lock
        """Pooled device block entry ``(M, L, idx_or_None)`` for one
        segment, producing/uploading on miss (shared by get_full_dev and
        get_full_dev_batch; one request count per call). Lock held."""
        self._check_poisoned(relation)
        self._bump(requests=1)
        self._count(relation, segment)
        key = (relation, segment)
        shard = int(self._seg_shard[segment])
        ent = self._dev_pool.get(key)
        if ent is None:
            launch = self._inflight.get(key)
            if launch is not None:
                # integration fills the device pool for the whole launch
                self._sync(launch)
                ent = self._dev_pool.get(key)
        if ent is None:
            Mh, Lh = self._fetch(relation, segment, full=True)
            # a cold miss dispatches a launch whose integration fills the
            # device pool — re-check before paying a host->device upload
            ent = self._dev_pool.get(key)
            if ent is None:
                pooled = True
                if self._injector is not None \
                        and self._injector.upload_fault(relation, segment,
                                                        shard):
                    # injected pool-upload OOM: drop every entry of this
                    # shard's pool (the standard OOM response — free, then
                    # retry once); a second failure serves the read
                    # un-pooled (degraded), or raises under degrade=False
                    self._dev_pool.clear_shard(shard)
                    if self._injector.upload_fault(relation, segment,
                                                   shard):
                        if not self._fault_policy.degrade:
                            raise PoolUploadError(
                                f"device block-pool upload failed twice "
                                f"for relation {relation!r}",
                                relation=relation, segment=segment,
                                shard=shard)
                        self._bump(degraded_reads=1)
                        pooled = False
                # uploads land on the segment's owning shard device, so the
                # per-shard pool really bounds that device's memory
                with span("consumer.upload"):
                    if self._multi_dev:
                        d = self.shard_plan.devices[shard]
                        ent = (jax.device_put(Mh, d), jax.device_put(Lh, d),
                               None)
                    else:
                        ent = (jnp.asarray(Mh), jnp.asarray(Lh), None)
                if pooled:
                    self._dev_pool.put(key, *ent)
                self._bump(devpool_uploads=1)
                self._bump_shard(shard, devpool_uploads=1)
                return ent
        self._bump(devpool_hits=1)
        self._bump_shard(shard, devpool_hits=1)
        return ent

    @spanned("consumer.read_dev")
    def get_full_dev_many(self, relations: Sequence[str],
                          segments: Sequence[int],
                          cols: Optional[Dict[str, int]] = None
                          ) -> ConsumerBatch:
        """Multi-relation device-batch read: one :class:`ConsumerBatch`
        serving the internal rows of ``segments`` across every relation in
        ``relations`` (all sharing one subject simplex kind) straight from
        the device block pool — the consumer pipeline's read primitive
        (docs/DESIGN.md §6).

        All misses are dispatched first through one round-robin
        ``prefetch_many`` (de-dup as usual), then each relation's internal
        rows are compacted into a single ``(rows_pad, width)`` device array
        with ONE fused gather straight off the retained launch array (the
        steady state; batches mixing several launches or uploaded blocks
        fall back to the :meth:`get_full_dev_batch` stacking) — no host
        copy of any block. ``cols`` optionally trims a relation's
        columns to a caller-proven degree bound (entries past the true max
        row count are all ``-1`` padding, so trimming is lossless); widths
        and the power-of-two row bucket are static per mesh, so the
        downstream consumer jits compile once.

        Blocking behavior, de-dup guarantee and stats counting are one
        :meth:`get_full_dev` per ``(relation, segment)``: every read is
        served by the device pool (``devpool_hits``) or a counted one-time
        upload (``devpool_uploads``) — never a host block read."""
        relations = tuple(relations)
        kind = relations[0][0]       # subject kind ("VV" subjects are V)
        for r in relations:
            if r[0] != kind:
                raise ValueError(
                    f"get_full_dev_many needs one subject kind per batch: "
                    f"{relations} mixes {kind!r} and {r[0]!r}")
        segments = [int(s) for s in segments]
        # host-side index assembly reads only immutable per-mesh tables, so
        # it runs OUTSIDE the engine lock — concurrent consumer threads
        # (docs/DESIGN.md §8) only serialize on the producer interaction
        n_int, _ = self.tables.counts(kind)
        iv = self.pre.interval(kind)
        ns_rows = [int(n_int[s]) for s in segments]
        n_rows = sum(ns_rows)
        rows_pad = ops.bucket_rows(n_rows)
        # flat (segment-slot * R + row) gather indices for the internal rows
        gid = np.empty(n_rows, dtype=np.int64)
        flat = np.zeros(rows_pad, dtype=np.int32)
        at = 0
        for j, (s, n) in enumerate(zip(segments, ns_rows)):
            gid[at:at + n] = np.arange(iv[s], iv[s] + n)
            flat[at:at + n] = np.arange(n, dtype=np.int32)  # + j*R below
            at += n
        gid_pad = np.full(rows_pad, -1, dtype=np.int64)
        gid_pad[:n_rows] = gid
        gid_dev = jnp.asarray(gid_pad.astype(np.int32))

        # producer interaction under the lock: prefetch + pool-entry
        # resolution (which may sync in-flight launches). Relations whose
        # circuit breaker is OPEN (docs/DESIGN.md §12) bypass the device
        # pool entirely: their blocks are read from the host cache
        # (degraded_reads) and assembled without touching the device arm.
        with self._consumer_entry("get_full_dev_many"):
            live = [r for r in relations if self._device_arm_ok(r)]
            if live:
                self._prefetch_many({r: segments for r in live})
            ents_by_rel = {r: [self._dev_entry(r, s) for s in segments]
                           for r in live}
            host_by_rel: Dict[str, list] = {}
            for r in relations:
                if r in ents_by_rel:
                    continue
                blocks = []
                for s in segments:
                    self._bump(requests=1, degraded_reads=1)
                    self._count(r, s)
                    blocks.append(self._fetch(r, s, full=True))
                host_by_rel[r] = blocks

        # the gathers run on held array references — outside the lock
        M: Dict[str, jnp.ndarray] = {}
        L: Dict[str, jnp.ndarray] = {}
        for r in relations:
            if r in host_by_rel:
                # degraded read: assemble the internal rows on the host in
                # exactly _gather_internal's layout (-1/0 bucket padding,
                # columns trimmed to w) and upload once — bit-identical to
                # the pooled gather output
                w = self.deg[r]
                if cols and r in cols:
                    w = min(w, max(int(cols[r]), 1))
                Mh = np.full((rows_pad, w), -1, dtype=np.int32)
                Lh = np.zeros(rows_pad, dtype=np.int32)
                at = 0
                for (Mb, Lb), n in zip(host_by_rel[r], ns_rows):
                    Mh[at:at + n] = Mb[:n, :w]
                    Lh[at:at + n] = Lb[:n]
                    at += n
                M[r], L[r] = jnp.asarray(Mh), jnp.asarray(Lh)
                continue
            # fast path: every segment's block lives in ONE retained launch
            # (the common steady state) — a single fused gather straight off
            # the launch array, no per-segment slicing or stacking
            ents = ents_by_rel[r]
            aid = id(ents[0][0])
            if (all(e[2] is not None for e in ents)
                    and all(id(e[0]) == aid for e in ents)):
                pool_M, pool_L = ents[0][0], ents[0][1]
                R = pool_M.shape[1]
                off = np.zeros(rows_pad, dtype=np.int32)
                at = 0
                for (_, _, i), n in zip(ents, ns_rows):
                    off[at:at + n] = i * R
                    at += n
                flat_dev = jnp.asarray(flat + off)
            else:        # mixed launches / uploads: generic stacked gather
                pool_M, pool_L = self._stack_entries(
                    ents, ops.bucket_rows(len(ents)))
                R = pool_M.shape[1]
                off = np.zeros(rows_pad, dtype=np.int32)
                at = 0
                for j, n in enumerate(ns_rows):
                    off[at:at + n] = j * R
                    at += n
                flat_dev = jnp.asarray(flat + off)
            w = pool_M.shape[2]
            if cols and r in cols:
                w = min(w, max(int(cols[r]), 1))
            M[r], L[r] = _gather_internal(pool_M, pool_L, flat_dev,
                                          gid_dev, w)
        return ConsumerBatch(kind=kind, segments=tuple(segments),
                             n_rows=n_rows, gid=gid, gid_dev=gid_dev,
                             M=M, L=L)

    def dev_inverse(self, kind: str, shard: Optional[int] = None):
        """Device inverse-map columns for simplex kind ``E``/``F``/``T``:
        ``(inv_seg, inv_gid, inv_row, inv_key_or_None, n_global)``.
        ``inv_key`` is only staged when the combined ``seg * n_global + gid``
        key fits i32 (the ``jnp.searchsorted`` oracle); the split columns
        always support the lexicographic binary search.

        With ``shard=k`` on a multi-device plan the columns are replicated
        to shard k's device (staged lazily, once per (kind, shard)) so the
        per-shard completion resolve runs without cross-device traffic
        (docs/DESIGN.md §9); the maps are global either way — resolving a
        neighbour row in *any* segment is exactly what the exchange step
        needs."""
        if kind not in self._inv_nglob:
            raise KeyError(f"no device inverse map for kind {kind!r}")
        base = (self._dev[f"inv_seg_{kind}"], self._dev[f"inv_gid_{kind}"],
                self._dev[f"inv_row_{kind}"],
                self._dev.get(f"inv_key_{kind}"), self._inv_nglob[kind])
        if shard is None or not self._multi_dev:
            return base
        key = (kind, int(shard))
        with self._cond:
            cached = self._inv_shard.get(key)
        if cached is None:
            d = self.shard_plan.devices[shard]
            # stage OUTSIDE the lock (device transfer), publish under it;
            # a concurrent duplicate staging is idempotent
            cached = tuple(jax.device_put(a, d) if a is not None else None
                           for a in base[:4]) + (base[4],)
            with self._cond:
                self._inv_shard[key] = cached
        return cached

    def get_batch(self, relation: str, segments: Sequence[int]):
        """Fetch several segments' (M, L) blocks as a list.

        All misses are enqueued first and produced in one batched launch
        (plus lookahead), then each block is read as in :meth:`get`; the
        call blocks until every requested block is ready. Duplicate segment
        ids in ``segments`` are served from the same produced block — the
        de-dup guarantee is per ``(relation, segment)``, not per call."""
        with self._consumer_entry("get_batch"):
            segments = [int(s) for s in segments]
            self._bump(requests=len(segments))
            for s in segments:
                self._count(relation, s)
            missing = [s for s in segments
                       if (relation, s) not in self.cache
                       and (relation, s) not in self._inflight]
            if missing:
                self._request(relation, missing)
                self._drain([relation])
            return [self._fetch(relation, s) for s in segments]

    def prefetch(self, relation: str, segments: Sequence[int]) -> None:
        """Traversal-order hint: enqueue + dispatch without blocking.

        Returns as soon as the kernels are *dispatched*; the launches land in
        the in-flight futures table and retire either opportunistically
        (when a later call finds them ready) or at the first blocking read.
        Segments already cached / in flight / pending are skipped entirely
        (de-dup), so repeated prefetch of a traversal window is free."""
        with self._consumer_entry("prefetch"):
            self._request(relation, segments)
            self._drain([relation])

    def prefetch_many(self, requests: Dict[str, Sequence[int]]) -> None:
        """Prefetch several relations at once without blocking; launches are
        dispatched round-robin across relations so their kernels are all in
        flight before the consumer resumes. Equivalent to one
        :meth:`prefetch` per relation but interleaves dispatch fairly;
        unknown relations are ignored. Same de-dup guarantee as
        :meth:`prefetch`."""
        with self._consumer_entry("prefetch_many"):
            self._prefetch_many(requests)

    def _prefetch_many(self, requests: Dict[str, Sequence[int]]) -> None:
        # contract: holds-lock
        for r, segs in requests.items():
            if r in self.queues:
                self._request(r, segs)
        self._drain([r for r in requests if r in self.queues])

    def local_rows(self, kind: str, segs: np.ndarray,
                   gids: np.ndarray) -> np.ndarray:
        """Vectorized ``(segment, global id) -> local block row`` for simplex
        kind ``V``/``E``/``F``/``T`` (``-1`` where absent) via the inverse
        maps built at table time — the row index to use with
        :meth:`get_full`. Host-side, non-blocking."""
        return self.tables.local_rows(kind, segs, gids)

    # -- leader-producer side -----------------------------------------------

    def _count(self, relation: str, segment: int) -> None:
        # contract: holds-lock
        key = (relation, segment)
        if key in self.cache:
            self._bump(cache_hits=1)
        elif key in self._inflight:
            self._bump(cache_hits=1, inflight_hits=1)
        else:
            self._bump(cache_misses=1)

    def _fetch(self, relation: str, segment: int, full: bool = False
               ) -> Tuple[np.ndarray, np.ndarray]:
        # contract: holds-lock
        """Stat-free read: serve from cache, else sync the in-flight launch,
        else queue-jump + dispatch + sync. Used by get()/get_full()/
        get_batch(); ``full`` keeps external + padding rows. Lock held
        (only :meth:`_sync` may release it while waiting on the device)."""
        self._check_poisoned(relation)
        key = (relation, segment)
        while True:
            hit = self.cache.get(key)
            if hit is not None:
                break
            launch = self._inflight.get(key)
            if launch is None:
                t0 = time.perf_counter()
                # a blocking miss jumps the queue (consumer is stalled on
                # it); at the queue front it integrates last (MRU), so its
                # own launch can never evict it and the loop terminates
                q = self.queues[relation]
                if segment in q:
                    q.remove(segment)
                q.insert(0, segment)
                self._bump(t_queue=time.perf_counter() - t0)
                launch = self._dispatch(relation)
            if launch is not None:
                self._sync(launch)
            # loop: a prefetched launch's own integration may have
            # LRU-evicted this segment (cache smaller than the launch, or a
            # concurrent consumer's integrations), in which case it must be
            # re-dispatched, now at the batch front; a self-dispatched
            # launch always syncs under one continuous lock hold, so the
            # MRU put guarantees the re-read hits and the loop terminates
        M, L, n_rows = hit
        # cached blocks are host ndarrays (see _integrate), so the views
        # need no conversion — and converting under the lock would trip
        # contractcheck's blocking-under-lock rule
        return (M, L) if full else (M[:n_rows], L[:n_rows])

    def _drain(self, relations: Optional[Sequence[str]] = None) -> None:
        # contract: holds-lock
        """Round-robin one bounded pass over the pending queues, dispatching
        up to ``batch_max`` segments per relation per turn so several
        relation kernels can be in flight at once. The budget is fixed at
        entry: lookahead overflow requeued by a dispatch does not extend
        this pass (production rolls forward on later calls instead)."""
        rels = [r for r in (relations or self.relations) if self.queues[r]]
        budgets = {r: len(self.queues[r]) for r in rels}
        progress = True
        while progress:
            progress = False
            for r in rels:
                if budgets[r] <= 0 or not self.queues[r]:
                    continue
                before = len(self.queues[r])
                self._dispatch(r)
                budgets[r] -= max(1, before - len(self.queues[r]))
                progress = True
        self._harvest()

    def _harvest(self) -> None:
        # contract: holds-lock
        """Retire completed in-flight launches into the cache without
        blocking (zero-wait integration of finished futures). Launches a
        consumer thread is already syncing are left to that thread."""
        for launch in self._flights:
            if not launch.done and not launch.syncing and launch.is_ready():
                self._integrate(launch)
        if any(l.done for l in self._flights):
            self._flights = collections.deque(
                l for l in self._flights if not l.done)

    def _sync(self, launch: _Launch) -> None:
        # contract: holds-lock
        """Block until a dispatched launch is ready (consumer wait — the
        paper's Fig. 10 'waiting' metric) and integrate it exactly once.

        Lock held exactly once on entry. The first consumer to need the
        launch becomes its *syncer*: it releases the lock for the device
        wait, re-acquires, and integrates. Concurrent consumers needing the
        same launch wait on the condition variable instead of issuing a
        second device wait; each accounts its own wall-clock wait in
        ``t_sync`` (so per-worker sync time reflects real consumer stalls).
        If the syncer fails before integrating (e.g. the launch overflows
        ``deg[relation]`` — :class:`RelationWidthError`), a waiter takes
        over and surfaces the same error instead of hanging.

        Sync watchdog (docs/DESIGN.md §12): with ``sync_timeout_s`` set,
        the syncer's device wait is a bounded poll; a launch that fails to
        become ready within the window costs one ``sync_timeouts`` and is
        re-waited up to ``max_attempts`` times, after which the launch is
        FAILED (:meth:`_fail_launch`): waiters wake immediately instead of
        hanging on the condvar, the breaker records the failure, and
        callers re-dispatch the segments (degrading to the host arm once
        the breaker opens).

        Every wait, a waiter's included, is one ``engine.sync`` span and
        lands in ``t_sync``; the integration after it is its own
        ``engine.integrate`` span."""
        if launch.done or launch.error is not None:
            return
        with self._timed("engine.sync", "t_sync", relation=launch.relation):
            synced = self._await_launch(launch)
        if synced is None:            # syncer failed: take over the sync
            self._sync(launch)
        elif synced:
            self._integrate(launch)
            self._cond.notify_all()

    def _await_launch(self, launch: _Launch) -> Optional[bool]:
        # contract: holds-lock
        """The wait of :meth:`_sync`. Returns True where this thread synced
        the launch and has to integrate it; False where nothing is left to
        do (another syncer integrated it, or the launch failed and the
        caller re-dispatches); None where the syncer this thread waited on
        gave up without integrating."""
        if launch.syncing:
            while launch.syncing and not launch.done \
                    and launch.error is None:
                self._cond.wait()   # contract: syncer-handoff
            if launch.error is None and not launch.done:
                return None
            return False
        launch.syncing = True
        try:
            while True:
                self._cond.release()
                try:
                    # the ONE device wait that runs lock-free (released
                    # above, re-acquired below)  # contract: syncer-handoff
                    try:
                        self._device_wait(launch)
                        timed_out = None
                    except SyncTimeoutError as exc:
                        timed_out = exc
                finally:
                    self._cond.acquire()
                if timed_out is None:
                    break
                self._bump(sync_timeouts=1)
                launch.sync_attempts += 1
                if launch.error is not None:
                    break             # failed meanwhile (shard loss)
                if launch.sync_attempts >= self._fault_policy.max_attempts:
                    self._fail_launch(launch, timed_out)
                    self._breaker_failure(launch.relation, timed_out)
                    return False
                self._bump(retries=1)
        finally:
            launch.syncing = False
            self._cond.notify_all()
        return launch.error is None

    def _device_wait(self, launch: _Launch) -> None:
        """Device wait for one launch, called by the syncer with the engine
        lock RELEASED (lock-free: this helper never touches shared engine
        state). With no ``sync_timeout_s`` this is the plain blocking wait;
        with the watchdog armed it polls readiness and raises
        :class:`SyncTimeoutError` when the window expires."""
        timeout = self._fault_policy.sync_timeout_s
        if timeout is None:
            jax.block_until_ready((launch.M, launch.L))
            wait = launch.hang_until - time.monotonic()
            if wait > 0:              # injected hang, no watchdog armed
                time.sleep(wait)
            return
        deadline = time.monotonic() + timeout
        poll = max(float(self._fault_policy.sync_poll_s), 1e-4)
        while True:
            if launch.is_ready():
                jax.block_until_ready((launch.M, launch.L))
                return
            if time.monotonic() >= deadline:
                raise SyncTimeoutError(
                    f"launch for relation {launch.relation!r} not ready "
                    f"after {timeout}s (segments {list(launch.segments)!r})",
                    timeout_s=timeout, relation=launch.relation,
                    segment=launch.segments[0] if launch.segments else None,
                    shard=launch.shard,
                    attempt=launch.sync_attempts + 1)
            time.sleep(poll)

    def _fail_launch(self, launch: _Launch, exc: BaseException) -> None:
        # contract: holds-lock
        """Abandon a dispatched launch after a terminal fault: record the
        error (waking condvar waiters), deregister its segments from the
        in-flight table so they can re-dispatch, and reverse the
        dispatch-time production counters — ``segments_produced`` keeps
        meaning "distinct blocks actually produced". Idempotent."""
        if launch.done or launch.error is not None:
            return
        launch.error = exc
        for s in launch.segments:
            if self._inflight.get((launch.relation, s)) is launch:
                self._inflight.pop((launch.relation, s))
        try:
            self._flights.remove(launch)
        except ValueError:
            pass
        n = len(launch.segments)
        self._bump(failed_launches=1, failed_segments=n,
                   kernel_launches=-1, segments_produced=-n)
        self._bump_shard(launch.shard, failed_launches=1, failed_segments=n,
                         kernel_launches=-1, segments_produced=-n)
        self._cond.notify_all()

    # -- per-relation circuit breaker (docs/DESIGN.md §12) -------------------

    def _breaker_failure(self, relation: str, exc: BaseException) -> None:
        # contract: holds-lock
        """Record one device-arm failure; after ``breaker_threshold``
        consecutive failures the breaker OPENS: production and
        ``get_full_dev_many`` reads degrade to the host arm until the
        cooldown expires (then one launch probes the device arm again).
        A failure while open re-arms the cooldown."""
        b = self._breaker.setdefault(
            relation, {"failures": 0, "open": False, "open_until": 0.0,
                       "exc": None})
        b["failures"] += 1
        b["exc"] = exc
        if b["open"]:
            b["open_until"] = (time.monotonic()
                               + self._fault_policy.breaker_cooldown_s)
        elif b["failures"] >= self._fault_policy.breaker_threshold:
            b["open"] = True
            b["open_until"] = (time.monotonic()
                               + self._fault_policy.breaker_cooldown_s)
            self._bump(breaker_trips=1)

    def _breaker_success(self, relation: str) -> None:
        # contract: holds-lock
        """A device-arm launch succeeded: reset the consecutive-failure
        count; if the breaker was open this was the cooldown probe — close
        it (``breaker_recoveries``) and return reads to the device arm."""
        b = self._breaker.get(relation)
        if b is None:
            return
        if b["open"]:
            b["open"] = False
            self._bump(breaker_recoveries=1)
        b["failures"] = 0

    def _device_arm_ok(self, relation: str) -> bool:
        # contract: holds-lock
        """True when the device arm may be tried: breaker closed, or open
        with an expired cooldown (the probe window)."""
        b = self._breaker.get(relation)
        if b is None or not b["open"]:
            return True
        return time.monotonic() >= b["open_until"]

    def _poison(self, relation: str, exc: BaseException) -> None:
        # contract: holds-lock
        if relation not in self._poisoned:
            self._poisoned[relation] = exc

    def _check_poisoned(self, relation: str) -> None:
        # contract: holds-lock
        exc = self._poisoned.get(relation)
        if exc is not None:
            raise RelationPoisonedError(
                f"relation {relation!r} permanently failed earlier "
                f"(fault_policy.degrade is off); the engine cannot serve "
                f"it", relation=relation) from exc

    def _backoff_sleep(self, attempt: int) -> None:
        # contract: holds-lock
        """Exponential backoff between launch retry attempts. The sleep
        itself runs with the engine lock RELEASED — sleeping under the lock
        would stall every consumer thread (§8 blocking-under-lock
        contract); the caller re-filters its batch against cache +
        in-flight after the gap, so the de-dup guarantee survives the
        window."""
        delay = float(self._fault_policy.backoff_s) * (
            float(self._fault_policy.backoff_factor) ** max(attempt - 1, 0))
        if delay <= 0:
            return
        self._cond.release()
        try:
            # lock released above, re-acquired below
            time.sleep(delay)   # contract: backoff-sleep
        finally:
            self._cond.acquire()

    def _rehome_shard(self, lost: int, exc: BaseException) -> bool:
        # contract: holds-lock
        """Whole-shard device loss (docs/DESIGN.md §12): re-home the lost
        shard onto the first surviving shard — fail its un-synced flights
        (their device arrays are gone), drop + re-route its device pool
        through :meth:`BlockStore.rehome`, re-stage its table slice on the
        survivor's device, and point its ``ShardPlan`` slot there. Segment
        *attribution* (``_seg_shard``, per-shard stats) stays logical, so
        the per-shard production partition is untouched. Returns ``False``
        when no surviving shard exists (single-shard engines degrade to
        the host arm instead)."""
        if lost in self._lost_shards:
            return True               # already re-homed; retry proceeds
        survivors = [k for k in range(self.n_shards)
                     if k != lost and k not in self._lost_shards]
        if not survivors:
            return False
        target = survivors[0]
        self._lost_shards.add(lost)
        for launch in list(self._flights):
            if launch.shard == lost and not launch.done:
                self._fail_launch(launch, exc)
        self.store.rehome(lost, target)
        dev = (self.shard_plan.devices[target] if self._multi_dev else None)
        lo, hi = self.shard_plan.shard_bounds(lost)
        self._shard_tables[lost] = self._stage_shard_tables(lo, hi, dev)
        self.shard_plan = self.shard_plan.rehomed(lost, target)
        # drop the lost shard's lazily staged inverse-map replicas so the
        # next sharded resolve re-stages them on the new device
        for key in [k for k in self._inv_shard if k[1] == lost]:
            self._inv_shard.pop(key)
        self._bump(shards_lost=1, rehomed_segments=hi - lo)
        self._cond.notify_all()
        return True

    def _integrate(self, launch: _Launch) -> None:
        # contract: holds-lock
        if launch.done or launch.error is not None:
            return
        with self._timed("engine.integrate", "t_integrate",
                         relation=launch.relation):
            # One host copy per launch while the results are known-ready.
            # Cached blocks must be host arrays, not device views: a lazy
            # device slice would queue behind later in-flight kernels on the
            # single device stream, so reads of batch k would stall on batch
            # k+1's launch.
            Mh = np.asarray(launch.M)   # contract: syncer-handoff (ready)
            Lh = np.asarray(launch.L)   # contract: syncer-handoff (ready)
            # Preallocated-width contract (paper §4.6): L is the TRUE row count
            # while M holds at most deg entries, so L > deg means the
            # compaction silently dropped neighbours. Fail loudly with the fix.
            worst = int(Lh.max()) if Lh.size else 0
            deg = self.deg[launch.relation]
            if worst > deg:
                raise RelationWidthError(
                    f"relation {launch.relation!r} produced a row with "
                    f"{worst} entries but the preallocated width is "
                    f"deg[{launch.relation!r}]={deg}; the compacted M row "
                    f"would silently drop neighbours. Construct the engine "
                    f"with deg={{{launch.relation!r}: {worst}}} (or larger).")
            # Reverse order so the explicitly requested segments (batch front)
            # are most-recently-used and cannot be LRU-evicted by their own
            # lookahead when the cache is small.
            for i, s in reversed(list(enumerate(launch.segments))):
                self._inflight.pop((launch.relation, s), None)
                self.cache.put((launch.relation, s),
                               (Mh[i], Lh[i], launch.n_rows[i]))
                # device pool: keep the still-device-resident rows addressable
                # for get_full_dev (holds a reference to the launch arrays).
                # Degraded host-arm launches hold numpy arrays — never pooled;
                # device reads of their blocks go through the counted upload
                # path in _dev_entry instead.
                if not launch.host:
                    self._dev_pool.put((launch.relation, s),
                                       launch.M, launch.L, i)
            launch.done = True
            self._bump(evictions=self.cache.evictions
                       - self.stats.evictions)

    def _lookahead_segments(self, relation: str, batch: List[int]) -> List[int]:
        # contract: holds-lock
        """Extend a drained batch with subsequent segments (paper §4.5:
        'the workload ... includes not only the currently requested segments
        but also subsequent segments for proactive precomputation').

        De-dups against the cache, the in-flight table AND the relation's
        pending queue: a queued segment must not also enter a launch as
        lookahead — it stays queued, so its eventual pop dispatches it once
        instead of burning a ``_drain`` budget slot on a stale entry.

        Lookahead never crosses a shard boundary (``hi`` is the owning
        shard's end): launches are shard-pure, so a shard only ever produces
        its own segments (docs/DESIGN.md §9)."""
        hi = self.shard_plan.bounds[int(self._seg_shard[batch[0]]) + 1]
        out: List[int] = []
        seen = set(batch)
        queued = set(self.queues[relation])
        for s in batch:
            for d in range(1, self.lookahead + 1):
                n = s + d
                if (n < hi and n not in seen and n not in queued
                        and (relation, n) not in self.cache
                        and (relation, n) not in self._inflight):
                    seen.add(n)
                    out.append(n)
        return out

    def _dispatch(self, relation: str) -> Optional[_Launch]:
        # contract: holds-lock
        """Drain the queue for ``relation`` (up to ``batch_max``), add
        lookahead, and dispatch one batched kernel. Never blocks when
        ``async_dispatch`` is on: the returned launch holds device-array
        futures registered in the in-flight table.

        Launches are shard-pure: the first popped segment fixes the shard,
        queued segments of other shards stay queued (front, original order)
        for a later dispatch, and the kernel reads the shard's OWN sliced
        tables at shard-local indices — on a multi-device plan the whole
        launch therefore runs and lands on the owning shard's device
        (docs/DESIGN.md §9)."""
        with self._timed("engine.dispatch", "t_prepare", relation=relation):
            q = self.queues[relation]
            batch: List[int] = []
            shard = -1
            deferred: List[int] = []
            while q and len(batch) < self.batch_max:
                s = q.pop(0)
                # stale entry: produced since it was queued
                if ((relation, s) in self.cache
                        or (relation, s) in self._inflight):
                    continue
                if shard < 0:
                    shard = int(self._seg_shard[s])
                elif int(self._seg_shard[s]) != shard:
                    deferred.append(s)
                    continue
                batch.append(s)
            if deferred:
                q[0:0] = deferred
            if not batch:
                return None
            look = self._lookahead_segments(relation, batch)
            room = self.batch_max - len(batch)
            batch = batch + look[:room]
            if look[room:]:
                # the launch is capped at batch_max; overflow lookahead is
                # requeued so proactive production continues in later
                # launches
                qs = set(q)
                q.extend(s for s in look[room:] if s not in qs)
        return self._launch(relation, batch, shard)

    def _launch(self, relation: str, batch: List[int], shard: int
                ) -> Optional[_Launch]:
        # contract: holds-lock
        """Produce one drained batch through the §12 recovery ladder:

        1. breaker OPEN (cooldown running) -> host arm immediately;
        2. device arm; an injected/structured :class:`RelationError` feeds
           the breaker, and a *transient* one retries up to
           ``max_attempts`` with exponential backoff — the backoff sleeps
           with the lock RELEASED, and the batch is re-filtered against
           cache + in-flight afterwards so a segment is never produced
           twice even if another thread produced it during the gap;
        3. :class:`DeviceLostError` re-homes the shard (surviving shards'
           device + pool) and retries there;
        4. exhausted/permanent -> host arm (``degrade=True``, the default)
           or poison the relation and raise (``degrade=False``).

        Only :class:`RelationError` subclasses enter the ladder —
        :class:`RelationWidthError` (a data error, identical on every arm),
        :class:`KernelCompileError` (the compiler refused the requested
        backend's kernel) and non-taxonomy exceptions propagate
        unchanged."""
        policy = self._fault_policy
        attempt = 1
        while True:
            if not self._device_arm_ok(relation):
                if policy.degrade:
                    return self._launch_host(relation, batch, shard)
                b = self._breaker.get(relation) or {}
                self._poison(relation, b.get("exc") or RelationError(
                    "circuit breaker open", relation=relation, shard=shard))
                self._check_poisoned(relation)
            try:
                launch = self._launch_device(relation, batch, shard,
                                             attempt)
            except (RelationWidthError, KernelCompileError):
                raise                 # identical on every attempt and arm
            except RelationError as exc:
                if isinstance(exc, DeviceLostError) \
                        and attempt < policy.max_attempts \
                        and self._rehome_shard(shard, exc):
                    self._bump(retries=1)
                    attempt += 1
                    continue
                self._breaker_failure(relation, exc)
                transient = (getattr(exc, "transient", False)
                             and not isinstance(exc, DeviceLostError))
                if transient and attempt < policy.max_attempts:
                    self._bump(retries=1)
                    attempt += 1
                    self._backoff_sleep(attempt - 1)
                    # the backoff gap ran with the lock released: another
                    # thread may have produced part of the batch meanwhile
                    batch = self._refilter(relation, batch)
                    if not batch:
                        return None
                    continue
                if policy.degrade:
                    return self._launch_host(relation, batch, shard)
                self._poison(relation, exc)
                raise
            if launch is not None and launch.error is None:
                self._breaker_success(relation)
            return launch

    def _refilter(self, relation: str, batch: List[int]) -> List[int]:
        # contract: holds-lock
        """De-dup a retry batch against cache + in-flight after a window
        in which the lock was released (backoff sleep)."""
        return [s for s in batch
                if (relation, s) not in self.cache
                and (relation, s) not in self._inflight]

    def _launch_device(self, relation: str, batch: List[int], shard: int,
                       attempt: int) -> _Launch:
        # contract: holds-lock
        """One device-arm kernel launch (the pre-§12 ``_dispatch`` tail):
        pad to the power-of-two bucket, slice the shard's tables, dispatch
        the fused kernel, and register the in-flight launch. Injected
        faults surface here as :class:`RelationError` subclasses."""
        if self._injector is not None:
            exc = self._injector.launch_fault(relation, batch, attempt,
                                              shard)
            if exc is not None:
                raise exc
        kx, ky = RELATION_TABLES[relation]
        deg = self.deg[relation]
        nvl = self.tables.NV
        tabs = self._shard_tables[shard]
        with self._timed("engine.dispatch", "t_prepare", relation=relation):
            # pad the launch to a power-of-two bucket (duplicating the last
            # segment) so jit sees O(log batch_max) shapes, not one per drain
            b_pad = ops.bucket_rows(len(batch), self.bucket_floor)
            padded = batch + [batch[-1]] * (b_pad - len(batch))
            lo = self.shard_plan.bounds[shard]
            segs = jnp.asarray(np.asarray(padded, dtype=np.int32) - lo)
            if relation == "VV":
                tabX = jnp.take(tabs["T_local"], segs, axis=0)
                tabY = tabX
                colg = jnp.take(tabs["LV_global"], segs, axis=0)
            else:
                tabX = self._table_dev(kx, segs, tabs)
                tabY = self._table_dev(ky, segs, tabs)
                colg = jnp.take(tabs[_GLOBAL_NAME[ky]], segs, axis=0)

        # a kernel shape new to a multi-device engine compiles on every
        # shard device at once, not once per device as each shard meets it
        compile_on = ()
        key = (relation, tabX.shape, tabY.shape)
        if self._multi_dev and key not in self._compiled_on_all:
            self._compiled_on_all.add(key)
            compile_on = tuple({d.id: d for d in
                                self.shard_plan.devices}.values())
        with self._timed("engine.dispatch", "t_dispatch", relation=relation,
                         shard=shard):
            M, L = ops.relation_block(
                relation, tabX, tabY, colg, nvl, deg=deg,
                backend=self.backend, block_x=self.block_x,
                block_y=self.block_y, vv_block=self.vv_block,
                assembly=self.assembly, compile_on=compile_on)
        self._bump(kernel_launches=1, segments_produced=len(batch))
        self._bump_shard(shard, kernel_launches=1,
                         segments_produced=len(batch))

        n_int, _ = self.tables.counts(kx if relation != "VV" else "V")
        launch = _Launch(relation, batch, M, L,
                         [int(n_int[s]) for s in batch], shard=shard)
        if self._injector is not None:
            hang = self._injector.sync_hang_s(relation, batch, attempt,
                                              shard)
            if hang > 0:
                launch.hang_until = time.monotonic() + hang
        for s in batch:
            self._inflight[(relation, s)] = launch
        self._flights.append(launch)
        if not self.async_dispatch:
            self._sync(launch)
        else:
            # backpressure on genuinely unfinished launches only (reads
            # retire launches via _sync without removing them from here)
            if any(l.done for l in self._flights):
                self._flights = collections.deque(
                    l for l in self._flights if not l.done)
            if len(self._flights) > self.inflight_max:
                self._sync(self._flights.popleft())
        return launch

    def _launch_host(self, relation: str, batch: List[int], shard: int
                     ) -> _Launch:
        # contract: holds-lock
        """Degraded production on the HOST arm (docs/DESIGN.md §12): the
        numpy mirror kernel (:func:`ops.relation_block_host`) computes the
        batch bit-identically to the device arms; results integrate into
        the host cache immediately (nothing to sync) and the
        ``degraded_*`` counters record the detour. Host launches are never
        device-pooled — device reads of their blocks go through the
        counted upload path."""
        t = self.tables
        kx, ky = RELATION_TABLES[relation]
        with self._timed("engine.dispatch", "t_dispatch", relation=relation,
                         shard=shard):
            segs = np.asarray(batch, dtype=np.intp)
            if relation == "VV":
                tabX = tabY = t.T_local[segs]
                colg = t.LV_global[segs]
            else:
                tabX = self._table_host(kx, segs)
                tabY = self._table_host(ky, segs)
                colg = getattr(t, _GLOBAL_NAME[ky])[segs]
            Mh, Lh = ops.relation_block_host(relation, tabX, tabY, colg,
                                             t.NV, deg=self.deg[relation])
        n = len(batch)
        self._bump(kernel_launches=1, segments_produced=n,
                   degraded_launches=1, degraded_segments=n)
        self._bump_shard(shard, kernel_launches=1, segments_produced=n,
                         degraded_launches=1, degraded_segments=n)
        n_int, _ = t.counts(kx if relation != "VV" else "V")
        launch = _Launch(relation, batch, Mh, Lh,
                         [int(n_int[s]) for s in batch], shard=shard,
                         host=True)
        for s in batch:
            self._inflight[(relation, s)] = launch
        self._integrate(launch)
        return launch

    def _table_host(self, kind: str, segs: np.ndarray) -> np.ndarray:
        # contract: holds-lock
        """Host mirror of :meth:`_table_dev` over the full (unsliced) host
        tables; ``segs`` are GLOBAL segment ids."""
        if kind == "V":
            lv = self.tables.LV_global[segs]
            iota = np.arange(self.tables.NV, dtype=np.int32)
            return np.where(lv >= 0, iota[None, :], -1)[..., None]
        name = {"E": "E_local", "F": "F_local", "T": "T_local"}[kind]
        return getattr(self.tables, name)[segs]

    def _table_dev(self, kind: str, segs: jnp.ndarray,
                   tabs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        # contract: holds-lock
        """Stacked per-segment table for ``kind`` from one shard's sliced
        tables (``segs`` are shard-local indices)."""
        if kind == "V":
            # virtual vertex table: tab[v] = (v,) with -1 past n_loc
            lv = jnp.take(tabs["LV_global"], segs, axis=0)  # (B, NV)
            iota = jnp.arange(self.tables.NV, dtype=jnp.int32)
            tab = jnp.where(lv >= 0, iota[None, :], -1)
            return tab[..., None]
        name = {"E": "E_local", "F": "F_local", "T": "T_local"}[kind]
        return jnp.take(tabs[name], segs, axis=0)

    # -- boundary relations (consumer-side, no accelerator — paper §4.4) ----

    def boundary_EV(self, edge_ids) -> np.ndarray:
        return self.pre.E[np.asarray(edge_ids)]

    def boundary_FV(self, face_ids) -> np.ndarray:
        return self.pre.F[np.asarray(face_ids)]

    def boundary_TV(self, tet_ids) -> np.ndarray:
        return self.smesh.tets[np.asarray(tet_ids)]

    def boundary_FE(self, face_ids) -> np.ndarray:
        """Edges of each face, via interval-bounded lookups (paper's example
        in §4.4: binary search inside the owner segment's E range)."""
        from .mesh import edge_lookup
        F = self.pre.F[np.asarray(face_ids)]
        nv = self.smesh.n_vertices
        e0 = edge_lookup(self.pre.E_keys, nv, F[:, 0], F[:, 1])
        e1 = edge_lookup(self.pre.E_keys, nv, F[:, 0], F[:, 2])
        e2 = edge_lookup(self.pre.E_keys, nv, F[:, 1], F[:, 2])
        return np.stack([e0, e1, e2], axis=1)

    def boundary_TE(self, tet_ids) -> np.ndarray:
        from .mesh import _EDGE_COMBOS, edge_lookup
        T = self.smesh.tets[np.asarray(tet_ids)]
        nv = self.smesh.n_vertices
        cols = [edge_lookup(self.pre.E_keys, nv, T[:, a], T[:, b])
                for a, b in _EDGE_COMBOS]
        return np.stack(cols, axis=1)

    def boundary_TF(self, tet_ids) -> np.ndarray:
        from .mesh import _FACE_COMBOS, face_lookup
        T = self.smesh.tets[np.asarray(tet_ids)]
        nv = self.smesh.n_vertices
        cols = [face_lookup(self.pre.F_keys, nv, T[:, a], T[:, b], T[:, c])
                for a, b, c in _FACE_COMBOS]
        return np.stack(cols, axis=1)


_GLOBAL_NAME = {"V": "LV_global", "E": "LE_global",
                "F": "LF_global", "T": "LT_global"}
