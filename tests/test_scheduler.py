"""Thread-parallel consumer scheduler (core/scheduler.py, DESIGN.md §8):

  - deterministic in-order reduction for any worker count,
  - workers=1 vs workers=4 bit-identity for all three TDA drivers on the
    engine AND the explicit baseline,
  - per-worker EngineStats breakdown merge round-trip,
  - a raising worker propagates its error instead of hanging the pool,
  - concurrent get_batch / device reads never under/over-count stats.

Every multi-threaded test joins with a timeout so a deadlock fails the
test instead of hanging the suite (CI additionally wraps the whole job in
a hard ``timeout``).
"""

import threading

import numpy as np
import pytest

from repro.algorithms import fields
from repro.algorithms.critical_points import critical_points, total_order
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale
from repro.core.adjacency import complete_adjacency
from repro.core.engine import EngineStats, RelationEngine, RelationWidthError
from repro.core.explicit import ExplicitTriangulation
from repro.core.mesh import segment_mesh
from repro.algorithms.persistence import persistence_pairs
from repro.core.scheduler import partition, run_collect, run_partitioned
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid

RELS = ["VV", "VE", "VF", "VT", "FT", "TT"]
INT_FIELDS = ("requests", "cache_hits", "inflight_hits", "cache_misses",
              "kernel_launches", "segments_produced", "evictions",
              "devpool_hits", "devpool_uploads", "completion_chunks",
              "completion_queries", "completion_fanout_blocks",
              "completion_raw_neighbors", "completion_neighbors")


@pytest.fixture(scope="module")
def setup():
    mesh = structured_grid(8, 8, 7, jitter=0.2, seed=3,
                           scalar_fn=fields.gaussians(5, k=4, sigma=3.0))
    sm = segment_mesh(mesh, capacity=40)
    pre = precondition(sm, relations=RELS)
    rank = total_order(sm.scalars)
    return sm, pre, rank


# ---- pure scheduler mechanics ---------------------------------------------

def test_partition_strided_and_ordered():
    assert partition(7, 3) == [[0, 3, 6], [1, 4], [2, 5]]
    assert partition(2, 8) == [[0], [1]]   # never more workers than items
    assert partition(0, 4) == []
    for share in partition(23, 5):
        assert share == sorted(share)      # global order preserved


@pytest.mark.parametrize("workers", [1, 2, 4, 8])
def test_reduce_runs_in_order_for_any_worker_count(workers):
    items = list(range(17))
    reduced = []

    def consume(i, item):
        return item * 10

    def finalize(inter):
        return inter + 1

    run_partitioned(items, consume, lambda i, r: reduced.append((i, r)),
                    workers=workers, finalize=finalize)
    assert reduced == [(i, i * 10 + 1) for i in items]


@pytest.mark.parametrize("workers", [2, 4])
def test_worker_exception_propagates_not_hangs(workers):
    """A worker raising mid-stream must abort the pool and re-raise the
    error on the caller — never hang the remaining workers or the caller's
    in-order reduce loop."""
    def consume(i, item):
        if i == 5:
            raise RelationWidthError("boom at 5")
        return i

    done = []
    with pytest.raises(RelationWidthError, match="boom at 5"):
        run_partitioned(list(range(32)), consume,
                        lambda i, r: done.append(i), workers=workers)
    assert done == sorted(done)            # whatever reduced stayed ordered
    # no scheduler worker threads left behind
    assert not [t for t in threading.enumerate()
                if t.name.startswith("consumer-")]


def test_prefetch_depth1_double_buffer_per_worker():
    """Each worker prefetches its NEXT own item before consuming the
    current one, and finalizes item k only after item k+1 was consumed
    (the per-worker depth-1 double buffer)."""
    log = []

    def prefetch(item):
        log.append(("prefetch", item))

    def consume(i, item):
        log.append(("consume", item))
        return item

    def finalize(inter):
        log.append(("finalize", inter))
        return inter

    run_partitioned([10, 11, 12], consume, lambda i, r: None, workers=1,
                    prefetch=prefetch, finalize=finalize)
    assert log == [
        ("prefetch", 10), ("prefetch", 11), ("consume", 10),
        ("prefetch", 12), ("consume", 11), ("finalize", 10),
        ("consume", 12), ("finalize", 11), ("finalize", 12)]


@pytest.mark.parametrize("workers", [1, 3, 8])
def test_run_collect_returns_items_in_order(workers):
    """run_collect is run_partitioned with the list-building reduce: the
    result list is in item order for any worker count, finalize applies."""
    items = list(range(17))
    out = run_collect(items, lambda i, x: x * x, workers=workers,
                      finalize=lambda r: r + 1)
    assert out == [x * x + 1 for x in items]
    assert run_collect([], lambda i, x: x, workers=workers) == []


# ---- driver bit-identity across worker counts -----------------------------

def _run_all(ds, pre, rank, workers, consumer="auto"):
    t, cp = critical_points(ds, pre, rank, batch_segments=4,
                            consumer=consumer, workers=workers)
    g = discrete_gradient(ds, pre, rank, batch_segments=4,
                          consumer=consumer, workers=workers)
    ms = morse_smale(ds, pre, g, batch_segments=4, consumer=consumer,
                     workers=workers)
    pd = persistence_pairs(ds, pre, rank, grad=g, batch_segments=4,
                           consumer=consumer, workers=workers)
    return t, cp, g, ms, pd


def _assert_identical(a, b):
    ta, cpa, ga, msa, pda = a
    tb, cpb, gb, msb, pdb = b
    np.testing.assert_array_equal(ta, tb)
    assert cpa == cpb
    for f in ("pair_v2e", "pair_e2f", "pair_f2t", "pair_e2v", "pair_f2e",
              "pair_t2f", "crit_v", "crit_e", "crit_f", "crit_t"):
        np.testing.assert_array_equal(getattr(ga, f), getattr(gb, f))
    for f in ("dest_min", "dest_max", "saddle1_ends", "saddle2_ends"):
        np.testing.assert_array_equal(getattr(msa, f), getattr(msb, f))
    assert pda.digest() == pdb.digest()


def test_drivers_bit_identical_across_workers_engine(setup):
    sm, pre, rank = setup
    ref = _run_all(RelationEngine(pre, RELS, lookahead=4), pre, rank, 1)
    for w in (2, 4):
        eng = RelationEngine(pre, RELS, lookahead=4)
        _assert_identical(ref, _run_all(eng, pre, rank, w))
        # zero duplicate production under concurrency: every block produced
        # exactly once (big cache -> no evictions -> produced == distinct)
        assert eng.stats.evictions == 0
        assert eng.stats.segments_produced == len(eng.cache)


def test_drivers_bit_identical_across_workers_explicit(setup):
    sm, pre, rank = setup
    ref = _run_all(ExplicitTriangulation(pre, RELS), pre, rank, 1)
    for w in (2, 4):
        _assert_identical(
            ref, _run_all(ExplicitTriangulation(pre, RELS), pre, rank, w))
    # and the baseline agrees with the engine
    _assert_identical(
        ref, _run_all(RelationEngine(pre, RELS, lookahead=4), pre, rank, 4))


def test_drivers_bit_identical_host_consumer_workers(setup):
    """The host consumer arm threads through the same scheduler."""
    sm, pre, rank = setup
    ref = _run_all(RelationEngine(pre, RELS, lookahead=4), pre, rank, 1,
                   consumer="host")
    eng = RelationEngine(pre, RELS, lookahead=4)
    _assert_identical(ref, _run_all(eng, pre, rank, 3, consumer="host"))


# ---- per-worker stats ------------------------------------------------------

def test_worker_stats_merge_round_trip(setup):
    sm, pre, rank = setup
    eng = RelationEngine(pre, RELS, lookahead=4)
    _run_all(eng, pre, rank, 4)
    # the drivers' completions fit one chunk of the device arm's rule on
    # this mesh; a completion cut in explicit chunks spans all 4 workers
    complete_adjacency(eng, "TT", np.arange(sm.n_tets), batch=64,
                       path="device", workers=4)
    assert set(eng.worker_stats) >= {"w0", "w1", "w2", "w3"}
    merged = eng.merged_worker_stats()
    s = eng.stats
    for f in INT_FIELDS:
        assert getattr(merged, f) == getattr(s, f), f
    for f in ("t_enqueue", "t_queue", "t_prepare", "t_dispatch", "t_sync",
              "t_integrate"):
        assert getattr(merged, f) == pytest.approx(getattr(s, f)), f
    # deterministic merge: same parts, same result
    again = eng.merged_worker_stats()
    assert again.as_dict() == merged.as_dict()


def test_engine_stats_merged_is_sum():
    a = EngineStats(requests=3, cache_hits=1, t_sync=0.5)
    b = EngineStats(requests=4, cache_misses=2, t_sync=0.25)
    m = EngineStats.merged([a, b])
    assert (m.requests, m.cache_hits, m.cache_misses) == (7, 1, 2)
    assert m.t_sync == pytest.approx(0.75)
    assert EngineStats.merged([]).as_dict() == EngineStats().as_dict()


def test_concurrent_get_batch_never_miscounts(setup):
    """Satellite regression: EngineStats counters used to be plain ints
    mutated from consumer paths — concurrent consumers must never lose or
    double-apply updates. Drive overlapping get_batch + device reads from
    several threads and check the conservation laws."""
    sm, pre, rank = setup
    eng = RelationEngine(pre, ["VV", "VT"], lookahead=3, batch_max=8,
                         cache_segments=4096)
    ns = sm.n_segments
    n_threads, rounds = 6, 8
    seglists = [[(w * 3 + r) % ns, (w * 5 + 2 * r + 1) % ns,
                 (w + 7 * r) % ns] for w in range(n_threads)
                for r in range(rounds)]
    # per round: 2 get_batch (one request per segment), one
    # get_full_dev_many (one request per unique (relation, segment)), one
    # get — the conservation laws below must hold to the exact count
    n_many = sum(2 * len(set(sl)) for sl in seglists)
    expected_requests = (sum(2 * len(sl) for sl in seglists)
                         + n_many + n_threads * rounds)
    errors = []

    def worker(w):
        try:
            with eng.worker_scope(f"w{w}"):
                for r in range(rounds):
                    sl = seglists[w * rounds + r]
                    eng.get_batch("VV", sl)
                    eng.get_batch("VT", sl)
                    eng.get_full_dev_many(("VV", "VT"), sorted(set(sl)))
                    eng.get("VV", sl[0])
        except BaseException as e:   # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
        assert not t.is_alive(), "deadlocked consumer thread"
    assert not errors

    s = eng.stats
    # conservation: every request classified exactly once
    assert s.cache_hits + s.cache_misses == s.requests
    assert s.requests == expected_requests
    # every device read is a pool hit or a counted upload — none lost
    assert s.devpool_hits + s.devpool_uploads == n_many
    # no duplicate production: big cache, so produced == distinct blocks
    assert s.evictions == 0
    assert s.segments_produced == len(eng.cache)
    # per-worker breakdown sums back exactly (ints) / approx (float time)
    merged = eng.merged_worker_stats()
    for f in INT_FIELDS:
        assert getattr(merged, f) == getattr(s, f), f
    assert merged.t_sync == pytest.approx(s.t_sync)
    assert s.t_sync >= 0.0


# ---- error propagation through the drivers --------------------------------

def test_worker_width_error_propagates_from_driver(setup):
    """Regression: a worker hitting RelationWidthError (produced row wider
    than deg[relation]) must surface the error through the pool — with the
    fix hint — instead of hanging the other consumers."""
    sm, pre, rank = setup
    eng = RelationEngine(pre, ["VV", "VT"], lookahead=2, deg={"VT": 2})
    with pytest.raises(RelationWidthError, match=r"deg\['VT'\]"):
        critical_points(eng, pre, rank, batch_segments=4, workers=4)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("critical_points-")]
