"""Engine behaviour: multi-queue accounting, lookahead, LRU eviction,
boundary relations, baselines, and waiting-time stats plumbing."""

import numpy as np
import pytest

from repro.algorithms import fields
from repro.core.engine import RelationEngine, RelationWidthError
from repro.core.explicit import (ActopoDS, ExplicitTriangulation,
                                 TopoClusterDS)
from repro.core.mesh import segment_mesh
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid, two_tets


@pytest.fixture(scope="module")
def setup():
    mesh = structured_grid(8, 8, 8, scalar_fn=fields.gaussians(1, k=3,
                                                               sigma=3.0))
    sm = segment_mesh(mesh, capacity=32)
    pre = precondition(sm, relations=["VV", "VT", "VE", "VF", "EF", "ET",
                                      "FT"])
    return sm, pre


def test_lookahead_precomputes_ahead(setup):
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=4, cache_segments=256)
    eng.get("VV", 0)
    # segments 1..4 were produced proactively -> hits, no new launch
    launches = eng.stats.kernel_launches
    for s in (1, 2, 3, 4):
        eng.get("VV", s)
    assert eng.stats.kernel_launches == launches
    assert eng.stats.cache_hits >= 4


def test_lru_eviction(setup):
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=0, batch_max=1,
                         cache_segments=2)
    for s in range(5):
        eng.get("VV", s)
    assert len(eng.cache) <= 2
    assert eng.cache.evictions >= 3
    # re-fetch of evicted segment still correct
    M, L = eng.get("VV", 0)
    ex = ExplicitTriangulation(pre, ["VV"])
    Me, Le = ex.get("VV", 0)
    assert (L == Le).all()


def test_multi_queue_isolation(setup):
    sm, pre = setup
    eng = RelationEngine(pre, ["VV", "VT"], lookahead=0)
    eng.request("VV", [1, 2])
    eng.request("VT", [3])
    assert eng.queues["VV"] == [1, 2]
    assert eng.queues["VT"] == [3]
    eng.get("VT", 3)
    assert eng.queues["VT"] == []
    assert eng.queues["VV"] == [1, 2]  # untouched (per-relation queues)


def test_boundary_relations_direct(setup):
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=0)
    # FE: each face's 3 edges exist and connect its vertices
    fe = eng.boundary_FE(np.arange(20))
    assert (fe >= 0).all()
    for f in range(20):
        verts = set(pre.F[f])
        for e in fe[f]:
            assert set(pre.E[e]) <= verts
    te = eng.boundary_TE(np.arange(10))
    tf = eng.boundary_TF(np.arange(10))
    assert (te >= 0).all() and (tf >= 0).all()
    launches = eng.stats.kernel_launches
    assert launches == 0  # boundary relations never touch the producer


def test_baselines_agree(setup):
    sm, pre = setup
    ex = ExplicitTriangulation(pre, ["VT"])
    for ds in (TopoClusterDS(pre, ["VT"]), ActopoDS(pre, ["VT"])):
        for k in (0, sm.n_segments // 2, sm.n_segments - 1):
            M, L = ds.get("VT", k)
            Me, Le = ex.get("VT", k)
            assert (L == Le).all()
            for r in range(len(L)):
                assert set(M[r][: L[r]]) == set(Me[r][: Le[r]])


def test_waiting_stats_populated(setup):
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=2)
    for s in range(min(8, sm.n_segments)):
        eng.get("VV", s)
    st = eng.stats
    assert st.requests >= 8
    assert st.t_dispatch > 0 and st.t_integrate >= 0
    assert st.segments_produced >= st.cache_misses


def test_no_relation_overflow(setup):
    """Default relation-array widths hold the densest rows (paper's
    preallocated M arrays must never overflow)."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV", "VT", "VE", "VF", "EF", "ET", "FT"])
    for R in ("VV", "VT", "VE", "VF", "EF", "ET", "FT"):
        for k in range(0, sm.n_segments, 7):
            M, L = eng.get(R, k)
            assert L.max(initial=0) <= M.shape[1], (R, k)


def test_relation_overflow_raises(setup):
    """Regression: a row wider than the preallocated deg[relation] used to
    be silently truncated by the top_k compaction into a wrong neighbor
    list; the engine must raise, naming the deg= override."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], deg={"VV": 2})
    with pytest.raises(RelationWidthError, match=r"deg\['VV'\]=2"):
        eng.get("VV", 0)
    # the error names the override that fixes it
    eng_wide = RelationEngine(pre, ["VV"], deg={"VV": 64})
    M, L = eng_wide.get("VV", 0)
    assert L.max() <= M.shape[1]


def test_lookahead_skips_queued_segments(setup):
    """Regression: lookahead must de-dup against the pending queue — a
    queued segment stays queued (one eventual dispatch) instead of also
    entering a launch as lookahead and leaving a stale queue entry."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=8, batch_max=32,
                         cache_segments=4096)
    eng.request("VV", [5])
    assert 5 not in eng._lookahead_segments("VV", [3])
    assert 6 in eng._lookahead_segments("VV", [3])  # others still looked at
    # end-to-end: mixed request/prefetch/get traffic never produces a
    # (relation, segment) block twice (big cache -> produced == distinct)
    eng.prefetch("VV", [0])
    eng.get("VV", 2)
    for s in range(sm.n_segments):
        eng.get("VV", s)
    assert eng.stats.segments_produced == len(eng.cache)


def test_async_bit_identical_to_blocking_and_explicit(setup):
    """Regression: async get() (in-flight futures, prefetch-driven) returns
    bit-identical (M, L) blocks to the blocking path and to the explicit
    oracle — scheduling must never change answers."""
    sm, pre = setup
    rels = ["VV", "VT", "EF"]
    a = RelationEngine(pre, rels, lookahead=3, batch_max=4,
                       async_dispatch=True)
    b = RelationEngine(pre, rels, lookahead=3, batch_max=4,
                       async_dispatch=False)
    ex = ExplicitTriangulation(pre, rels)
    # drive the async engine the way the algorithms do: prefetch ahead,
    # then read — most reads land on in-flight futures
    for R in rels:
        a.prefetch(R, range(min(4, sm.n_segments)))
    for R in rels:
        for s in range(sm.n_segments):
            a.prefetch(R, [min(s + 1, sm.n_segments - 1)])
            Ma, La = a.get(R, s)
            Mb, Lb = b.get(R, s)
            Me, Le = ex.get(R, s)
            np.testing.assert_array_equal(Ma, Mb)
            np.testing.assert_array_equal(La, Lb)
            np.testing.assert_array_equal(La, Le)
            for r in range(len(La)):
                assert set(Ma[r][: La[r]]) == set(Me[r][: Le[r]]), (R, s, r)
    # prefetching actually produced ahead (hits from cache or in-flight)
    assert a.stats.cache_hits > 0


def test_inflight_futures_table(setup):
    """White-box: a dispatched launch registers (relation, segment) futures
    in the in-flight table; a consumer read syncs exactly that launch,
    retires it into the cache, and counts as an in-flight hit."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=0, batch_max=4,
                         async_dispatch=True)
    eng.request("VV", [0, 1, 2])
    launch = eng._dispatch("VV")
    assert launch is not None and not launch.done
    for s in (0, 1, 2):
        assert ("VV", s) in eng._inflight
    eng.get("VV", 0)                       # blocks only on this read
    assert eng.stats.inflight_hits == 1
    assert launch.done
    for s in (0, 1, 2):                    # whole launch retired at once
        assert ("VV", s) not in eng._inflight
        assert ("VV", s) in eng.cache
    # a segment is never produced twice: re-requesting is a no-op
    eng.request("VV", [1])
    assert eng.queues["VV"] == []
    assert eng.stats.kernel_launches == 1


def test_get_batch_counts_each_segment_once(setup):
    """Regression: get_batch must not double-count requests/hits/misses
    (it used to bump them once itself and once more per get())."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=0, batch_max=64)
    segs = list(range(6))
    eng.get_batch("VV", segs)
    assert eng.stats.requests == 6
    assert eng.stats.cache_misses == 6
    assert eng.stats.cache_hits == 0
    eng.get_batch("VV", segs)
    assert eng.stats.requests == 12
    assert eng.stats.cache_misses == 6
    assert eng.stats.cache_hits == 6
    assert (eng.stats.cache_hits + eng.stats.cache_misses
            == eng.stats.requests)


def test_lookahead_capped_at_batch_max(setup):
    """Regression: lookahead must not grow a launch past batch_max (the cap
    used to be a no-op); overflow rolls into later launches instead."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=8, batch_max=4)
    eng.get("VV", 0)
    assert eng.stats.kernel_launches == 1
    assert eng.stats.segments_produced <= 4
    # overflow lookahead segments were requeued, not dropped
    assert eng.queues["VV"], "lookahead overflow should be requeued"
    assert all(s <= 8 for s in eng.queues["VV"])


def test_sync_wait_and_dispatch_accounted_separately(setup):
    """t_dispatch is host-side dispatch only; t_sync is the consumer wait
    (Fig. 10 'waiting'). Both must be populated on the async path."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=2, async_dispatch=True)
    eng.prefetch("VV", range(min(8, sm.n_segments)))
    for s in range(min(8, sm.n_segments)):
        eng.get("VV", s)
    assert eng.stats.t_dispatch > 0
    assert eng.stats.kernel_launches >= 1
    # the blocking arm waits on every launch and must record it as t_sync
    blk = RelationEngine(pre, ["VV"], lookahead=2, async_dispatch=False)
    for s in range(min(8, sm.n_segments)):
        blk.get("VV", s)
    assert blk.stats.t_sync > 0


def test_read_survives_eviction_by_own_launch(setup):
    """Regression: a segment deep in a prefetched launch can be LRU-evicted
    by that launch's own integration when the cache is smaller than the
    launch; reading it must re-dispatch, not crash."""
    sm, pre = setup
    eng = RelationEngine(pre, ["VV"], lookahead=0, batch_max=16,
                         cache_segments=4, async_dispatch=True)
    n = min(16, sm.n_segments)
    eng.prefetch("VV", range(n))
    s = n - 2
    M, L = eng.get("VV", s)
    ex = ExplicitTriangulation(pre, ["VV"])
    Me, Le = ex.get("VV", s)
    assert (L == Le).all()


def test_toy_matches_paper_figure(setup):
    """Fig. 1: VV(v0) on the toy mesh (labels modulo canonicalization)."""
    mesh = two_tets()
    sm = segment_mesh(mesh, capacity=6)
    pre = precondition(sm, relations=["VV"])
    eng = RelationEngine(pre, ["VV"])
    M, L = eng.get("VV", 0)
    # the vertex with scalar 2.0 (paper's v0) neighbours scalars {4,5,1,0}
    v0 = int(np.argmin(np.abs(sm.scalars - 2.0)))
    nbrs = {round(float(sm.scalars[u]), 1) for u in M[v0][: L[v0]]}
    assert nbrs == {4.0, 5.0, 1.0, 0.0}
