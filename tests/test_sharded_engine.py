"""Sharded engine (docs/DESIGN.md §9): ShardPlan geometry, shard-affine
scheduling, per-shard production/stats, the cross-shard completion
exchange, and bit-identity of all three drivers across shard counts.

These tests run on any platform: with one device the shard exchange takes
the stack+sum fallback (identical integers to the psum path), and the CI
``sharded-smoke`` job re-runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so every shard owns
a distinct device."""

import re

import numpy as np
import pytest

from repro.algorithms import fields
from repro.algorithms.critical_points import critical_points, total_order
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale
from repro.core.adjacency import complete_adjacency, plan_completion
from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.scheduler import partition, segment_batches
from repro.core.segtables import precondition
from repro.data.meshgen import load_dataset
from repro.distributed.sharding import ShardPlan

RELS = ["VV", "VE", "VF", "VT", "FT", "TT"]


class TestShardPlan:
    def test_even_contiguous_bounds(self):
        p = ShardPlan.make(10, shards=4)
        assert p.bounds == (0, 3, 6, 8, 10)
        assert p.n_shards == 4
        assert [p.shard_bounds(k) for k in range(4)] == [
            (0, 3), (3, 6), (6, 8), (8, 10)]
        assert list(p.segments(1)) == [3, 4, 5]

    def test_shard_of_matches_bounds(self):
        p = ShardPlan.make(10, shards=3)
        got = [p.shard_of(s) for s in range(10)]
        assert got == list(p.shard_of_array(np.arange(10)))
        for k in range(p.n_shards):
            lo, hi = p.shard_bounds(k)
            assert got[lo:hi] == [k] * (hi - lo)

    def test_shard_count_clamped_to_segments(self):
        p = ShardPlan.make(3, shards=8)
        assert p.n_shards == 3
        assert p.bounds == (0, 1, 2, 3)

    def test_unsharded_plan_stays_off_the_device_api(self):
        p = ShardPlan.make(5, shards=1)
        assert p.devices == (None,)
        assert not p.multi_device

    def test_multi_device_requires_distinct_devices(self):
        import jax
        devs = jax.devices()
        p = ShardPlan.make(8, shards=4)
        # distinct devices per shard <-> collective exchange path
        assert p.multi_device == (len({d.id for d in p.devices}) == 4)
        same = ShardPlan.make(8, shards=4, devices=(devs[0],) * 4)
        assert not same.multi_device


class TestShardAffineScheduling:
    def _check(self, shares, n):
        flat = sorted(i for sh in shares for i in sh)
        assert flat == list(range(n))                 # disjoint cover
        for sh in shares:
            assert sh == sorted(sh)                   # ascending

    def test_fewer_workers_than_shards(self):
        plan = ShardPlan.make(16, shards=4)
        shard_of = lambda i: plan.shard_of(i)         # noqa: E731
        shares = partition(16, 2, shard_of)
        self._check(shares, 16)
        # worker 0 owns shards 0 and 2, worker 1 owns shards 1 and 3
        assert {shard_of(i) for i in shares[0]} == {0, 2}
        assert {shard_of(i) for i in shares[1]} == {1, 3}

    def test_more_workers_than_shards_stay_shard_pure(self):
        plan = ShardPlan.make(12, shards=2)
        shard_of = lambda i: plan.shard_of(i)         # noqa: E731
        shares = partition(12, 5, shard_of)
        self._check(shares, 12)
        for sh in shares:                             # each worker: 1 shard
            assert len({shard_of(i) for i in sh}) == 1

    def test_no_shard_of_preserves_strided_partition(self):
        assert partition(7, 3) == [[0, 3, 6], [1, 4], [2, 5]]

    def test_segment_batches_restart_at_shard_boundaries(self):
        plan = ShardPlan.make(10, shards=3)           # bounds 0,4,7,10
        got = segment_batches(10, 3, plan)
        assert got == [[0, 1, 2], [3], [4, 5, 6], [7, 8, 9]]
        for b in got:
            assert len({plan.shard_of(s) for s in b}) == 1
        # unsharded: the plain contiguous chop
        assert segment_batches(10, 3, None) == [
            [0, 1, 2], [3, 4, 5], [6, 7, 8], [9]]


@pytest.fixture(scope="module")
def bar():
    mesh = load_dataset("bar", scalar_fn=fields.gaussians(2, k=5, sigma=5.0))
    sm = segment_mesh(mesh, capacity=64)
    pre = precondition(sm, relations=RELS + ["FF"])
    rank = total_order(sm.scalars)
    return sm, pre, rank


def _run_drivers(eng, pre, rank, workers):
    _, cp = critical_points(eng, pre, rank, batch_segments=4, workers=workers)
    g = discrete_gradient(eng, pre, rank, batch_segments=4,
                          co_prefetch=("TT",), workers=workers)
    ms = morse_smale(eng, pre, g, batch_segments=4, workers=workers)
    return (cp, g.counts(), ms.counts(),
            g.pair_v2e.tobytes(), g.pair_e2f.tobytes(),
            g.pair_f2t.tobytes(), ms.dest_min.tobytes(),
            ms.dest_max.tobytes(), ms.saddle1_ends.tobytes(),
            ms.saddle2_ends.tobytes())


@pytest.fixture(scope="module")
def bar_baseline(bar):
    sm, pre, rank = bar
    eng = RelationEngine(pre, RELS, lookahead=8, dev_pool_segments=4096)
    return _run_drivers(eng, pre, rank, workers=1)


class TestDriverBitIdentityAcrossShards:
    @pytest.mark.parametrize("shards,workers", [(4, 1), (4, 4), (2, 1)])
    def test_drivers_match_unsharded_baseline(self, bar, bar_baseline,
                                              shards, workers):
        sm, pre, rank = bar
        eng = RelationEngine(pre, RELS, lookahead=8, dev_pool_segments=4096,
                             shards=shards)
        assert eng.shard_plan.n_shards == shards
        got = _run_drivers(eng, pre, rank, workers=workers)
        assert got == bar_baseline

        # every shard counter partitions the global one exactly: each
        # launch (hence each produced segment) belongs to exactly one shard
        st, m = eng.stats, eng.merged_shard_stats()
        assert m.segments_produced == st.segments_produced
        assert m.kernel_launches == st.kernel_launches
        assert m.devpool_uploads == st.devpool_uploads
        assert m.devpool_hits == st.devpool_hits
        assert set(eng.shard_stats) <= set(range(shards))


class TestPerShardProduction:
    def test_full_sweep_produces_each_shard_exactly_once(self, bar):
        """One relation swept start to finish: shard k produces exactly its
        own segments, no segment is produced on more than one shard."""
        sm, pre, rank = bar
        eng = RelationEngine(pre, ["VV"], lookahead=4, shards=4)
        plan = eng.shard_plan
        for s in range(sm.n_segments):
            eng.get("VV", s)
        sizes = {k: plan.bounds[k + 1] - plan.bounds[k]
                 for k in range(plan.n_shards)}
        produced = {k: st.segments_produced
                    for k, st in eng.shard_stats.items()}
        assert produced == sizes
        assert sum(produced.values()) == sm.n_segments
        assert eng.stats.segments_produced == sm.n_segments


class TestShardedCompletion:
    def test_cross_shard_pairs_resolve_into_neighbour_shards(self, bar):
        """The bar's shard boundaries are planar face walls: the completion
        fan-out must consult segments of the adjacent shard (k +- 1)."""
        sm, pre, rank = bar
        eng = RelationEngine(pre, RELS, shards=4)
        splan = eng.shard_plan
        ids = np.arange(sm.n_tets, dtype=np.int64)
        plan = plan_completion(eng, "TT", ids, prefetch=False)
        q_shard = splan.shard_of_array(
            pre.owner_segment("T", plan.ids[plan.pair_query]))
        p_shard = splan.shard_of_array(plan.pair_seg)
        delta = p_shard - q_shard
        assert (delta != 0).any()                     # cross-shard traffic
        assert (delta == 1).any()                     # ... into shard k+1
        # contiguous Morton shards keep the exchange local: every cross
        # pair lands within two shards, at least half on the next shard
        cross = np.abs(delta[delta != 0])
        assert cross.max() <= 2 and (cross == 1).mean() >= 0.5
        # at least one adjacent shard pair exchanges rows in both roles
        # (owner-serving and querying) across the same boundary wall
        assert any(((q_shard == k) & (p_shard == k + 1)).any()
                   for k in range(splan.n_shards - 1))

    @pytest.mark.parametrize("relation", ["TT", "FF"])
    def test_sharded_exchange_bit_identical_to_single_pool(self, bar,
                                                           relation):
        sm, pre, rank = bar
        nq = sm.n_tets if relation == "TT" else pre.n_faces
        rels = RELS + ([relation] if relation not in RELS else [])
        ids = np.arange(0, nq, 2, dtype=np.int64)
        ref_eng = RelationEngine(pre, rels)
        M0, L0 = complete_adjacency(ref_eng, relation, ids, path="device")
        for shards in (2, 4):
            eng = RelationEngine(pre, rels, shards=shards)
            M, L = complete_adjacency(eng, relation, ids, path="device")
            np.testing.assert_array_equal(M, M0)
            np.testing.assert_array_equal(L, L0)
            Mh, Lh = complete_adjacency(eng, relation, ids, path="host")
            np.testing.assert_array_equal(Mh, M0)
            np.testing.assert_array_equal(Lh, L0)

    def test_explicit_shards_argument_validates(self, bar):
        sm, pre, rank = bar
        eng = RelationEngine(pre, RELS, shards=2)
        ids = np.arange(8, dtype=np.int64)
        M, L = complete_adjacency(eng, "TT", ids, shards=2)
        assert M.shape[0] == 8
        with pytest.raises(ValueError, match="shards"):
            complete_adjacency(eng, "TT", ids, shards=4)


class TestDriverShardsValidation:
    def test_driver_shards_mismatch_raises(self, bar):
        sm, pre, rank = bar
        eng = RelationEngine(pre, RELS, shards=2)
        with pytest.raises(ValueError, match="shards=4"):
            critical_points(eng, pre, rank, shards=4)
        with pytest.raises(ValueError, match="shards=3"):
            discrete_gradient(eng, pre, rank, shards=3)

    def test_engine_rejects_foreign_plan(self, bar):
        sm, pre, rank = bar
        wrong = ShardPlan.make(sm.n_segments + 5, shards=2)
        with pytest.raises(ValueError, match="segments"):
            RelationEngine(pre, RELS, shard_plan=wrong)


class TestCompileOnDevices:
    def test_the_call_reuses_the_devices_executables(self):
        """What ``compile_on_devices`` compiles is what the later call runs:
        the call pays no compile of its own."""
        import jax
        import jax.numpy as jnp
        from repro.distributed.sharding import compile_on_devices

        f = jax.jit(lambda x, k: jnp.sort(x * k), static_argnums=1)
        # committed to its device, as the sharded engine's tables are
        x = jax.device_put(jnp.arange(24, dtype=jnp.int32), jax.devices()[0])
        compile_on_devices(f, (x, 3), jax.devices())
        seen = []

        def listener(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                seen.append(duration)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            np.testing.assert_array_equal(np.asarray(f(x, 3)),
                                          np.arange(24) * 3)
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
        assert seen == []


def test_four_devices_one_pool_each_and_one_compile_per_kernel_shape():
    """On four host devices the sharded engine keeps each shard's blocks on
    its own device, exchanges by psum and matches shards=1 bit for bit;
    each relation kernel shape compiles exactly once on each device."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import collections, logging, re, sys
        sys.path.insert(0, %r)
        import jax
        jax.config.update("jax_log_compiles", True)
        names = collections.Counter()
        class Count(logging.Handler):
            def emit(self, rec):
                m = re.search(r"Finished XLA compilation of (\\S+)",
                              rec.getMessage())
                if m:
                    names[m.group(1)] += 1
        logging.getLogger("jax").addHandler(Count())
        import chip_smoke
        from repro.core.engine import RelationEngine
        chip_smoke.check_sharded((16, 6, 6), seed=1, shards=4, log=print)
        jax.clear_caches()
        names.clear()
        sm, pre, rank, _ = chip_smoke.build((16, 6, 6), 1)
        eng = RelationEngine(pre, list(chip_smoke.RELS), shards=4)
        chip_smoke.run_drivers(eng, pre, rank, "device", "count", print)
        print("kernel shapes", len(eng._compiled_on_all),
              "compiles", names["jit(_relation_block_fused)"])
    """ % repo)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bit-identical to shards=1" in out.stdout
    assert re.search(r"exchange psum=[1-9]\d* stack=0", out.stdout)
    shapes, compiles = map(int, re.search(
        r"kernel shapes (\d+) compiles (\d+)", out.stdout).groups())
    assert shapes > 0 and compiles == 4 * shapes, out.stdout
