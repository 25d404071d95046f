"""The device completion arm's chunk rule (``core/adjacency.device_chunk``):
the chunk follows the relation's candidate width, the chunked arms stay
bit-identical to the host arm and the scalar reference, and every chunk is
one plan (``EngineStats.completion_chunks``)."""

import math

import numpy as np
import pytest

from repro.core.adjacency import (CHUNK_ENTRIES, complete_adjacency,
                                  complete_adjacency_scalar, device_chunk)
from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid
from repro.kernels import ops


@pytest.mark.parametrize("relation,deg,want", [
    ("TT", ops.DEFAULT_DEG["TT"], 8192),      # 4 faces x 8
    ("EE", ops.DEFAULT_DEG["EE"], 2048),      # 2 vertices x 64
    ("FF", ops.DEFAULT_DEG["FF"], 1024),      # 3 edges x 48
    ("TT", CHUNK_ENTRIES, 1),                 # wider than the whole budget
])
def test_chunk_follows_the_candidate_width(relation, deg, want):
    assert device_chunk(relation, deg) == want


@pytest.fixture(scope="module")
def tt_mesh():
    """16,464 tets: three TT chunks under the rule (2 x 8,192 + 80)."""
    sm = segment_mesh(structured_grid(15, 15, 15, jitter=0.2, seed=3),
                      capacity=64)
    return sm, precondition(sm, relations=["TT", "FT"])


@pytest.fixture(scope="module")
def ff_mesh():
    """2,352 faces: three FF chunks under the rule (2 x 1,024 + 304)."""
    sm = segment_mesh(structured_grid(7, 7, 6, jitter=0.2, seed=3),
                      capacity=16)
    return sm, precondition(sm, relations=["EE", "FF", "TT", "EF", "FT"])


@pytest.mark.parametrize("relation,fixture", [("TT", "tt_mesh"),
                                              ("FF", "ff_mesh")])
def test_rule_chunks_are_bit_identical(relation, fixture, request):
    sm, pre = request.getfixturevalue(fixture)
    eng = RelationEngine(pre, [relation], cache_segments=4096)
    n = sm.n_tets if relation == "TT" else pre.n_faces
    ids = np.arange(n)
    chunk = device_chunk(relation, eng.deg[relation])
    assert n > chunk                       # the rule cuts this list
    Md, Ld = complete_adjacency(eng, relation, ids, path="device")
    assert eng.stats.completion_chunks == math.ceil(n / chunk)
    Mv, Lv = complete_adjacency(eng, relation, ids, path="device", out="dev")
    Mh, Lh = complete_adjacency(eng, relation, ids, path="host")
    Ms, Ls = complete_adjacency_scalar(eng, relation, ids)
    assert np.array_equal(Md, Mh) and np.array_equal(Ld, Lh)
    assert np.array_equal(Ms, Mh) and np.array_equal(Ls, Lh)
    Mv = np.asarray(Mv)
    w = Mh.shape[1]
    assert np.array_equal(Mv[:, :w], Mh) and (Mv[:, w:] == -1).all()
    assert np.array_equal(np.asarray(Lv), Lh)


def test_sharded_arm_takes_the_rule(tt_mesh):
    """One path: the cross-shard exchange chunks by the same rule and
    stays bit-identical to the single-pool arm."""
    sm, pre = tt_mesh
    ids = np.arange(sm.n_tets)
    one = RelationEngine(pre, ["TT"], cache_segments=4096)
    two = RelationEngine(pre, ["TT"], cache_segments=4096, shards=2)
    M1, L1 = complete_adjacency(one, "TT", ids, path="device")
    M2, L2 = complete_adjacency(two, "TT", ids, path="device")
    assert np.array_equal(M1, M2) and np.array_equal(L1, L2)
    want = math.ceil(sm.n_tets / device_chunk("TT", two.deg["TT"]))
    assert two.stats.completion_chunks == want == 3


@pytest.mark.parametrize("batch", [1000, 20000, 0])
def test_explicit_batch_is_honoured(tt_mesh, batch):
    """``batch=`` overrides the rule on the device arm (0 or a batch longer
    than the list: one chunk) and is the host arm's only chunking."""
    sm, pre = tt_mesh
    ids = np.arange(sm.n_tets)
    eng = RelationEngine(pre, ["TT"], cache_segments=4096)
    want = math.ceil(sm.n_tets / batch) if 0 < batch < sm.n_tets else 1
    Md, Ld = complete_adjacency(eng, "TT", ids, batch=batch, path="device")
    assert eng.stats.completion_chunks == want
    Mh, Lh = complete_adjacency(eng, "TT", ids, batch=batch, path="host")
    assert eng.stats.completion_chunks == 2 * want
    assert np.array_equal(Md, Mh) and np.array_equal(Ld, Lh)
    complete_adjacency(eng, "TT", ids, path="host")
    assert eng.stats.completion_chunks == 2 * want + 1


def test_device_completion_releases_its_blocks(ff_mesh):
    """The device arm frees the relation's pooled device blocks when the
    call ends (the host cache keeps them): a later device read uploads the
    same block, and other relations' pooled blocks stay."""
    sm, pre = ff_mesh
    eng = RelationEngine(pre, ["FF", "TT"], cache_segments=4096)
    eng.get_full_dev("TT", 0)                  # TT's launch: pooled blocks
    tt_blocks = len(eng.store)
    Md, _ = complete_adjacency(eng, "FF", np.arange(pre.n_faces),
                               path="device")
    assert eng.stats.devpool_hits > 1          # read from the pool meanwhile
    assert len(eng.store) == tt_blocks         # FF's blocks released
    uploads = eng.stats.devpool_uploads
    M, L = eng.get_full_dev("FF", 0)
    assert eng.stats.devpool_uploads == uploads + 1
    Mh, Lh = eng.get_full("FF", 0)
    assert np.array_equal(np.asarray(M), Mh)
    assert np.array_equal(np.asarray(L), Lh)
    Mh2, _ = complete_adjacency(eng, "FF", np.arange(pre.n_faces),
                                path="device")
    assert np.array_equal(Md, Mh2)


def test_chunks_release_what_later_chunks_skip(tt_mesh):
    """``release_dev(relation, segments)`` drops those segments' blocks
    alone; a completion in many chunks releases each block after the last
    chunk that consults it, so only blocks released before it upload."""
    sm, pre = tt_mesh
    eng = RelationEngine(pre, ["TT"], cache_segments=4096,
                         dev_pool_segments=4096)
    eng.prefetch("TT", [0, 1, 2])
    eng.get_full_dev("TT", 0)
    pooled = len(eng.store)
    assert eng.release_dev("TT", [0, 1]) == 2
    assert len(eng.store) == pooled - 2 and ("TT", 2) in eng.store
    ids = np.arange(sm.n_tets)
    M, L = complete_adjacency(eng, "TT", ids, batch=1000, path="device")
    assert eng.stats.devpool_uploads == 2     # the two released by hand
    assert len(eng.store) == 0
    Mh, Lh = complete_adjacency(eng, "TT", ids, path="host")
    assert np.array_equal(M, Mh) and np.array_equal(L, Lh)
