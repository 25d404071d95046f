"""Pallas sparse-producer parity (ISSUE 10): the entry-assembly kernels in
``kernels/segment_relations.py`` must be bit-identical to the fused xla
oracle for every relation, through the REAL engine dispatch — including
the EE/FF dense fallback arm and the ``RelationWidthError`` overflow path
— plus the autotune round trip (``launch/autotune.py``)."""

import jax
import numpy as np
import pytest

from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.segtables import OFFLOADED_RELATIONS, precondition
from repro.data.meshgen import structured_grid, two_tets
from repro.errors import RelationWidthError
from repro.kernels import ops
from repro.launch import autotune


@pytest.fixture(scope="module")
def pre():
    mesh = structured_grid(3, 3, 3)
    sm = segment_mesh(mesh, capacity=16)
    return precondition(sm, relations=list(OFFLOADED_RELATIONS))


def _engines(pre, **kw):
    ref = RelationEngine(pre, OFFLOADED_RELATIONS, backend="xla",
                         lookahead=0, tune="off", **kw)
    pal = RelationEngine(pre, OFFLOADED_RELATIONS,
                         backend="pallas_interpret", lookahead=0,
                         tune="off", **kw)
    return ref, pal


# -- per-relation bit identity, all ten relations ---------------------------

@pytest.mark.parametrize("relation", OFFLOADED_RELATIONS)
def test_engine_blocks_bit_identical(pre, relation):
    ref, pal = _engines(pre, batch_max=2)
    segs = list(range(min(2, pre.smesh.n_segments)))
    for (mr, lr), (mp, lp) in zip(ref.get_batch(relation, segs),
                                  pal.get_batch(relation, segs)):
        np.testing.assert_array_equal(mr, mp)
        np.testing.assert_array_equal(lr, lp)


def test_ee_ff_take_the_dense_fallback(pre):
    # EE/FF have no sparse specialization: both backends must agree while
    # routing through the pairwise counts arm
    t = pre.tables
    for relation in ("EE", "FF"):
        tab, _ = t.table(relation[0])
        assert not ops.sparse_arm_ok(relation, tab, tab, t.NV)


def test_relation_width_error_on_both_backends():
    mesh = two_tets()
    sm = segment_mesh(mesh, capacity=4)
    p = precondition(sm, relations=["VT"])
    for backend in ("xla", "pallas_interpret"):
        eng = RelationEngine(p, ["VT"], backend=backend, lookahead=0,
                             tune="off", deg={"VT": 1})
        with pytest.raises(RelationWidthError):
            eng.get("VT", 0)


# -- raw kernel parity on adversarial (prime) table sizes -------------------

def _rand_tables(rng, B, N, arity, nvl, fill=0.7):
    tab = np.full((B, N, arity), -1, dtype=np.int32)
    for b in range(B):
        for i in range(max(1, int(N * fill))):
            tab[b, i] = rng.choice(nvl, size=arity, replace=False)
    return tab


@pytest.mark.parametrize("n", [1, 7, 127])
def test_prime_sized_tables_entry_parity(n):
    rng = np.random.default_rng(n)
    nvl = max(8, n)
    tx = _rand_tables(rng, 2, n, 2, nvl)
    colg = np.where(tx[:, :, 0] >= 0,
                    np.arange(n, dtype=np.int32)[None, :], -1)
    for assembly in ("sparse", "dense"):
        want = ops.relation_block("VE", tx, tx, colg, nvl, deg=8,
                                  backend="xla", assembly=assembly)
        got = ops.relation_block("VE", tx, tx, colg, nvl, deg=8,
                                 backend="pallas_interpret",
                                 assembly=assembly)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("n", [1, 7, 127])
def test_prime_sized_tables_counts_parity(n):
    # the counts kernels pad the simplex axes to 128 multiples internally;
    # the tail blocks past n must not contribute (explicit -1 masking)
    rng = np.random.default_rng(100 + n)
    nvl = 128
    tt = _rand_tables(rng, 2, n, 4, nvl)
    np.testing.assert_array_equal(
        np.asarray(ops.counts_vv(tt, nvl, backend="pallas_interpret")),
        np.asarray(ops.counts_vv(tt, nvl, backend="xla")))
    np.testing.assert_array_equal(
        np.asarray(ops.counts_meet(tt, tt, nvl,
                                   backend="pallas_interpret")),
        np.asarray(ops.counts_meet(tt, tt, nvl, backend="xla")))


# -- autotune round trip ----------------------------------------------------

def test_autotune_roundtrip(pre, tmp_path):
    cfg = autotune.KernelConfig(block_x=128, block_y=512, vv_block=128,
                                batch_max=8, bucket_floor=2)
    path = str(tmp_path / "tune.json")
    ns = pre.smesh.n_segments
    kind = jax.devices()[0].device_kind
    autotune.record("xla", ns, cfg, kind, path=path, score_s=1.0)
    assert autotune.lookup("xla", ns, kind, path=path) == cfg
    # other backends / other device kinds / other mesh buckets miss
    assert autotune.lookup("pallas", ns, kind, path=path) is None
    assert autotune.lookup("xla", ns, "TPU v5 lite", path=path) is None

    eng = RelationEngine(pre, ["VV"], backend="xla", lookahead=0, tune=path)
    assert (eng.batch_max, eng.block_x, eng.block_y, eng.vv_block,
            eng.bucket_floor) == (8, 128, 512, 128, 2)
    # explicit arguments win over the tuned table
    eng2 = RelationEngine(pre, ["VV"], backend="xla", lookahead=0,
                          tune=path, block_x=64)
    assert (eng2.block_x, eng2.batch_max) == (64, 8)

    # tuned engine produces the identical blocks as today's defaults
    base = RelationEngine(pre, ["VV"], backend="xla", lookahead=0,
                          tune="off")
    for s in range(min(3, ns)):
        for a, b in zip(base.get("VV", s), eng.get("VV", s)):
            np.testing.assert_array_equal(a, b)


def test_tune_off_matches_built_in_defaults(pre):
    eng = RelationEngine(pre, ["VV"], backend="xla", tune="off")
    assert (eng.batch_max, eng.block_x, eng.block_y, eng.vv_block,
            eng.bucket_floor, eng.assembly) == (64, 256, 256, None, 1,
                                                "sparse")


def test_corrupt_table_falls_back_to_defaults(pre, tmp_path):
    """A table that cannot be read raises (it never silently configures
    the defaults); a missing or stale-version table is no table."""
    bad = tmp_path / "bad.json"
    bad.write_text("{not json", encoding="utf-8")
    with pytest.raises(autotune.TuneTableError):
        RelationEngine(pre, ["VV"], backend="xla", tune=str(bad))
    kind = jax.devices()[0].device_kind
    key = autotune.table_key(kind, "xla", pre.smesh.n_segments)
    bad.write_text('{"version": %d, "configs": {"%s": [1]}}'
                   % (autotune.TABLE_VERSION, key), encoding="utf-8")
    with pytest.raises(autotune.TuneTableError):
        RelationEngine(pre, ["VV"], backend="xla", tune=str(bad))
    eng = RelationEngine(pre, ["VV"], backend="xla",
                         tune=str(tmp_path / "missing.json"))
    assert (eng.batch_max, eng.block_x, eng.block_y) == (64, 256, 256)
    stale = tmp_path / "stale.json"
    stale.write_text('{"version": -1, "configs": {}}', encoding="utf-8")
    assert autotune.load_table(str(stale)) == {}


def test_peaks_are_keyed_by_device_kind():
    """Candidate ranking reads published peaks for the device kind, and a
    device without a row is an error, not a default."""
    from repro.launch import roofline
    assert roofline.peaks("TPU v5 lite").hbm_bytes_s == 819e9
    assert autotune.candidate_configs(64, "TPU v5 lite", max_candidates=2)
    with pytest.raises(roofline.UnknownDeviceError):
        autotune.candidate_configs(64, "cpu")


def test_version_mismatch_invalidates(tmp_path):
    path = str(tmp_path / "t.json")
    autotune.record("xla", 64, autotune.KernelConfig(), "cpu", path=path)
    import json
    with open(path) as f:
        data = json.load(f)
    data["version"] = autotune.TABLE_VERSION + 1
    with open(path, "w") as f:
        json.dump(data, f)
    assert autotune.lookup("xla", 64, "cpu", path=path) is None


# -- the public cache surface (the satellite the benchmarks now use) --------

def test_clear_cache_and_nbytes(pre):
    eng = RelationEngine(pre, ["VV"], backend="xla", lookahead=0,
                         tune="off")
    assert eng.cache_nbytes() == 0
    M0, L0 = eng.get("VV", 0)
    assert eng.cache_nbytes() > 0
    assert eng.clear_cache() > 0
    assert eng.cache_nbytes() == 0
    M1, L1 = eng.get("VV", 0)
    np.testing.assert_array_equal(M0, M1)
    np.testing.assert_array_equal(L0, L1)
