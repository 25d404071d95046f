"""Unit tests of the on-chip benchmark's harness, run on the CPU: discovery
of cells by name, the rate and roofline arithmetic, the peaks table, the
trace reduction and the refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

from chipbench import drive, harness, meshgen, reference, roofline, xplane  # noqa: E402


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _later():
    """Cells of the traffic mixes kept for a later PR (PERF.md)."""
    with open(os.path.join(DATA, "later_cells.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]
                                      + _later()])
def test_every_cell_resolves_by_name(workload, tmp_path):
    root = ROOT
    later = {w["name"]: w for w in _later()}
    if workload in later:
        spec = _spec()
        spec["workloads"].append(later[workload])
        root = str(tmp_path)
        os.makedirs(os.path.join(root, "benchmarks"))
        os.symlink(BENCH, os.path.join(root, "benchmarks", "chip"))
        with open(os.path.join(root, "BENCHMARK.json"), "w",
                  encoding="utf-8") as f:
            json.dump(spec, f)
    cell = harness.load_cell(root, workload)
    assert cell.chips == 1
    for step in cell.traffic["steps"]:
        assert step["driver"] in drive.DRIVERS
    for m in cell.per_layer:
        assert callable(harness.load_reader(cell.bench_dir, m["name"]))
    names = {m["name"] for m in cell.end_to_end}
    assert names == {"tets_per_s", "peak_hbm_gb", "setup_s"}


def test_metric_files_match_the_spec():
    spec = _spec()
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in spec["per_layer"]}
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_completion_metrics_only_where_completion_runs():
    cells = {w["name"]: harness.load_cell(ROOT, w["name"])
             for w in _spec()["workloads"]}
    for name, cell in cells.items():
        listed = {m["name"] for m in cell.per_layer}
        completes = any(s["driver"] == "morse_smale"
                        for s in cell.traffic["steps"])
        assert ("completion.queries" in listed) == completes, name


def test_rate_counts_whole_passes_over_the_window():
    assert harness.rate(1_429_968, 3, 60.0) == pytest.approx(71_498.4)
    assert harness.rate(100, 1, 0.5) == 200.0


def test_relation_block_bytes_by_hand():
    rows = {"V": 128, "E": 640, "F": 1024, "T": 512}
    # VV: T_local (512 x 4) + LV_global 128 read; M (128 x 32) + L written
    assert roofline.block_bytes("VV", rows, 32) == 4 * (512 * 4 + 128
                                                        + 128 * 33)
    # VT: LV_global 128 (vertex table) + T_local 512x4 + LT_global 512
    assert roofline.block_bytes("VT", rows, 64) == 4 * (128 + 512 * 5
                                                        + 128 * 65)
    # VF: LV_global + F_local 1024x3 + LF_global 1024
    assert roofline.block_bytes("VF", rows, 96) == 4 * (128 + 1024 * 4
                                                        + 128 * 97)
    # FT: F_local 1024x3 + T_local 512x4 + LT_global; M (1024 x 4) + L
    assert roofline.block_bytes("FT", rows, 4) == 4 * (1024 * 3 + 512 * 5
                                                       + 1024 * 5)
    # TT reads its one table once
    assert roofline.block_bytes("TT", rows, 8) == 4 * (512 * 5 + 512 * 9)
    deg = {"VV": 32, "VT": 64}
    vv = roofline.block_bytes("VV", rows, 32)
    vt = roofline.block_bytes("VT", rows, 64)
    # every segment of both relations: exact
    assert roofline.least_bytes(["VV", "VT"], 20, 10, rows, deg) == 10 * (
        vv + vt)
    # 13 blocks of 10 segments: at most 10 of the cheaper relation
    lo, hi = sorted((vv, vt))
    assert roofline.least_bytes(["VV", "VT"], 13, 10, rows, deg) == (
        10 * lo + 3 * hi)


def test_peaks_table_is_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")


def test_trace_reduction_against_hand_counts():
    import jax
    with open(os.path.join(DATA, "tpu_like.textproto"),
               encoding="utf-8") as f:
        pd = jax.profiler.ProfileData.from_text_proto(f.read())
    r = xplane.reduce(pd)
    assert r.window_s == pytest.approx(10_000e-9)
    assert r.busy_s == pytest.approx(4_000e-9)
    assert r.idle_share == pytest.approx(0.6)
    # module time inside the pass, summed per jitted function
    assert r.program_s == pytest.approx({"_relation_block_fused": 3_000e-9,
                                         "_union_jit": 1_000e-9})
    assert r.seconds_of(["_relation_block_fused", "_union_jit"]) == \
        pytest.approx(4_000e-9)
    assert r.seconds_of(["_gather_union_xla"]) is None
    # ops keyed by their program; the same op name in two programs apart
    assert r.op_s == pytest.approx({
        "_relation_block_fused:sort.3": 2_500e-9,
        "_relation_block_fused:fusion.2": 1_000e-9,
        "_union_jit:fusion.2": 1_000e-9})
    # gaps longest first, each named by the shortest host span covering it
    assert r.gaps == [
        ("chipbench.step.discrete_gradient", pytest.approx(4_000e-9)),
        ("Transpose", pytest.approx(1_500e-9)),
        ("chipbench.step.critical_points", pytest.approx(500e-9))]


def test_trace_without_a_device_plane_reduces_to_nothing():
    import jax
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "cpu.xplane.pb"))
    assert any(p.name == "/host:CPU" for p in pd.planes)
    assert xplane.reduce(pd) is None


def test_interval_union_and_gaps():
    busy = xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy == [(1, 4), (5, 8)]
    assert xplane.gaps_of(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert xplane.program_name("jit__place_rows(11689433692295701711)") \
        == "_place_rows"


def test_mesh_is_a_function_of_the_seed():
    cfg = {"grid": [6, 5, 4], "capacity": 64,
           "field": {"k": 8, "sigma_per_span": 0.125, "scale_per_span": 1.0},
           "mask": {"drop_below_quantile": 0.4, "field_seed": 3}}
    a = meshgen.generate(cfg, 2 ** 33 + 5)
    b = meshgen.generate(cfg, 2 ** 33 + 5)
    c = meshgen.generate(cfg, 2 ** 33 + 6)
    assert np.array_equal(a.scalars, b.scalars)
    # the mesh is the configuration's; only the field follows the seed
    assert np.array_equal(a.tets, c.tets)
    assert not np.array_equal(a.scalars, c.scalars)
    n_cells = 5 * 4 * 3
    assert len(a.tets) == 6 * round(0.6 * n_cells)
    assert np.array_equal(a.grid_ids(a.points), np.arange(a.n_vertices))


def test_reference_on_two_tets():
    # two tets sharing face (1, 2, 3)
    tets = np.array([[0, 1, 2, 3], [1, 2, 3, 4]])
    cx = reference.Complex(tets, 5)
    assert len(cx.E) == 9 and len(cx.F) == 7 and len(cx.T) == 2
    assert list(cx.relation_row("TT", 0)) == [1]
    assert list(cx.relation_row("VV", 0)) == [1, 2, 3]
    assert list(cx.relation_row("FT", int(cx.face_ids([[1, 2, 3]])[0]))) \
        == [0, 1]
    rank = np.array([0, 1, 2, 3, 4])
    t = reference.vertex_types(cx, rank)
    assert t[0] == reference.MINIMUM and t[4] == reference.MAXIMUM
    assert (t[1:4] == reference.REGULAR).all()
    # vertex 4 (highest) has the whole star of tet 1 below it
    # by hand: 4 takes its least lower edge (1, 4); then the least cells
    # with one free facet: (1,2,4)-(2,4), (1,3,4)-(3,4), (1,2,3,4)-(2,3,4)
    pairs = reference.lower_star_pairs(cx, rank, 4)
    assert pairs == {(4,): (1, 4), (1, 4): (4,),
                     (1, 2, 4): (2, 4), (2, 4): (1, 2, 4),
                     (1, 3, 4): (3, 4), (3, 4): (1, 3, 4),
                     (1, 2, 3, 4): (2, 3, 4), (2, 3, 4): (1, 2, 3, 4)}
    assert reference.lower_star_pairs(cx, rank, 0) == {(0,): None}


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "box.cp", "--seed", str(2 ** 32 + 3), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("grid,mask", [
    ([7, 6, 5], None),
    ([11, 10, 9], {"drop_below_quantile": 0.4, "field_seed": 2507})])
def test_reference_gradient_is_each_lower_star(grid, mask):
    cfg = {"grid": grid, "capacity": 100,
           "field": {"k": 8, "sigma_per_span": 0.125, "scale_per_span": 1.0},
           "mask": mask}
    raw = meshgen.generate(cfg, 2 ** 31 + 5)
    rank = meshgen.injective_rank(raw.scalars)
    cx = reference.Complex(raw.tets, raw.n_vertices)
    g = reference.gradient(cx, rank)
    rows = {2: ("E", cx.edge_ids), 3: ("F", cx.face_ids),
            4: ("T", cx.tet_ids)}
    cells = {"V": lambda i: (int(i),), "E": lambda i: tuple(cx.E[i]),
             "F": lambda i: tuple(cx.F[i]), "T": lambda i: tuple(cx.T[i])}
    links = {"V": ("pair_v2e",), "E": ("pair_e2v", "pair_e2f"),
             "F": ("pair_f2e", "pair_f2t"), "T": ("pair_t2f",)}
    n_cells = 0
    for v in range(raw.n_vertices):
        for c, want in reference.lower_star_pairs(cx, rank, v).items():
            kind = "V" if len(c) == 1 else rows[len(c)][0]
            i = c[0] if kind == "V" else int(rows[len(c)][1](
                np.array([c]))[0])
            got = None
            for name in links[kind]:
                if g[name][i] >= 0:
                    got = tuple(int(x) for x in cells[name[-1].upper()](
                        g[name][i]))
            assert bool(g["crit_" + kind.lower()][i]) == (want is None)
            assert got == want, (v, c)
            n_cells += 1
    assert n_cells == raw.n_vertices + len(cx.E) + len(cx.F) + len(cx.T)


def test_completed_rows_are_the_tets_across_each_face():
    raw = meshgen.generate(
        {"grid": [6, 5, 5], "capacity": 100,
         "field": {"k": 8, "sigma_per_span": 0.125, "scale_per_span": 1.0},
         "mask": {"drop_below_quantile": 0.4, "field_seed": 3}}, 11)
    cx = reference.Complex(raw.tets, raw.n_vertices)
    ids = np.arange(len(cx.T))
    rows = cx.completed_rows("TT", ids)
    for t in ids:
        got = np.sort(rows[t][rows[t] >= 0])
        assert np.array_equal(got, cx.relation_row("TT", int(t)))
