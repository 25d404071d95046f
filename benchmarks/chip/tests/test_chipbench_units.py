"""Unit tests of the on-chip benchmark's harness, run on the CPU: discovery
of cells by name, the meshes and their checks and vertex map, the rate and
roofline arithmetic, the peaks table, the trace reduction and the refusal
to run without a TPU."""

import hashlib
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
    cfg = {"mesh": "kuhn", "grid": [6, 5, 4], "capacity": 64,
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
    assert np.array_equal(a.raw_ids(a.points), np.arange(a.n_vertices))
    assert a.facts == {"grid": [6, 5, 4], "kept_share": 0.6}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


# sha1 of points, tets and scalars of each configuration's mesh as it is
# run, computed before the Kuhn split moved into ``meshes/kuhn.py``: the
# move changed no mesh, bit for bit
MESH_SHA1 = {
    ("box", 0): ("e17d7d7cba0e4f310bc18d1a780c91d826f2b73c", "176ff37e9553ce710c3b6433e6cd6efadaabf709", "5127bcc5110f18cc03509d30b9831904a5222c59"),  # noqa: E501
    ("box", 2147483725): ("e17d7d7cba0e4f310bc18d1a780c91d826f2b73c", "176ff37e9553ce710c3b6433e6cd6efadaabf709", "a9e65bd114161afbfda73216b4d435650abae058"),  # noqa: E501
    ("box", 8589934597): ("e17d7d7cba0e4f310bc18d1a780c91d826f2b73c", "176ff37e9553ce710c3b6433e6cd6efadaabf709", "8caa6d4a89ed5754bbcdffe09d60117424154766"),  # noqa: E501
    ("masked", 0): ("0b239db9c08fcf143ef8021061b7d8a002c8b82e", "b0b183424a246d722c97ae1d4e0826d07e2f454b", "30e3713680a0467e112cb37b0d45c3cfc87ad840"),  # noqa: E501
    ("masked", 2147483725): ("0b239db9c08fcf143ef8021061b7d8a002c8b82e", "b0b183424a246d722c97ae1d4e0826d07e2f454b", "8883d7c21d21fee19b6314793d265b75785e9601"),  # noqa: E501
    ("masked", 8589934597): ("0b239db9c08fcf143ef8021061b7d8a002c8b82e", "b0b183424a246d722c97ae1d4e0826d07e2f454b", "48863c48724e39ca0ad7e58a6cf9032dcd480186"),  # noqa: E501
}


@pytest.mark.parametrize("config,seed", sorted(MESH_SHA1))
def test_mesh_is_pinned(config, seed):
    raw = meshgen.generate(_config(config), seed)
    got = tuple(hashlib.sha1(a.tobytes()).hexdigest()
                for a in (raw.points, raw.tets, raw.scalars))
    assert got == MESH_SHA1[config, seed]


def _grid_lookup(raw, points):
    """The vertex map the harness used while every mesh was a grid: a
    table indexed by the rounded grid coordinate."""
    nx, ny, nz = raw.facts["grid"]
    p = np.rint(np.asarray(points, np.float64)).astype(np.int64)
    full = (p[:, 0] * ny + p[:, 1]) * nz + p[:, 2]
    lut = np.full(nx * ny * nz, -1, np.int64)
    mine = np.rint(raw.points.astype(np.float64)).astype(np.int64)
    lut[(mine[:, 0] * ny + mine[:, 1]) * nz + mine[:, 2]] = np.arange(
        len(mine))
    ok = ((p >= 0).all(1) & (p[:, 0] < nx) & (p[:, 1] < ny)
          & (p[:, 2] < nz))
    return np.where(ok, lut[np.where(ok, full, 0)], -1)


@pytest.mark.parametrize("config", ["box", "masked"])
def test_raw_ids_is_an_exact_coordinate_match(config):
    raw = meshgen.generate(_config(config), 3)
    perm = np.random.default_rng(4).permutation(raw.n_vertices)
    shuffled = raw.points[perm]
    got = raw.raw_ids(shuffled)
    assert np.array_equal(got, _grid_lookup(raw, shuffled))
    assert np.array_equal(got, perm)       # the inverse of the shuffle
    # a point not in the mesh: off the grid, between grid points, one ulp
    # away from a vertex, and (masked) a grid point the mask removed
    first = raw.points[0]
    away = np.array([[-1, 0, 0], [0.5, 0, 0],
                     np.nextafter(first, np.float32(np.inf)),
                     [1e6, 1e6, 1e6]], np.float32)
    assert (raw.raw_ids(away) == -1).all()
    assert raw.raw_ids(-0.0 * raw.points[:1] + raw.points[:1])[0] == 0
    nx, ny, nz = raw.facts["grid"]
    if raw.n_vertices < nx * ny * nz:
        grid = np.stack(np.meshgrid(np.arange(nx), np.arange(ny),
                                    np.arange(nz), indexing="ij"),
                        -1).reshape(-1, 3).astype(np.float32)
        ids = raw.raw_ids(grid)
        assert np.array_equal(ids, _grid_lookup(raw, grid))
        assert (ids == -1).sum() == nx * ny * nz - raw.n_vertices


BREAKS = {
    "repeated_point": "raw.points[1] = raw.points[0]",
    "unsorted_tet_row": "raw.tets[3] = raw.tets[3][::-1]",
    "id_out_of_range": "raw.tets[2, 3] = len(raw.points)",
    "negative_id": "raw.tets[2, 0] = -1",
    "points_not_float32": "raw.points = raw.points.astype(np.float64)",
}


@pytest.mark.parametrize("fault", sorted(BREAKS))
def test_generator_check_names_the_generator(tmp_path, fault):
    os.makedirs(tmp_path / "meshes")
    (tmp_path / "meshes" / "broken.py").write_text(
        "import numpy as np\n"
        "from chipbench import meshgen\n\n\n"
        "def generate(config, seed):\n"
        "    raw = meshgen.generate(dict(config, mesh='kuhn'), seed,\n"
        f"                           {BENCH!r})\n"
        f"    {BREAKS[fault]}\n"
        "    return raw\n")
    cfg = {"mesh": "broken", "grid": [5, 4, 3], "capacity": 64,
           "field": {"k": 8, "sigma_per_span": 0.125, "scale_per_span": 1.0},
           "mask": None}
    with pytest.raises(meshgen.MeshError, match="'broken'"):
        meshgen.generate(cfg, 7, str(tmp_path))
    assert meshgen.generate(dict(cfg, mesh="kuhn"), 7).n_vertices == 60


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
    cfg = {"mesh": "kuhn", "grid": grid, "capacity": 100,
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
        {"mesh": "kuhn", "grid": [6, 5, 5], "capacity": 100,
         "field": {"k": 8, "sigma_per_span": 0.125, "scale_per_span": 1.0},
         "mask": {"drop_below_quantile": 0.4, "field_seed": 3}}, 11)
    cx = reference.Complex(raw.tets, raw.n_vertices)
    ids = np.arange(len(cx.T))
    rows = cx.completed_rows("TT", ids)
    for t in ids:
        got = np.sort(rows[t][rows[t] >= 0])
        assert np.array_equal(got, cx.relation_row("TT", int(t)))
