"""Whole benchmark runs on the CPU, each configuration cut to its own
``tiny`` sizes: the look for a chip is skipped, everything else runs as on
the chip. A sound run is correct; the control (the bfloat16 vertex order)
and each fault planted in the timed path come out not correct; and a new
cell, configuration, mesh kind, traffic mix and per-layer metric are added
as files plus one ``BENCHMARK.json`` entry each, with no edit to the
harness."""

import json
import os
import shutil
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from chipbench import control, harness, meshgen  # noqa: E402

SEED = 2 ** 31 + 77
# cells of traffic mixes the benchmark keeps for a later PR (PERF.md, Open
# questions): each runs here as a cell of the tiny checkout
with open(os.path.join(BENCH, "tests", "data", "later_cells.json"),
          encoding="utf-8") as _f:
    LATER = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"] + LATER]


def _tiny_root(path):
    """A checkout holding the benchmark with each configuration updated by
    its own ``tiny`` object (same files otherwise), and the cells kept for
    later."""
    shutil.copytree(BENCH, os.path.join(path, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    spec["workloads"] += LATER
    for c in spec["configs"]:
        p = os.path.join(path, c["file"])
        with open(p, encoding="utf-8") as f:
            cfg = json.load(f)
        cfg.update(cfg["tiny"])
        with open(p, "w", encoding="utf-8") as f:
            json.dump(cfg, f)
    with open(os.path.join(path, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    return spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("checkout"))
    _tiny_root(path)
    return path


def _run(root, workload, trace=False, seed=SEED):
    return harness.run_cell(root, workload, seed, 0.05, trace,
                            time.perf_counter(), require_tpu=False,
                            log=lambda m: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    r = _run(root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tets_per_s", "peak_hbm_gb", "setup_s"}
    assert r["metrics"]["tets_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["limit"] == 0 for c in r["checks"].values())


# At these tiny grids rounding the field to bfloat16 reorders few
# vertices, and on most seeds none whose type or pairing it changes; these
# seeds are ones where it does. On the chip, at the cells' own size, every
# seed tried changes them (PERF.md).
CONTROL_SEED = {"box": 2 ** 31 + 101, "masked": 2 ** 31 + 84}


@pytest.mark.parametrize("workload,number", [
    ("box.cp", "cp_types"), ("masked.cp", "cp_types"),
    ("box.dg", "dg_stars"), ("masked.ms", "grad_input_stars")])
def test_control_is_not_correct(root, workload, number):
    seed = CONTROL_SEED[workload.split(".")[0]]
    got = control.readings(root, workload, seed)
    assert got[number] > 0, got
    sound = _run(root, workload, seed=seed)
    assert sound["correct"], sound["checks"]


def _alter_first_answer(fn):
    def altered(*a, **k):
        t = fn(*a, **k)
        return t.at[0].set(t[0] + 1)
    return altered


def _flip_first_critical_vertex(fn):
    def altered(*a, **k):
        crit_vertex, *rest = fn(*a, **k)
        flipped = crit_vertex.at[0].set(
            (~crit_vertex[0].astype(bool)).astype(crit_vertex.dtype))
        return (flipped, *rest)
    return altered


def _drop_first_neighbour(fn):
    def altered(*a, **k):
        M, L = fn(*a, **k)
        return M.at[:, 0, 0].set(-1), L
    return altered


def _half_of_each_batch(fn):
    return lambda *a, **k: [b[:max(1, len(b) // 2)] for b in fn(*a, **k)]


def _drop_first_completed(fn):
    def altered(*a, **k):
        M, L = fn(*a, **k)
        if hasattr(M, "at"):
            return M.at[:, 0].set(-1), L
        M = M.copy()
        M[:, 0] = -1
        return M, L
    return altered


def _identity(x):
    return x


def _one_scalar_moved(fn):
    def altered(*a, **k):
        sm = fn(*a, **k)
        sm.scalars[0] += 1.0
        return sm
    return altered


def _second_call_altered(fn):
    calls = []

    def altered(*a, **k):
        calls.append(1)
        out = fn(*a, **k)
        if len(calls) == 2:          # the window's first pass
            out = (out[0].copy(), out[1])
            out[0][0] += 1
        return out
    return altered


def _no_op(*a, **k):
    return None


# (cell, module, attribute, how the timed path is broken, the numbers
# one of which has to catch it)
FAULTS = {
    "answer_altered": ("box.cp", "repro.algorithms.critical_points",
                       "_classify_batch", _alter_first_answer, ("cp_types",)),
    "answer_altered_dg": ("box.dg", "repro.algorithms.discrete_gradient",
                          "_lower_star_batch", _flip_first_critical_vertex,
                          ("dg_stars",)),
    "block_altered": ("masked.cp", "repro.kernels.ops", "relation_block",
                      _drop_first_neighbour, ("blocks_VV", "blocks_VT")),
    "half_the_batch": ("box.cp", "repro.algorithms.critical_points",
                       "segment_batches", _half_of_each_batch, ("cp_types",)),
    "half_the_batch_dg": ("box.dg", "repro.algorithms.discrete_gradient",
                          "segment_batches", _half_of_each_batch,
                          ("dg_stars",)),
    "state_unchanged": ("box.dg", "repro.algorithms.discrete_gradient",
                        "run_partitioned", lambda fn: _no_op, ("dg_stars",)),
    "paths_unfollowed": ("masked.ms", "repro.algorithms.morse_smale",
                         "_pointer_jump", lambda fn: _identity,
                         ("ms_dest_min", "ms_dest_max")),
    "completion_altered": ("masked.ms", "repro.algorithms.morse_smale",
                           "complete_adjacency", _drop_first_completed,
                           ("ms_dest_max", "ms_separatrices")),
    "completion_read_altered": ("masked.ms", "repro.core.adjacency",
                                "execute_completion_device",
                                _drop_first_completed, ("completion_TT",)),
    "mesh_altered": ("box.cp", "repro.core.mesh", "segment_mesh",
                     _one_scalar_moved, ("mesh_tables",)),
    "one_pass_altered": ("box.cp", "repro.algorithms.critical_points",
                         "critical_points", _second_call_altered,
                         ("passes_differing",)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    import importlib
    workload, module, attr, breaks, caught_by = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, breaks(getattr(mod, attr)))
    r = harness.run_cell(root, workload, SEED, 1.0, False,
                         time.perf_counter(), require_tpu=False,
                         log=lambda m: None)
    assert not r["correct"], r["checks"]
    assert any(r["checks"][n]["value"] > 0 for n in caught_by), r["checks"]
    assert r["failed"] == r["attempted"]


def test_new_cell_is_files_plus_one_entry(tmp_path):
    path = str(tmp_path)
    spec = _tiny_root(path)
    bench = os.path.join(path, "benchmarks", "chip")
    with open(os.path.join(bench, "configs", "box.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(name="slab", grid=[10, 6, 5])
    with open(os.path.join(bench, "configs", "slab.json"), "w",
              encoding="utf-8") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "cp.json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    traffic["steps"][0]["args"]["batch_segments"] = 4
    with open(os.path.join(bench, "traffic", "cp4.json"), "w",
              encoding="utf-8") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "metrics", "engine.launches.py"), "w",
              encoding="utf-8") as f:
        f.write("def read(run):\n"
                "    return run.per_pass('kernel_launches')\n")
    spec["configs"].append({"name": "slab", "source": "test",
                            "file": "benchmarks/chip/configs/slab.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "slab.cp4", "config": "slab",
                              "traffic": "cp4", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "engine.launches", "unit": "launches",
                              "better": "lower", "source": "program_counter",
                              "layer": "engine producer (core/engine.py)",
                              "moves": "tets_per_s",
                              "workloads": ["slab.cp4"]})
    with open(os.path.join(path, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    r = _run(path, "slab.cp4", trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["engine.launches"]["value"] > 0
    assert r["metrics"]["driver.window_compiles"]["value"] == 0
    # no device plane on the CPU: the trace's metrics are left out, not 0
    assert "device.idle_share" not in r["metrics"]
    assert "kernel.relation_roofline" not in r["metrics"]
    # the cells already there do not list the new metric
    assert "engine.launches" not in _run(path, "box.cp", trace=True)[
        "metrics"]


# A mesh kind with non-integer coordinates: the Kuhn mesh of the grid with
# every vertex moved by a seeded offset in (-a, a)^3, same topology.
JITTER = '''import os

import numpy as np

from chipbench import meshgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def generate(config, seed):
    raw = meshgen.generate(dict(config, mesh="kuhn"), seed, BENCH)
    a = float(config["jitter"]["amplitude"])
    rng = np.random.default_rng(int(config["jitter"]["seed"]))
    raw.points = (raw.points + rng.uniform(-a, a, raw.points.shape)
                  ).astype(np.float32)
    raw.facts["jitter"] = a
    return raw
'''


@pytest.fixture(scope="module")
def jitter_root(tmp_path_factory):
    """The tiny checkout with a new mesh kind added as files: the generator
    ``meshes/jitter.py``, its configuration file with ``mesh`` and
    ``tiny``, and one ``configs`` entry and one ``workloads`` entry for each
    of two traffic mixes."""
    path = str(tmp_path_factory.mktemp("jitter"))
    spec = _tiny_root(path)
    bench = os.path.join(path, "benchmarks", "chip")
    with open(os.path.join(bench, "meshes", "jitter.py"), "w",
              encoding="utf-8") as f:
        f.write(JITTER)
    with open(os.path.join(bench, "configs", "box.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(name="jitter", mesh="jitter", grid=[30, 30, 30],
               tiny={"grid": [9, 7, 8]},
               jitter={"amplitude": 0.3, "seed": 12})
    cfg.update(cfg["tiny"])
    with open(os.path.join(bench, "configs", "jitter.json"), "w",
              encoding="utf-8") as f:
        json.dump(cfg, f)
    spec["configs"].append({"name": "jitter", "source": "test",
                            "file": "benchmarks/chip/configs/jitter.json",
                            "reduced": [], "why": "test"})
    for traffic in ("cp", "dg"):
        spec["workloads"].append({"name": "jitter." + traffic,
                                  "config": "jitter", "traffic": traffic,
                                  "chips": 1, "why": "test"})
    with open(os.path.join(path, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    return path


@pytest.mark.parametrize("traffic", ["cp", "dg"])
def test_new_mesh_kind_is_files_plus_one_entry(jitter_root, traffic):
    logged = []
    r = harness.run_cell(jitter_root, "jitter." + traffic, SEED, 0.05,
                         False, time.perf_counter(), require_tpu=False,
                         log=logged.append)
    assert r["correct"], r["checks"]
    assert r["checks"]["mesh_tables"]["value"] == 0
    assert any('"jitter": 0.3' in m for m in logged)


def _two_vertices_swapped(fn):
    def swapped(self, points):
        out = fn(self, points)
        out[[0, 1]] = out[[1, 0]]
        return out
    return swapped


def test_new_mesh_kind_with_a_wrong_map_is_not_correct(jitter_root,
                                                        monkeypatch):
    monkeypatch.setattr(meshgen.RawMesh, "raw_ids",
                        _two_vertices_swapped(meshgen.RawMesh.raw_ids))
    r = _run(jitter_root, "jitter.cp")
    assert not r["correct"], r["checks"]
    assert r["checks"]["mesh_tables"]["value"] > 0
