"""The split of device idle time by program layer (``chipbench/layers.py``),
checked against hand counts on recorded and hand-made traces."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

from chipbench import layers, xplane  # noqa: E402


def _trace(name):
    import jax
    with open(os.path.join(DATA, name), encoding="utf-8") as f:
        return jax.profiler.ProfileData.from_text_proto(f.read())


def test_idle_split_by_layer_against_hand_counts():
    pd = _trace("tpu_spans.textproto")
    s = layers.split(pd)
    ns = 1e-9
    assert s.window_s == pytest.approx(20_000 * ns)
    assert s.idle_s == pytest.approx(13_000 * ns)
    assert s.idle_by_layer == pytest.approx({
        "driver": 3_000 * ns, "completion": 2_500 * ns,
        "consumer": 1_000 * ns, "engine": 2_500 * ns})
    assert s.idle_untraced_s == pytest.approx(4_000 * ns)
    # innermost rule: the span that started last, on either line; a tie
    # in start goes to the one that ends first; TraceMe's #k=v# stripped
    assert s.idle_by_span == pytest.approx({
        "driver.morse_smale": 1_500 * ns, "driver.ms.separatrices": 1_500 * ns,
        "completion.plan": 1_500 * ns, "completion.execute": 500 * ns,
        "completion.width_check": 500 * ns, "consumer.consume": 1_000 * ns,
        "engine.dispatch": 1_000 * ns, "engine.sync": 1_500 * ns})
    # the sum identity with the harness's own reduction of the same trace
    r = xplane.reduce(pd)
    assert sum(s.idle_by_layer.values()) + s.idle_untraced_s == \
        pytest.approx(r.idle_share * r.window_s)
    assert s.self_s == pytest.approx({
        "driver.morse_smale": 3_000 * ns, "completion.complete": 1_000 * ns,
        "completion.plan": 3_000 * ns, "completion.execute": 2_000 * ns,
        "completion.width_check": 2_000 * ns, "engine.dispatch": 2_000 * ns,
        "driver.ms.separatrices": 2_000 * ns,
        "consumer.consume": 3_500 * ns, "engine.sync": 2_500 * ns})
    assert set(s.count.values()) == {1} and len(s.count) == 9


def test_trace_without_program_spans_is_all_untraced():
    # the hand counts of tpu_like.textproto (test_chipbench_units): busy
    # 4000 of a 10000 ns window; the benchmark's own spans are no layer
    pd = _trace("tpu_like.textproto")
    s = layers.split(pd)
    r = xplane.reduce(pd)
    assert (r.window_s, r.busy_s) == pytest.approx((10_000e-9, 4_000e-9))
    assert s.window_s == pytest.approx(r.window_s)
    assert s.idle_untraced_s == pytest.approx(6_000e-9)
    assert s.idle_by_layer == {k: 0.0 for k in layers.LAYERS}
    assert s.count == {} and s.self_s == {}


def test_trace_without_a_device_plane_splits_to_nothing():
    import jax
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "cpu.xplane.pb"))
    assert layers.split(pd) is None


@pytest.mark.parametrize("name,layer", [
    ("engine.sync#relation=TT#", "engine"), ("completion.plan", "completion"),
    ("driver.ms.descending", "driver"), ("consumer.upload", "consumer"),
    ("chipbench.pass", None), ("Transpose", None), ("engine", None),
    ("enginex.sync", None)])
def test_layer_of_event_names(name, layer):
    assert layers.layer_of(layers.base_name(name)) == layer


def test_command_runs_a_cell_and_restores_the_reduction(tmp_path):
    import importlib.util
    import json
    import shutil
    import time

    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                           "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    for c in spec["configs"]:
        p = os.path.join(root, c["file"])
        with open(p, encoding="utf-8") as f:
            cfg = json.load(f)
        cfg["grid"] = [9, 8, 7]
        with open(p, "w", encoding="utf-8") as f:
            json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    loc = importlib.util.spec_from_file_location(
        "idle_by_layer", os.path.join(BENCH, "idle_by_layer.py"))
    tool = importlib.util.module_from_spec(loc)
    loc.loader.exec_module(tool)
    reduce = xplane.reduce
    result, split, lines = tool.run(root, "box.cp", 2 ** 31 + 77, 0.05,
                                    time.perf_counter(), require_tpu=False,
                                    log=lambda m: None)
    assert xplane.reduce is reduce
    assert result["correct"], result["checks"]
    assert split is None        # the CPU trace has no device plane
    out = tool.summary(result, split, lines)
    assert out["end_to_end"]["tets_per_s"] > 0
    assert out["traced_pass_s"] > 0 and out["pass_s_median"] > 0
