"""Program spans (``core/spans.py``): the names in the source are the closed
set, the driver paths record the spans they must, and the engine's phase
counters are the sums of their spans' host intervals."""

import ast
import math
import os
import pathlib
import time

import jax
import numpy as np
import pytest

from repro.algorithms import fields
from repro.algorithms.critical_points import critical_points, total_order
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale
from repro.core import spans
from repro.core.adjacency import complete_adjacency, device_chunk
from repro.core.engine import RelationEngine
from repro.core.mesh import segment_mesh
from repro.core.segtables import precondition
from repro.data.meshgen import structured_grid

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
# callables whose first argument is a span name
SPAN_CALLS = ("span", "spanned", "_timed")
MS_RELS = ["VE", "VF", "VT", "TT", "FT"]


def _span_calls():
    """(file, line, first argument node) of every span-naming call outside
    the primitives' own definitions."""
    for path in sorted(SRC.rglob("*.py")):
        if path.name == "spans.py":
            continue
        stack = [ast.parse(path.read_text(encoding="utf-8"))]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.FunctionDef) and node.name in SPAN_CALLS:
                continue
            stack.extend(ast.iter_child_nodes(node))
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else getattr(f, "attr", "")
            if name in SPAN_CALLS:
                yield path, node.lineno, (node.args[0] if node.args
                                          else None)


def test_every_span_name_is_declared_with_a_layer():
    assert len(set(spans.NAMES)) == len(spans.NAMES)
    for n in spans.NAMES:
        assert n.split(".")[0] in spans.LAYERS, n
    used = set()
    for path, line, arg in _span_calls():
        where = f"{path.relative_to(SRC)}:{line}"
        assert isinstance(arg, ast.Constant) and isinstance(arg.value, str), \
            f"{where}: span name is not a literal"
        assert arg.value in spans.NAMES, f"{where}: {arg.value!r}"
        used.add(arg.value)
    # the tuple is closed: no declared name is left without a site
    assert used == set(spans.NAMES)


def test_no_span_bypasses_the_primitive():
    for path in sorted(SRC.rglob("*.py")):
        if path.name == "spans.py":
            continue
        assert "TraceAnnotation" not in path.read_text(encoding="utf-8"), \
            path


@pytest.fixture(scope="module")
def mesh():
    m = structured_grid(8, 8, 7, jitter=0.15, seed=5,
                        scalar_fn=fields.gaussians(0, k=4, sigma=3.0,
                                                   scale=8))
    sm = segment_mesh(m, capacity=24)
    pre = precondition(sm, relations=["VV", "VT"] + MS_RELS)
    return pre, total_order(sm.scalars)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its value and the host
    program spans as ``{name: [seconds, ...]}``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    files = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    pd = jax.profiler.ProfileData.from_file(files[0])
    got = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name in spans.NAMES:
                    got.setdefault(name, []).append(e.duration_ns * 1e-9)
    return out, got


def _assert_counters_are_span_sums(stats, got):
    # the counter and the profiler read clocks of their own: a span that
    # releases the GIL (a device wait) reads up to ~40 us longer than the
    # counter's interval inside it, so allow 50 us a span
    for counters, name in ((("t_sync",), "engine.sync"),
                           (("t_integrate",), "engine.integrate"),
                           (("t_prepare", "t_dispatch"), "engine.dispatch")):
        durations = got.get(name, [])
        want = sum(getattr(stats, c) for c in counters)
        assert want == pytest.approx(sum(durations),
                                     abs=5e-5 * (len(durations) + 1)), name


@pytest.mark.parametrize("async_dispatch", [True, False])
def test_critical_points_pass_records_its_spans(mesh, tmp_path,
                                                async_dispatch):
    pre, rank = mesh

    def run():
        eng = RelationEngine(pre, ["VV", "VT"], cache_segments=4096,
                             async_dispatch=async_dispatch)
        critical_points(eng, pre, rank, consumer="device")
        return eng

    eng, got = _traced(tmp_path, run)
    must = {"driver.critical_points", "engine.init", "consumer.prefetch",
            "consumer.consume", "consumer.finalize", "consumer.reduce",
            "consumer.read_dev", "engine.dispatch", "engine.integrate"}
    if not async_dispatch:      # every launch is synced as it is made
        must.add("engine.sync")
    assert must <= set(got), must - set(got)
    assert len(got["engine.integrate"]) == eng.stats.kernel_launches
    _assert_counters_are_span_sums(eng.stats, got)


def test_morse_smale_pass_records_its_spans(mesh, tmp_path):
    pre, rank = mesh
    nt = pre.smesh.n_tets

    def run():
        eng = RelationEngine(pre, MS_RELS, cache_segments=4096)
        grad = discrete_gradient(eng, pre, rank, consumer="device")
        ms = morse_smale(eng, pre, grad, consumer="device", adjacency="tt",
                         batch_segments=2)
        ms_chunks = eng.stats.completion_chunks
        # and a completion cut into several chunks by an explicit batch
        complete_adjacency(eng, "TT", np.arange(nt), batch=256,
                           path="device", out="dev")
        return eng, grad, ms, ms_chunks

    (eng, grad, ms, ms_chunks), got = _traced(tmp_path, run)
    assert len(ms.dest_min) == pre.smesh.n_vertices
    must = {"driver.discrete_gradient", "driver.morse_smale",
            "driver.ms.descending", "driver.ms.successors",
            "driver.ms.cofacets", "driver.ms.ascending_jump",
            "driver.ms.separatrices", "completion.complete",
            "completion.plan", "completion.execute",
            "completion.width_check", "consumer.read_dev"}
    assert must <= set(got), must - set(got)
    # the pass's successors complete the paired tets in the rule's chunks
    paired = int((grad.pair_t2f >= 0).sum())
    assert ms_chunks == math.ceil(paired / device_chunk("TT", eng.deg["TT"]))
    assert ms_chunks == 1
    # one plan and one width check per completed chunk
    chunks = ms_chunks + math.ceil(nt / 256)
    assert len(got["completion.plan"]) == eng.stats.completion_chunks
    assert eng.stats.completion_chunks == chunks
    assert len(got["completion.width_check"]) == chunks
    assert len(got["completion.complete"]) == 2
    _assert_counters_are_span_sums(eng.stats, got)


def test_counter_bookkeeping_stays_outside_its_span(mesh, tmp_path):
    """A phase counter's bookkeeping runs after its span closes: a pause
    there (a garbage collection set off by the bump's allocations) must
    lengthen neither the span nor the counter."""
    pre, rank = mesh

    class PausingEngine(RelationEngine):
        def _bump(self, **deltas):
            if any(k.startswith("t_") for k in deltas):
                time.sleep(0.002)
            super()._bump(**deltas)

    def run():
        eng = PausingEngine(pre, ["VV", "VT"], cache_segments=4096,
                            async_dispatch=False)
        critical_points(eng, pre, rank, consumer="device")
        return eng

    eng, got = _traced(tmp_path, run)
    assert len(got["engine.sync"]) > 0
    _assert_counters_are_span_sums(eng.stats, got)


@pytest.mark.parametrize("batch", [None, 300])
def test_completion_chunks_are_plan_spans(mesh, tmp_path, batch):
    """``completion_chunks`` counts the plans: ceil(n / chunk) for the
    rule's chunk or an explicit batch, one ``completion.plan`` span each."""
    pre, _ = mesh
    nt = pre.smesh.n_tets

    def run():
        eng = RelationEngine(pre, ["TT"], cache_segments=4096)
        complete_adjacency(eng, "TT", np.arange(nt), batch=batch,
                           path="device")
        return eng

    eng, got = _traced(tmp_path, run)
    chunk = batch or device_chunk("TT", eng.deg["TT"])
    assert eng.stats.completion_chunks == math.ceil(nt / chunk)
    assert len(got["completion.plan"]) == eng.stats.completion_chunks


def test_spanned_keeps_the_function():
    def f(a):
        """doc"""
        return a + 1

    g = spans.spanned("driver.morse_smale")(f)
    assert (g.__name__, g.__doc__) == ("f", "doc")
    assert g(np.arange(3))[0] == 1
    with pytest.raises(TypeError):
        g(None)
