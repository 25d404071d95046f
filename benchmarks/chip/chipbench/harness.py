"""The harness: one cell of ``BENCHMARK.json``, run once.

Everything that belongs to one configuration, mesh kind, traffic mix or
per-layer metric is found by name: ``BENCHMARK.json``'s ``configs[].file``,
the generator ``meshes/<mesh>.py`` that the configuration file names (see
:mod:`.meshgen`), ``traffic/<traffic>.json`` and ``metrics/<metric>.py`` (a
module with ``read(run)`` that returns the metric's value, or None where
the run holds nothing to read). A new cell is files plus one ``workloads``
entry; a new mesh kind is one more file.

Order of a run: check the device, generate the mesh from the seed, set the
program up (segment, precondition, the traffic's inputs), one whole warm-up
pass; that is set-up. Then the window: whole passes for ``--seconds``. Then
the device's peak memory is read, the last pass's blocks are read back, the
engine is freed, and the answers are compared with the reference. With
``--trace 1`` one more pass runs under the profiler, and the per-layer
metrics are read from it and from the window's counters.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, drive, load_file, meshgen, roofline, xplane
from . import reference as ref
from .meter import CompileMeter

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
TRACE_DIR = ".chipbench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """A workload entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str


def load_cell(root: str, workload: str) -> Cell:
    """Resolve ``workload`` in ``<root>/BENCHMARK.json``: its
    configuration file, its traffic file and the metrics that apply to
    it (those without a ``workloads`` list, and those that list it)."""
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    w = _by_name(spec["workloads"], workload, "workload")
    c = _by_name(spec["configs"], w["config"], "config")
    bench_dir = os.path.join(root, spec["paths"][0])
    with open(os.path.join(root, c["file"]), encoding="utf-8") as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"),
              encoding="utf-8") as f:
        traffic = json.load(f)

    def applies(m):
        return workload in m.get("workloads", [workload])
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                bench_dir=bench_dir)


def load_reader(bench_dir: str, name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    return load_file(os.path.join(bench_dir, "metrics", name + ".py"),
                     "chipbench_metric_" + name).read


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read: the reduced trace of the traced
    pass (None without one), each window pass's engine counters, the
    compiles inside the window, the launch shapes of the relation kernels
    with the blocks the traced pass produced, and the device's peaks."""

    trace: Optional[xplane.Reduced]
    pass_stats: List[Dict[str, float]]
    window_compiles: int
    launch: dict
    peaks: Dict[str, float]
    readers: Dict[str, Callable] = dataclasses.field(default_factory=dict)

    def read(self, name: str):
        return self.readers[name](self)

    def per_pass(self, counter: str) -> float:
        """Mean of an engine counter over the window's passes."""
        return float(np.mean([s[counter] for s in self.pass_stats]))


def rate(n_tets: int, n_passes: int, seconds: float) -> float:
    """Tets analysed per second: mesh tets times whole passes over the
    seconds from the window's start to the end of the last pass."""
    return n_tets * n_passes / seconds


# -- correctness -----------------------------------------------------------

def _sample(rng, n: int, k: int, always=()) -> np.ndarray:
    pick = rng.choice(n, size=min(k, n), replace=False)
    return np.unique(np.concatenate([pick, np.asarray(always, np.int64)]))


# segments whose relation blocks are read back and compared, besides the
# first and the last
BLOCK_SAMPLE = 24


def compare(program: drive.Program, raw, rank_raw: np.ndarray,
            passes: List[drive.Pass], seed: int) -> Dict[str, int]:
    """Every number compared for the cell, each with the limit 0: the
    program's simplices (``mesh_tables``), the blocks of each relation the
    pass reads for sampled segments as the last pass's engine holds them
    (``blocks_<R>``), every row the last pass's completions returned
    (``completion_<R>``), each step's answer over the whole mesh
    (``cp_types``, ``dg_stars``, ``ms_*``), the gradient given as input
    (``grad_input_stars``) and the window passes whose answers differ from
    the last one's (``passes_differing``). The reference computes the
    gradient itself; Morse-Smale's reference runs on it. Frees the last
    pass's engine."""
    rng = np.random.default_rng([seed, 7])
    sm, pre = program.sm, program.pre
    last = passes[-1]
    ns = sm.n_segments
    segs = _sample(rng, ns, BLOCK_SAMPLE, always=(0, ns - 1))
    blocks = {r: {int(s): last.eng.get(r, int(s)) for s in segs}
              for r in program.pass_relations}
    completed = [(r, q, np.asarray(M), np.asarray(L))
                 for r, q, (M, L) in last.completed]
    last.release()

    cx = ref.Complex(raw.tets, raw.n_vertices)
    ids = check.Ids(raw, cx, sm, pre)
    out = {"mesh_tables": ids.table_mismatch}

    for r, held in blocks.items():
        out[f"blocks_{r}"] = check.block_mismatch(held, pre, ids, r)
    for r, q, M, L in completed:
        out[f"completion_{r}"] = out.get(f"completion_{r}", 0) \
            + check.completion_mismatch(M, L, ids, r, q)

    got = last.answers
    if "types" in got:
        out["cp_types"] = check.type_mismatch(
            got["types"], ids, ref.vertex_types(cx, rank_raw))
    if "grad" in got or "grad" in program.state:
        grad = ref.gradient(cx, rank_raw)
    if "grad" in program.state:
        out["grad_input_stars"] = check.gradient_mismatch(
            program.state["grad"], ids, grad)
    if "grad" in got:
        out["dg_stars"] = check.gradient_mismatch(got["grad"], ids, grad)
    if "ms" in got:
        want = ref.morse_smale(cx, v_pair=grad["pair_v2e"],
                               t_pair=grad["pair_t2f"],
                               crit_e=grad["crit_e"], crit_f=grad["crit_f"],
                               crit_t=grad["crit_t"])
        out.update(check.ms_mismatch(got["ms"], ids, want))
    digests = [tuple(check.digest(p.answers[k]) for k in sorted(p.answers))
               for p in passes]
    out["passes_differing"] = sum(d != digests[-1] for d in digests)
    return out


# -- the run -------------------------------------------------------------------

def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _traced_pass(program: drive.Program, root: str):
    """One pass under the profiler, with a host span around the pass and
    each step; returns the pass and the reduced trace."""
    import jax

    d = os.path.join(root, TRACE_DIR)
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.PASS_SPAN):
            p = program.run_pass(annotate=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    reduced = None
    if files:
        pd = jax.profiler.ProfileData.from_file(files[0])
        reduced = xplane.reduce(pd)
    shutil.rmtree(d, ignore_errors=True)
    return p, reduced


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             log: Callable[[str], None] = print) -> dict:
    """Run one cell once; returns the result object (the last line's
    JSON). Raises :class:`NoChip` before any work where the device is not
    a TPU (``require_tpu``) or has fewer chips than the cell asks for."""
    import jax

    cell = load_cell(root, workload)
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"{workload} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    peaks = roofline.peaks(dev.device_kind) if require_tpu else {}

    t0 = time.perf_counter()
    raw = meshgen.generate(cell.config, seed, cell.bench_dir)
    rank_raw = meshgen.injective_rank(raw.scalars)
    log(f"mesh generated {time.perf_counter() - t0:.6f}s")
    program = drive.Program(raw, rank_raw, cell.config, cell.traffic,
                            log=log)
    log(f"mesh vertices={raw.n_vertices} tets={raw.n_tets} "
        f"segments={program.sm.n_segments} facts={json.dumps(raw.facts)} "
        f"setup_inputs={sorted(cell.traffic.get('inputs', {}))}")
    warm = program.run_pass()
    log(f"warm-up pass {warm.seconds:.6f}s")
    warm.release()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.6f}")

    with CompileMeter() as meter:
        passes, elapsed = drive.window(program, seconds)
    for i, p in enumerate(passes):
        log(f"pass {i} {p.seconds:.6f}s")
    log(f"window {elapsed:.6f}s passes={len(passes)} "
        f"compiles={meter.n} compile_s={meter.seconds:.6f}")
    peak = _peak_bytes(dev)
    pass_stats = [p.stats for p in passes]
    launch = {"relations": program.pass_relations,
              "n_segments": program.sm.n_segments,
              "rows": {"V": program.pre.tables.NV,
                       "E": program.pre.tables.NE,
                       "F": program.pre.tables.NF,
                       "T": program.pre.tables.NT},
              "deg": dict(passes[-1].eng.deg)}
    t0 = time.perf_counter()
    checks = compare(program, raw, rank_raw, passes, seed)
    log(f"checks {time.perf_counter() - t0:.6f}s")
    correct = all(v <= 0 for v in checks.values())

    metrics: Dict[str, dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(passes),
              "failed": 0 if correct else len(passes)}
    if trace:
        t0 = time.perf_counter()
        tp, reduced = _traced_pass(program, root)
        log(f"traced pass {tp.seconds:.6f}s, with the trace's reduction "
            f"{time.perf_counter() - t0:.6f}s")
        result["attempted"] += 1
        same = ({k: check.digest(v) for k, v in tp.answers.items()}
                == {k: check.digest(v) for k, v in passes[-1].answers.items()})
        checks["traced_pass_differs"] = int(not same)
        correct = correct and same
        result["correct"] = correct
        result["failed"] = 0 if correct else result["attempted"]
        tp.release()
        launch["produced"] = tp.stats["segments_produced"]
        record = RunRecord(trace=reduced, pass_stats=pass_stats,
                           window_compiles=meter.n, launch=launch,
                           peaks=peaks)
        record.readers = {m["name"]: load_reader(cell.bench_dir, m["name"])
                          for m in cell.per_layer}
        for m in cell.per_layer:
            v = record.read(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in xplane.top(reduced.op_s)],
                "idle_gaps": [list(x) for x in reduced.gaps]}
            log("device programs: " + json.dumps(
                xplane.top(reduced.program_s, 20)))
    else:
        values = {"tets_per_s": rate(raw.n_tets, len(passes), elapsed),
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in checks.items()}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    """The command line of ``benchmarks/chip/run.py``."""
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start,
                          log=lambda msg: print(msg, flush=True))
    except (NoChip, roofline.UnknownDevice) as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 1
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set (JAX reads it itself), else the fixed ``<checkout>/.jax_cache``.
    Every program is cached, however small or quick to compile, so a
    run after the first compiles nothing."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d

