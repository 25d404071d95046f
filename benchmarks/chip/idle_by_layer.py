#!/usr/bin/env python3
"""Run one cell as ``run.py --trace 1`` does, and split the traced pass's
device idle time by the program layer that held it.

  python3 benchmarks/chip/idle_by_layer.py --workload masked.ms --seed 7 \
      --seconds 51

The harness reduces the trace with ``chipbench/xplane.py`` and then deletes
it; for this one run that reduction is wrapped so that the same trace is
also split by ``chipbench/layers.py``. Standard output is the run's log,
then one line ``layers <json>``: the split, each program span's count and
self seconds, the check that the split sums to the harness's idle time,
the window's end-to-end numbers and the traced pass against the window's
passes, and what one span costs on this host outside and inside a profiler
session. The last line is the harness's result, as ``run.py`` prints it.
Exits non-zero without a TPU, as ``run.py`` does.
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import harness, layers, roofline, xplane  # noqa: E402


def run(root, workload, seed, seconds, t_start, require_tpu=True,
        log=print):
    """``harness.run_cell`` with ``--trace 1``; returns its result, the
    layer split of its traced pass (None without a device plane) and the
    run's log lines."""
    splits, lines = [], []
    reduce = xplane.reduce

    def reduce_and_split(pd, *args, **kwargs):
        splits.append(layers.split(pd))
        return reduce(pd, *args, **kwargs)

    def keep(msg):
        lines.append(msg)
        log(msg)

    xplane.reduce = reduce_and_split
    try:
        result = harness.run_cell(root, workload, seed, seconds, True,
                                  t_start, require_tpu=require_tpu,
                                  log=keep)
    finally:
        xplane.reduce = reduce
    return result, (splits[0] if splits else None), lines


def _logged(lines, pattern):
    return [tuple(float(g) for g in m.groups()) for m in
            (re.match(pattern, s) for s in lines) if m]


def summary(result: dict, split, lines) -> dict:
    """The ``layers`` line: the split and the window's numbers."""
    passes = [s for (s,) in _logged(lines, r"pass \d+ ([\d.]+)s$")]
    (elapsed, n), = _logged(lines, r"window ([\d.]+)s passes=(\d+)")
    (tets,), = _logged(lines, r"mesh vertices=\d+ tets=(\d+)")
    (setup_s,), = _logged(lines, r"setup_s ([\d.]+)")
    (traced_s, reduce_s), = _logged(
        lines, r"traced pass ([\d.]+)s, with the trace's reduction "
               r"([\d.]+)s")
    dev = result["device"]
    out = {"correct": result["correct"],
           "end_to_end": {"tets_per_s": harness.rate(int(tets), int(n),
                                                     elapsed),
                          "setup_s": setup_s,
                          "peak_hbm_gb": dev["memory_peak_bytes"] / 1e9},
           "pass_s_median": statistics.median(passes),
           "traced_pass_s": traced_s, "reduction_s": reduce_s}
    if split is None:
        return out
    idle = dev["window_s"] - dev["busy_s"]
    out.update(
        window_s=split.window_s, idle_s=idle,
        idle_by_layer=split.idle_by_layer,
        idle_untraced_s=split.idle_untraced_s,
        identity_error_s=(sum(split.idle_by_layer.values())
                          + split.idle_untraced_s - idle),
        idle_by_span=xplane.top(split.idle_by_span, 12),
        self_s=xplane.top(split.self_s, 30), count=split.count,
        spans=sum(split.count.values()))
    return out


def span_cost(root: str, n: int = 200_000) -> dict:
    """Seconds per ``with`` of a program span (a ``TraceAnnotation``, as
    ``src/repro/core/spans.py`` makes one) outside a profiler session, with
    and without an argument, and inside one."""
    import jax

    ann = jax.profiler.TraceAnnotation

    def per(make, k):
        t0 = time.perf_counter()
        for _ in range(k):
            with make():
                pass
        return (time.perf_counter() - t0) / k

    out = {"inactive_s": per(lambda: ann("engine.sync"), n),
           "inactive_arg_s": per(lambda: ann("engine.sync", relation="VT"),
                                 n)}
    d = os.path.join(root, harness.TRACE_DIR)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out["active_arg_s"] = per(
            lambda: ann("engine.sync", relation="VT"), n // 10)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    try:
        result, split, lines = run(
            harness.ROOT, args.workload, args.seed, args.seconds, T_START,
            log=lambda msg: print(msg, flush=True))
    except (harness.NoChip, roofline.UnknownDevice) as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 1
    out = summary(result, split, lines)
    out["span_cost"] = span_cost(harness.ROOT)
    print("layers " + json.dumps(out), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
