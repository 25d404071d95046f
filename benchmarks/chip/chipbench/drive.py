"""The general generator: a traffic mix (``traffic/<name>.json``) turned
into passes over the program.

A pass is a fresh ``RelationEngine`` followed by the mix's steps, each one
analysis driver called on that engine, ending in ``block_until_ready``.
Inputs the mix names under ``inputs`` (a gradient for Morse-Smale) are
computed once in set-up, each by a driver on an engine of its own. A step's
answer is kept under the driver's output name, so a later step of the same
pass reads it in place of an input (a pipeline). The relations a pass
produces follow from its drivers; every engine is built and driven with the
settings below. The rows the drivers' cross-segment completions return in a
pass are kept with it for the checks. The program is imported here and
nowhere else in the benchmark but the checks' readback.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import time
from typing import Dict, List, Tuple

import numpy as np

# driver name -> (output name, input names it reads besides (ds, pre),
# engine relations it reads)
DRIVERS = {
    "critical_points": ("types", ("rank",), ("VV", "VT")),
    "discrete_gradient": ("grad", ("rank",), ("VE", "VF", "VT")),
    "morse_smale": ("ms", ("grad",), ("TT", "FT")),
}
ENGINE = {"backend": "xla", "lookahead": 8, "dev_pool_segments": 4096}
CONSUMER = "device"
WORKERS = 1
# the driver modules whose cross-segment completions a pass keeps
COMPLETING = ("repro.algorithms.critical_points",
              "repro.algorithms.discrete_gradient",
              "repro.algorithms.morse_smale")


def _driver(name: str):
    if name == "critical_points":
        from repro.algorithms.critical_points import critical_points
        return lambda ds, pre, rank, **kw: critical_points(
            ds, pre, rank, **kw)[0]
    if name == "discrete_gradient":
        from repro.algorithms.discrete_gradient import discrete_gradient
        return discrete_gradient
    if name == "morse_smale":
        from repro.algorithms.morse_smale import morse_smale
        return morse_smale
    raise KeyError(f"no driver {name!r}; known: {sorted(DRIVERS)}")


def relations(steps) -> Tuple[str, ...]:
    """The engine relations ``steps`` read, with their co-prefetches."""
    out: List[str] = []
    for step in steps:
        for r in (DRIVERS[step["driver"]][2]
                  + tuple(step.get("args", {}).get("co_prefetch", ()))):
            if r not in out:
                out.append(r)
    return tuple(out)


def _kwargs(step: dict) -> dict:
    kw = dict(step.get("args", {}))
    if "co_prefetch" in kw:
        kw["co_prefetch"] = tuple(kw["co_prefetch"])
    kw["consumer"] = CONSUMER
    kw["workers"] = WORKERS
    return kw


@contextlib.contextmanager
def _keeping_completions(sink: list):
    """While open, every ``complete_adjacency`` call of a driver appends
    ``(relation, ids, (M, L))`` to ``sink``, the rows as it returned them."""
    mods = [importlib.import_module(m) for m in COMPLETING]
    saved = [(m, m.complete_adjacency) for m in mods]

    def keeping(fn):
        def complete_adjacency(eng, relation, ids, *a, **kw):
            out = fn(eng, relation, ids, *a, **kw)
            sink.append((relation, np.asarray(ids), out))
            return out
        return complete_adjacency
    for m, fn in saved:
        m.complete_adjacency = keeping(fn)
    try:
        yield
    finally:
        for m, fn in saved:
            m.complete_adjacency = fn


class Program:
    """The program under test, set up for one mesh: segmented,
    preconditioned, with the traffic's inputs computed."""

    def __init__(self, raw, rank_raw: np.ndarray, config: dict,
                 traffic: dict, log=lambda msg: None):
        from repro.core.mesh import TetMesh, segment_mesh
        from repro.core.segtables import precondition

        self.traffic = traffic
        inputs = list(traffic.get("inputs", {}).values())
        self.pass_relations = relations(traffic["steps"])
        self.relations = relations(inputs + traffic["steps"])
        t0 = time.perf_counter()
        self.sm = segment_mesh(TetMesh(raw.points, raw.tets, raw.scalars),
                               capacity=int(config["capacity"]))
        self.pre = precondition(self.sm, relations=self.relations)
        log(f"segment_mesh + precondition {time.perf_counter() - t0:.6f}s")
        self.raw_of_prog_v = raw.raw_ids(self.sm.points)
        self.state: Dict[str, object] = {
            "rank": np.asarray(rank_raw)[self.raw_of_prog_v]}
        for name, spec in traffic.get("inputs", {}).items():
            out = self.run_pass([spec])
            self.state[name] = out.answers[DRIVERS[spec["driver"]][0]]
            out.release()
            log(f"input {name} {out.seconds:.6f}s")

    def engine(self):
        from repro.core.engine import RelationEngine
        return RelationEngine(self.pre, self.relations, **ENGINE)

    def run_pass(self, steps=None, annotate=None) -> "Pass":
        """One pass: a fresh engine, then each step. ``annotate(name)``
        gives a context manager wrapped around each step (the traced run's
        spans)."""
        import contextlib

        import jax

        steps = self.traffic["steps"] if steps is None else steps
        annotate = annotate or (lambda name: contextlib.nullcontext())
        completed: list = []
        t0 = time.perf_counter()
        eng = self.engine()
        state = dict(self.state)
        answers = {}
        with _keeping_completions(completed):
            for step in steps:
                out_name, needs, _ = DRIVERS[step["driver"]]
                fn = _driver(step["driver"])
                with annotate(f"chipbench.step.{step['driver']}"):
                    out = fn(eng, self.pre, *(state[n] for n in needs),
                             **_kwargs(step))
                    out = jax.block_until_ready(out)
                state[out_name] = answers[out_name] = out
        return Pass(eng, answers, time.perf_counter() - t0, completed)


class Pass:
    """One pass's engine, answers, seconds and the rows its drivers'
    completions returned (``completed``)."""

    def __init__(self, eng, answers: dict, seconds: float,
                 completed: list = ()):
        self.eng = eng
        self.answers = answers
        self.seconds = seconds
        self.completed = list(completed)
        self.stats = eng.stats.as_dict()

    def release(self) -> None:
        """Drop the engine and the completed rows and collect them, so
        their device memory is freed before the next pass builds one."""
        self.eng = None
        self.completed = []
        gc.collect()


def window(program: Program, seconds: float) -> Tuple[List[Pass], float]:
    """Passes back to back, a closed loop with one client, until
    ``seconds`` have passed; the last pass is not cut. Returns the passes
    and the seconds from the window's start to the end of the last pass.
    Every pass but the last is released as soon as it ends; the last keeps
    its engine for the checks."""
    passes: List[Pass] = []
    t0 = time.perf_counter()
    while True:
        if passes:
            passes[-1].release()
        passes.append(program.run_pass())
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return passes, elapsed
