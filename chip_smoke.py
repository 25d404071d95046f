#!/usr/bin/env python3
"""Chip smoke: the GALE main path end to end on a TPU, checked bit for bit.

Builds a seeded ``structured_grid`` tet mesh, segments and preconditions it at
capacity 64, then runs the four analysis drivers (critical points, discrete
gradient, Morse-Smale, persistence) on one ``RelationEngine`` (xla backend,
device consumer arm) and checks every result against the same drivers on
``ExplicitTriangulation``, plus the Morse-Euler identity. Any mismatch, any
fault-recovery counter above zero, a set ``REPRO_FAULT_SPEC`` or a missing TPU
exits non-zero without the final ``ok`` line.

  python chip_smoke.py              # one chip, 100^3 grid (1 M vertices)
  python chip_smoke.py --chips 4    # sharded engine: shards=4 vs shards=1
  python chip_smoke.py --grid 63 63 63 --warm   # smaller mesh, + warm pass

Earlier lines report the device, mesh and segment counts, set-up time, per
driver phase the cold time with its compile count and seconds (and, with
``--warm``, the time on a fresh engine with every jit compiled), the
engine's counters and peak device memory. The last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process; it starts no child.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RELS = ("VV", "VE", "VF", "VT", "FT", "TT")
# EngineStats counters that must stay zero: any of them means the run
# recovered from a fault instead of running the device path throughout
FAULT_COUNTERS = ("retries", "sync_timeouts", "failed_launches",
                  "failed_segments", "breaker_trips", "degraded_launches",
                  "degraded_segments", "degraded_reads", "shards_lost",
                  "rehomed_segments")
GRID_1CHIP = (100, 100, 100)
GRID_4CHIP = (400, 50, 50)


class SmokeFailure(RuntimeError):
    """A check of the smoke failed; the message says which."""


def _log(msg: str) -> None:
    print(msg, flush=True)


def _digest(obj) -> str:
    """sha1 over a result dataclass's array fields, in field order."""
    import numpy as np
    h = hashlib.sha1()
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if isinstance(v, np.ndarray):
            h.update(f.name.encode())
            h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def build(grid, seed: int):
    """Seeded mesh -> (segmented mesh, preconditioned tables, rank, chi)."""
    from repro.algorithms import fields
    from repro.algorithms.critical_points import total_order
    from repro.core.mesh import segment_mesh
    from repro.core.segtables import precondition
    from repro.data.meshgen import structured_grid

    span = float(max(grid))
    mesh = structured_grid(*grid, scalar_fn=fields.gaussians(
        seed, k=8, sigma=span / 8, scale=span))
    sm = segment_mesh(mesh, capacity=64)
    pre = precondition(sm, relations=list(RELS))
    rank = total_order(sm.scalars)
    chi = sm.n_vertices - pre.n_edges + pre.n_faces - sm.n_tets
    return sm, pre, rank, chi


class CompileMeter:
    """Counts XLA backend compiles and their seconds while installed (a
    ``with`` block), through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def run_drivers(ds, pre, rank, consumer: str, label: str, log) -> dict:
    """The four drivers in ``examples/analyze_mesh.py``'s order. Each phase
    ends in ``block_until_ready`` and logs its wall time and the compiles
    it paid as soon as it finishes: their count and their seconds summed
    (a sharded engine compiles a kernel for its devices concurrently, so
    the sum can exceed the wall time)."""
    import jax
    import numpy as np
    from repro.algorithms.critical_points import critical_points
    from repro.algorithms.discrete_gradient import discrete_gradient
    from repro.algorithms.morse_smale import morse_smale
    from repro.algorithms.persistence import persistence_pairs

    def phase(name, fn):
        with CompileMeter() as meter:
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
        log(f"[{label}] {name} {dt:.3f}s compiles={meter.n} "
            f"compile_s={meter.seconds:.3f}")
        return out

    types, cp = phase("critical_points", lambda: critical_points(
        ds, pre, rank, batch_segments=16, consumer=consumer))
    g = phase("discrete_gradient", lambda: discrete_gradient(
        ds, pre, rank, batch_segments=16, co_prefetch=("TT",),
        consumer=consumer))
    ms = phase("morse_smale", lambda: morse_smale(
        ds, pre, g, consumer=consumer))
    diag = phase("persistence", lambda: persistence_pairs(
        ds, pre, rank, grad=g, consumer=consumer))
    return {
        "cp_types": hashlib.sha1(
            np.ascontiguousarray(np.asarray(types)).tobytes()).hexdigest(),
        "cp_counts": dict(cp),
        "gradient_counts": g.counts(),
        "gradient_digest": _digest(g),
        "euler": g.euler(),
        "ms_counts": ms.counts(),
        "ms_digest": _digest(ms),
        "persistence_digest": diag.digest(),
    }


def compare(got: dict, want: dict, what: str) -> None:
    bad = [k for k in want if got[k] != want[k]]
    if bad:
        raise SmokeFailure(
            f"{what}: " + "; ".join(f"{k}: {got[k]} != {want[k]}"
                                   for k in bad))


def check_faults(eng, what: str) -> None:
    s = eng.stats
    fired = {k: getattr(s, k) for k in FAULT_COUNTERS if getattr(s, k)}
    if fired:
        raise SmokeFailure(f"{what}: fault recovery fired: {fired}")


def _engine_line(eng) -> str:
    s = eng.stats
    return (f"launches={s.kernel_launches} "
            f"segments_produced={s.segments_produced} "
            f"requests={s.requests} devpool_hits={s.devpool_hits} "
            f"devpool_uploads={s.devpool_uploads} "
            f"completion_queries={s.completion_queries} "
            f"t_sync={s.t_sync:.3f}s t_kernel={s.t_kernel:.3f}s")


def check_main_path(grid=GRID_1CHIP, seed: int = 0, warm: bool = False,
                    log=_log) -> dict:
    """Engine (xla, device consumer) vs ``ExplicitTriangulation`` on one
    seeded mesh. Raises :class:`SmokeFailure` on any mismatch or fired
    fault counter; returns the reference results.

    The engine pass is cold: each phase pays its own compiles, and its line
    gives their count and seconds. ``warm=True`` adds a second pass on
    a fresh engine with every jit already compiled — at the 100^3 default
    that pass does not fit the smoke's 1,200 s budget, so it is off."""
    from repro.core.engine import RelationEngine
    from repro.core.explicit import ExplicitTriangulation

    t0 = time.perf_counter()
    sm, pre, rank, chi = build(grid, seed)
    log(f"mesh grid={grid} seed={seed} vertices={sm.n_vertices} "
        f"edges={pre.n_edges} faces={pre.n_faces} tets={sm.n_tets} "
        f"segments={sm.n_segments} chi={chi} "
        f"setup={time.perf_counter() - t0:.3f}s")

    def engine():
        return RelationEngine(pre, list(RELS), lookahead=8,
                              dev_pool_segments=4096)

    # the warm pass is a fresh engine (every block produced again) in a
    # process whose jit caches are warm; one engine is alive at a time
    results = {}
    for label in ("engine cold", "engine warm")[:2 if warm else 1]:
        eng = engine()
        results[label] = run_drivers(eng, pre, rank, "device", label, log)
        log(f"[{label}] {_engine_line(eng)}")
        check_faults(eng, label)
        del eng

    t0 = time.perf_counter()
    ref = ExplicitTriangulation(pre, list(RELS))
    log(f"[explicit] construct {time.perf_counter() - t0:.3f}s")
    want = run_drivers(ref, pre, rank, "device", "explicit", log)
    log(f"reference critical={want['cp_counts']} "
        f"gradient={want['gradient_counts']} ms={want['ms_counts']} "
        f"persistence={want['persistence_digest'][:12]}")
    if want["euler"] != chi:
        raise SmokeFailure(
            f"Morse-Euler identity: reference {want['euler']} != chi {chi}")
    for label, got in results.items():
        compare(got, want, f"{label} vs explicit")
        log(f"[{label}] bit-identical to explicit: " + ", ".join(want))
    return want


def check_sharded(grid=GRID_4CHIP, seed: int = 0, shards: int = 4,
                  log=_log) -> dict:
    """``shards``-way engine vs the unsharded engine on one bar mesh whose
    shard walls force cross-shard completion. Also checks that each shard's
    pooled blocks live on its own device and that the completion exchange
    ran as a ``psum`` whenever the shards sit on distinct devices."""
    import jax
    from repro.core.engine import RelationEngine

    t0 = time.perf_counter()
    sm, pre, rank, chi = build(grid, seed)
    log(f"mesh grid={grid} seed={seed} vertices={sm.n_vertices} "
        f"tets={sm.n_tets} segments={sm.n_segments} chi={chi} "
        f"setup={time.perf_counter() - t0:.3f}s")
    results = {}
    for k in (1, shards):
        eng = RelationEngine(pre, list(RELS), lookahead=8,
                             dev_pool_segments=4096, shards=k)
        results[k] = run_drivers(eng, pre, rank, "device", f"shards={k}",
                                 log)
        log(f"[shards={k}] {_engine_line(eng)}")
        check_faults(eng, f"shards={k}")
        if results[k]["euler"] != chi:
            raise SmokeFailure(f"shards={k}: Morse-Euler identity violated")
        if k == 1:
            del eng
            continue
        plan = eng.shard_plan
        per = {i: st.segments_produced
               for i, st in sorted(eng.shard_stats.items())}
        log(f"[shards={k}] devices={[d.id for d in plan.devices]} "
            f"segments_produced per shard={per} "
            f"exchange psum={eng.stats.exchange_psum} "
            f"stack={eng.stats.exchange_stack}")
        if len(jax.devices()) >= k and not plan.multi_device:
            raise SmokeFailure(f"shards={k} did not get one device each: "
                               f"{[d.id for d in plan.devices]}")
        if plan.multi_device:
            for i, occ in enumerate(eng.store.shard_occupancy()):
                want_dev = {plan.devices[i].id}
                if occ["entries"] and set(occ["device_ids"]) != want_dev:
                    raise SmokeFailure(
                        f"shard {i} pools blocks on devices "
                        f"{occ['device_ids']}, not {sorted(want_dev)}")
            if not eng.stats.exchange_psum or eng.stats.exchange_stack:
                raise SmokeFailure(
                    f"cross-shard exchange: psum={eng.stats.exchange_psum} "
                    f"stack={eng.stats.exchange_stack}; expected psum only")
        for d in jax.devices()[:k]:
            log(f"[shards={k}] device {d.id} peak_bytes_in_use="
                f"{_peak_bytes(d)}")
    compare(results[shards], results[1], f"shards={shards} vs shards=1")
    log(f"[shards={shards}] bit-identical to shards=1: "
        + ", ".join(results[1]))
    return results[1]


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-engine phase")
    ap.add_argument("--grid", type=int, nargs=3, metavar=("NX", "NY", "NZ"),
                    help="grid vertices per axis (default "
                         f"{GRID_1CHIP}, or {GRID_4CHIP} with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warm", action="store_true",
                    help="add a warm pass on a fresh engine (one chip)")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FAULT_SPEC"):
        print("REPRO_FAULT_SPEC is set: the smoke runs fault-free only",
              file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()
    dev = devices[0]
    _log(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.launch import compile_cache
    _log(f"compilation cache: {compile_cache.enable()}")
    try:
        if args.chips == 4:
            check_sharded(tuple(args.grid or GRID_4CHIP), args.seed,
                          shards=4)
        else:
            check_main_path(tuple(args.grid or GRID_1CHIP), args.seed,
                            warm=args.warm)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    _log(f"peak_bytes_in_use device {dev.id}: {_peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
