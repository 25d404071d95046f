"""Self-tuning kernel parameters for the relation engine (DESIGN.md §4).

The paper's Appendix A parameter study shows the right block/launch sizes
are mesh- and backend-dependent; instead of hard-coding ``_pick_block``
heuristics, this layer

  1. derives a small ranked set of candidate configurations from the
     roofline model (:func:`candidate_configs` — analytic byte/flop volumes
     scored through :func:`repro.launch.roofline.kernel_roofline`),
  2. lets ``benchmarks/bench_kernel_params.py`` measure them on the real
     engine (:func:`measure_engine`), and
  3. persists the winner per ``(device kind, backend, mesh-size bucket)``
     in a small on-disk JSON table that
     :class:`~repro.core.engine.RelationEngine` consults at construction
     (``tune="auto" | "off" | <path>``).

Config key: ``jax``'s ``device_kind`` comes first, so an entry measured on
one device (say the CPU) is never applied on another (a TPU). The mesh size
is bucketed to the next power of two (same bucketing as
``ops.bucket_rows``) so one tuned entry covers a range of meshes; the
backend is part of the key because the Pallas sparse-assembly kernels and
the fused xla oracle have different sweet spots. Lookup order inside the
engine: explicit constructor argument > tuned table entry > built-in
default. A missing table is no table; a table of another ``version`` is
ignored whole (its keys cannot apply); a table that cannot be read as one
raises :class:`TuneTableError` instead of silently configuring nothing.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from .roofline import kernel_roofline

TABLE_VERSION = 2
_DEFAULT_NAME = "TUNE_kernel_params.json"

# amortized per-launch dispatch overhead (host->device + jit call), the
# constant the batch dimension exists to hide; coarse but only used to RANK
# candidates before real measurement
_LAUNCH_OVERHEAD_S = 50e-6


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One tuned kernel-parameter point (engine constructor knobs)."""

    block_x: int = 256
    block_y: int = 256
    vv_block: Optional[int] = None
    batch_max: int = 64
    bucket_floor: int = 1

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KernelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        if kw.get("vv_block") is not None:
            kw["vv_block"] = int(kw["vv_block"])
        return cls(**kw)


def default_path() -> str:
    """Table location: ``$REPRO_TUNE_TABLE`` or ``TUNE_kernel_params.json``
    in the current working directory."""
    return os.environ.get("REPRO_TUNE_TABLE",
                          os.path.join(os.getcwd(), _DEFAULT_NAME))


class TuneTableError(ValueError):
    """The tuning table exists but is not a readable table."""


def bucket(n_segments: int) -> int:
    """Mesh-size bucket: next power of two >= n_segments (min 1)."""
    n = max(1, int(n_segments))
    return 1 << (n - 1).bit_length()


def table_key(device_kind: str, backend: str, n_segments: int) -> str:
    return f"{device_kind}/{backend}/{bucket(n_segments)}"


def load_table(path: Optional[str] = None) -> Dict[str, Dict]:
    """Load the tuning table's configs: ``{}`` when the file is missing or
    was written for another ``TABLE_VERSION``; :class:`TuneTableError` when
    it is not JSON or has no ``configs`` object."""
    path = path or default_path()
    try:
        with open(path) as f:
            text = f.read()
    except FileNotFoundError:
        return {}
    try:
        data = json.loads(text)
    except ValueError as e:
        raise TuneTableError(f"tuning table {path} is not JSON: {e}") from e
    if not isinstance(data, dict) or not isinstance(data.get("configs"),
                                                    dict):
        raise TuneTableError(f"tuning table {path} has no 'configs' object")
    if data.get("version") != TABLE_VERSION:
        return {}
    return data["configs"]


def save_table(configs: Dict[str, Dict], path: Optional[str] = None) -> str:
    path = path or default_path()
    with open(path, "w") as f:
        json.dump({"version": TABLE_VERSION, "configs": configs}, f,
                  indent=2, sort_keys=True)
        f.write("\n")
    return path


def lookup(backend: str, n_segments: int, device_kind: str,
           path: Optional[str] = None) -> Optional[KernelConfig]:
    """The engine-side read: tuned config for (device kind, backend, mesh
    bucket), or ``None`` when nothing is recorded for that key."""
    key = table_key(device_kind, backend, n_segments)
    entry = load_table(path).get(key)
    if entry is None:
        return None
    try:
        return KernelConfig.from_dict(entry)
    except (AttributeError, TypeError, ValueError) as e:
        raise TuneTableError(
            f"tuning table entry {key!r} is not a kernel config: {e}") from e


def record(backend: str, n_segments: int, config: KernelConfig,
           device_kind: str, path: Optional[str] = None,
           score_s: Optional[float] = None) -> str:
    """Persist a measured winner for (device kind, backend, mesh bucket)."""
    configs = load_table(path)
    entry = config.to_dict()
    if score_s is not None:
        entry["score_s"] = float(score_s)
    configs[table_key(device_kind, backend, n_segments)] = entry
    return save_table(configs, path)


def _predicted_launch_s(cfg: KernelConfig, n_segments: int,
                        rows_per_segment: int, arity: int,
                        deg: int, device_kind: str) -> float:
    """Analytic time per SEGMENT for one candidate: roofline memory/compute
    terms for a ``batch_max``-segment launch plus the launch overhead, both
    amortized over the batch. i32 tables in, (M, L) entry blocks out."""
    b = max(1, min(cfg.batch_max, n_segments))
    rows = rows_per_segment * b
    in_bytes = rows * arity * 4 * 2          # X and Y tables
    out_bytes = rows * (deg + 1) * 4         # M + L
    # sort-join assembly: ~O(rows log rows) compare-exchange flops
    flops = rows * arity * max(1, rows_per_segment.bit_length()) * 4.0
    terms = kernel_roofline(flops, in_bytes + out_bytes, device_kind)
    t_launch = max(terms["t_compute_s"], terms["t_memory_s"])
    # oversized blocks waste grid cover on small tables; fold a mild
    # utilization penalty so candidates differ on block shape too
    util = min(1.0, rows_per_segment / max(cfg.block_x, cfg.block_y))
    return (t_launch / max(util, 1 / 16) + _LAUNCH_OVERHEAD_S) / b


def candidate_configs(n_segments: int, device_kind: str,
                      rows_per_segment: int = 512,
                      arity: int = 4, deg: int = 32,
                      max_candidates: int = 8) -> List[KernelConfig]:
    """Roofline-ranked candidate configs for a mesh of ``n_segments``
    segments with ``rows_per_segment`` table rows each, scored with the
    peaks of ``device_kind`` (``roofline.peaks`` raises for a device with
    none). The returned list (best predicted first) is what the benchmark
    actually measures — the model prunes the sweep, the measurement picks
    the winner."""
    cands = []
    for bx in (128, 256, 512):
        for by in (128, 256, 512):
            for bm in (16, 32, 64, 128):
                for floor in (1, 4):
                    cands.append(KernelConfig(
                        block_x=bx, block_y=by,
                        vv_block=None if bx == by else min(bx, by),
                        batch_max=bm, bucket_floor=floor))
    cands.sort(key=lambda c: _predicted_launch_s(
        c, n_segments, rows_per_segment, arity, deg, device_kind))
    return cands[:max_candidates]


def measure_engine(make_engine: Callable[[KernelConfig], Any],
                   relations: Sequence[str], segments: Sequence[int],
                   config: KernelConfig, repeats: int = 3) -> float:
    """Wall-clock seconds for one cold-cache sweep of ``relations`` over
    ``segments`` on an engine built with ``config`` (best of ``repeats``,
    first warmup sweep excluded — it pays jit compilation).

    ``make_engine`` builds the engine from the candidate (the bench passes
    the constructor knobs through); cache state is reset between timed
    sweeps with the public :meth:`~repro.core.engine.RelationEngine.
    clear_cache`."""
    eng = make_engine(config)
    for r in relations:                      # warmup: compile every kernel
        for s in segments:
            eng.get(r, s)
    best = float("inf")
    for _ in range(max(1, repeats)):
        eng.clear_cache()
        t0 = time.perf_counter()
        for r in relations:
            for s in segments:
                eng.get(r, s)
        best = min(best, time.perf_counter() - t0)
    return best
