"""Roofline term extraction from compiled HLO (the dry-run 'profile').

XLA's ``cost_analysis()`` visits each ``while`` body once, so scanned layer
stacks are undercounted by the trip count. This module parses the optimized
per-device HLO text with **loop awareness**: it recovers trip counts from the
scan-generated loop conditions (``compare(counter, constant(N)), LT``),
recurses through fusions/calls, and accumulates

  - matmul FLOPs (``dot`` ops: 2 · |result| · K),
  - HBM traffic estimate (operand + result bytes of top-level ops),
  - collective bytes moved per device, with ring factors per primitive.

Hardware model: :data:`PEAKS`, one row of published per-chip peaks per
``jax`` ``device_kind``, each with its source. A device without a row is an
error (:func:`peaks`), never a default.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    bf16_flops: float        # FLOP/s
    hbm_bytes_s: float       # HBM bandwidth, bytes/s
    ici_link_bytes_s: float  # inter-chip interconnect per link, bytes/s
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of ICI per chip over its 4 links = 50 GB/s per link
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_s=819e9, ici_link_bytes_s=50e9,
        source="Google Cloud documentation, 'TPU v5e' "
               "(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s "
               "bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, "
               "1,600 Gbit/s ICI"),
}

# the chip the dry-run's production pods are built from
DRYRUN_DEVICE_KIND = "TPU v5 lite"


class UnknownDeviceError(KeyError):
    """No published peaks are recorded for this ``device_kind``."""


def peaks(device_kind: str) -> ChipPeaks:
    """The :data:`PEAKS` row for ``device_kind``; raises
    :class:`UnknownDeviceError` for a device without one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to repro.launch.roofline.PEAKS") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([a-z][\w\-]*)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


@dataclasses.dataclass
class OpInfo:
    name: str
    kind: str
    result_type: str
    args: str          # remainder of the line after the '('
    line: str


def parse_computations(hlo: str):
    """-> (computations: name -> [OpInfo], types: op name -> result type).

    Newer HLO dumps omit operand types inside op argument lists, so a global
    symbol table resolves operand shapes for dot-FLOP accounting."""
    comps: Dict[str, List[OpInfo]] = {}
    types: Dict[str, str] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        mc = _COMP_RE.match(line.strip()) if "{" in line and "->" in line \
            else None
        if mc and not line.startswith("  "):
            cur = mc.group(1)
            comps[cur] = []
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        mo = _OP_RE.match(line)
        if mo:
            op = OpInfo(name=mo.group(1), result_type=mo.group(2),
                        kind=mo.group(3), args=mo.group(4), line=line)
            comps[cur].append(op)
            types[op.name] = op.result_type
    return comps, types


_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _operand_names(args: str) -> List[str]:
    # operands appear before attribute clauses; cut at '),'
    head = args.split("),")[0]
    return _OPERAND_RE.findall(head)


def _attr(line: str, key: str) -> Optional[str]:
    m = re.search(key + r"=\{([^}]*)\}", line)
    return m.group(1) if m else None


def _dot_flops(op: OpInfo, types: Dict[str, str]) -> int:
    # result elems x 2 x contraction size (from lhs dims + contracting dims)
    res = _shape_elems(op.result_type)
    lhs_type = None
    m = _SHAPE_RE.search(op.args)          # old dumps: inline operand types
    if m:
        lhs_type = m.group(0)
    else:
        names = _operand_names(op.args)
        if names:
            lhs_type = types.get(names[0])
    if not lhs_type:
        return 2 * res  # conservative fallback
    sm = _SHAPE_RE.search(lhs_type)
    lhs_dims = [int(d) for d in sm.group(2).split(",") if d] if sm else []
    cd = _attr(op.line, "lhs_contracting_dims")
    k = 1
    if cd and lhs_dims:
        for i in cd.split(","):
            i = i.strip()
            if i:
                k *= lhs_dims[int(i)]
    return 2 * res * k


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _collective_bytes(op: OpInfo, n_devices: int) -> float:
    """Per-device bytes moved over ICI for one execution of the op."""
    size = _shape_bytes(op.result_type)
    if "clone_promoted" in op.line:
        # XLA:CPU's AllReducePromotion widens bf16 all-reduces to f32; a TPU
        # build reduces natively in bf16 — count the semantic payload.
        size //= 2
    elif ("f32[" in op.result_type and "convert" in op.args
          and not op.kind.startswith("all-reduce")):
        # same CPU re-widening for gathers/permutes of bf16 values (operand
        # is a convert fusion): TPU moves these in bf16.
        size //= 2
    n = _group_size(op.line, n_devices)
    if n <= 1:
        return 0.0
    if op.kind.startswith("all-reduce"):
        return 2.0 * size * (n - 1) / n
    if op.kind.startswith("all-gather"):
        return size * (n - 1) / n          # result is the gathered size
    if op.kind.startswith("reduce-scatter"):
        return size * (n - 1)              # result is the scattered shard
    if op.kind.startswith("all-to-all"):
        return size * (n - 1) / n
    if op.kind.startswith("collective-permute"):
        return float(size)
    return 0.0


def _while_trip_count(cond_ops: List[OpInfo]) -> int:
    const = None
    for op in cond_ops:
        m = re.search(r"constant\((\d+)\)", op.line)
        if m and "s32" in op.result_type:
            const = int(m.group(1))
    for op in cond_ops:
        if op.kind == "compare" and "direction=LT" in op.line and const:
            return const
    return const or 1


@dataclasses.dataclass
class HloCosts:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    collective_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    unknown_while: int = 0

    def add(self, other: "HloCosts", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.ici_bytes += other.ici_bytes * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = (self.collective_counts.get(k, 0)
                                         + v * int(mult))
        self.unknown_while += other.unknown_while


_CALLED_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")


def analyze(hlo: str, n_devices: int) -> HloCosts:
    comps, types = parse_computations(hlo)
    memo: Dict[str, HloCosts] = {}

    # HBM accounting (v2): each materializing op's RESULT is counted once as
    # written + once as later read (2x result bytes). Operands are NOT
    # separately counted — their producers were counted when they wrote —
    # which avoids the 3-4x double counting of a per-edge model. Fusion
    # internals (elementwise) are assumed register/VMEM-resident on the TPU
    # target; fusions contribute their result like any producer.
    _MATERIALIZING = ("dot", "fusion", "copy", "transpose", "sort",
                      "scatter", "gather", "dynamic-update-slice",
                      "dynamic-slice", "reduce", "concatenate",
                      "convolution", "custom-call")

    def comp_cost(name: str, in_fusion: bool = False) -> HloCosts:
        key = (name, in_fusion)
        if key in memo:
            return memo[key]
        memo[key] = HloCosts()  # break cycles defensively
        total = HloCosts()
        for op in comps.get(name, []):
            if op.kind == "dot":
                total.flops += _dot_flops(op, types)
            elif any(op.kind.startswith(c) for c in COLLECTIVES):
                if op.kind.endswith("-done"):
                    continue
                total.ici_bytes += _collective_bytes(op, n_devices)
                base = op.kind.replace("-start", "")
                total.collective_counts[base] = \
                    total.collective_counts.get(base, 0) + 1
                total.hbm_bytes += 2 * _shape_bytes(op.result_type)
            elif op.kind == "fusion" or op.kind == "call":
                m = _CALLED_RE.search(op.line)
                if m:
                    total.add(comp_cost(m.group(1), in_fusion=True))
            elif op.kind == "while":
                mb = _CALLED_RE.search(op.line)
                mcnd = _COND_RE.search(op.line)
                trip = 1
                if mcnd and mcnd.group(1) in comps:
                    trip = _while_trip_count(comps[mcnd.group(1)])
                body = comp_cost(mb.group(1)) if mb and mb.group(1) in comps \
                    else HloCosts()
                total.add(body, mult=trip)
                if trip == 1:
                    total.unknown_while += 1
            elif op.kind == "convolution":
                total.flops += 2 * _shape_elems(op.result_type)
            # fusion-internal ops stay in registers/VMEM on the TPU target
            if not in_fusion and op.kind in _MATERIALIZING:
                if op.kind == "dynamic-update-slice":
                    # in-place when aliased: traffic = the update slice
                    names = _operand_names(op.args)
                    upd = types.get(names[1], "") if len(names) > 1 else ""
                    total.hbm_bytes += 2 * _shape_bytes(upd)
                elif op.kind == "scatter":
                    # scatter(operand, indices, updates): in-place when
                    # aliased — traffic = indices + updates
                    names = _operand_names(op.args)
                    upd = "".join(types.get(n, "") for n in names[1:3])
                    total.hbm_bytes += 2 * _shape_bytes(upd)
                else:
                    total.hbm_bytes += 2 * _shape_bytes(op.result_type)
        memo[key] = total
        return total

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
            break
    if entry is None:
        # fall back: computation named main-ish
        entry = next((n for n in comps if "main" in n), None)
    return comp_cost(entry) if entry else HloCosts()


def roofline_terms(costs: HloCosts, device_kind: str) -> Dict[str, float]:
    p = peaks(device_kind)
    tc = costs.flops / p.bf16_flops
    tm = costs.hbm_bytes / p.hbm_bytes_s
    tx = costs.ici_bytes / p.ici_link_bytes_s
    dom = max((tc, "compute"), (tm, "memory"), (tx, "collective"))[1]
    total = max(tc, tm, tx)
    return {
        "t_compute_s": tc, "t_memory_s": tm, "t_collective_s": tx,
        "bottleneck": dom,
        "roofline_fraction": tc / total if total > 0 else 0.0,
    }


def kernel_roofline(flops: float, hbm_bytes: float, device_kind: str,
                    ici_bytes: float = 0.0) -> Dict[str, float]:
    """Roofline terms for a single relation kernel launch, from analytic
    (not HLO-parsed) cost estimates. This is the scoring function behind
    ``launch/autotune.py``'s candidate ranking: the autotuner does not need
    HLO text, only the launch's flop/byte volumes implied by a candidate
    (block, batch) configuration."""
    return roofline_terms(HloCosts(flops=float(flops),
                                   hbm_bytes=float(hbm_bytes),
                                   ici_bytes=float(ici_bytes)), device_kind)


def model_flops(cfg, shape) -> float:
    """Per-device MODEL_FLOPS: 6·N·D train, 2·N·D inference (active params
    for MoE), D = tokens processed per device per step."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n * tokens
