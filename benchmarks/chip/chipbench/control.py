"""The control of the comparison that decides ``correct``.

The configurations promise exact answers on the injective vertex order of
the float32 field. The control breaks that promise the way a later change
might be tempted to: the cell's pass runs on the order of the field rounded
to bfloat16 (ties then broken by vertex id), and its answers are compared
with the reference on the float32 order, exactly as a benchmark run compares
them. The control has to come out not correct.
"""

from __future__ import annotations

import argparse
import json

import ml_dtypes
import numpy as np

from . import drive, harness, meshgen


def readings(root: str, workload: str, seed: int) -> dict:
    """The numbers compared for one seed, with the pass run on the
    bfloat16 order and the reference on the float32 order."""
    cell = harness.load_cell(root, workload)
    raw = meshgen.generate(cell.config, seed, cell.bench_dir)
    rank = meshgen.injective_rank(raw.scalars)
    low = meshgen.injective_rank(
        raw.scalars.astype(ml_dtypes.bfloat16).astype(np.float32))
    program = drive.Program(raw, low, cell.config, cell.traffic)
    return harness.compare(program, raw, rank, [program.run_pass()], seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Readings of the control, one JSON line per seed.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": readings(harness.ROOT, args.workload,
                                               seed)}), flush=True)
    return 0
