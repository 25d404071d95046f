#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

  python3 benchmarks/chip/run.py --workload box.cp --seed 7 --seconds 10 \
      --trace 0

Runs from the root of a checkout, in this one process, on the TPU it finds;
exits non-zero without a result where JAX finds no TPU or fewer chips than
the cell asks for. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``, each number compared with
its limit, which also end standard error.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
