#!/usr/bin/env python3
"""Run the control of the comparison that decides ``correct``.

  python3 benchmarks/chip/control.py --workload box.cp --seeds 11 12 13

prints one JSON line per seed: the readings of every number compared, with
the cell's pass run on the order of the field rounded to bfloat16 and the
reference on the float32 order (see ``chipbench/control.py``). It runs on
whatever device JAX finds; the benchmark's own runs never run it.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench.control import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
