#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, from the root of a checkout:

    python3 benchmarks/chip/run.py --workload eurlex-4k.serve-bsr \
        --seed 7 --seconds 51 --trace 0

Refuses (exit status other than 0, no result line) without a TPU, with
fewer chips than the cell asks for, on a device kind the peak table lacks,
or where the program is not importable. See harness.py for the layout.
"""

import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
