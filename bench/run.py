#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for; without them it prints no result and exits 2.  See bench/harness/main.py.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
