#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work and prints no result. See ``bench/harness.py``.
"""
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
# the checkout's root, in place of this script's directory
sys.path[0] = str(Path(__file__).resolve().parent.parent)

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t0=T0))
