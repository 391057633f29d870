#!/usr/bin/env python3
"""Readings that the limits of a cell's ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        [--control 3] [--faults 3] [--seconds 5]

For a cell at its own size, in one process on the chip:

* sound runs: on every seed the program's numbers against the reference
  (the lower readings: the largest of them);
* the control: on the first ``--control`` seeds, the reference computed
  at HIGH precision (three bf16 passes, the step below the float32 at
  HIGHEST that the configurations state) put in the program's place;
* faults: on the first ``--faults`` seeds, the program with each fault
  of ``bench/faults.py`` that the cell can have planted in its timed
  path.

A training cell needs no window; a serving cell runs a window of
``--seconds`` at its own load. Each reading is a JSON line on standard
output; the last line sums them up per number: the lower reading, the
control's smallest, and each fault's smallest.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)

CELL_FAULTS = {"train": ("unchanged_step", "half_batch"),
               "serve": ("half_batch", "altered_answer")}


def _log(msg: str):
    print(f"[{time.perf_counter() - T0:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


class _Runner:
    """Program and reference readings of one cell, by kind of driver."""

    def __init__(self, cell, seconds: float):
        from bench import harness
        self.cell, self.seconds = cell, seconds
        self.kind = cell.traffic["kind"]
        self.drv = harness.driver(cell)

    def program(self, seed: int):
        """Set-up (and a window for serving) of the program: its state."""
        st = self.drv.prepare(self.cell, seed, self.seconds, _log)
        if self.kind == "serve":
            self.drv.window(st, self.seconds, _log)
        self.drv.release(st)
        return st

    def reference(self, st, prec):
        if self.kind == "serve":
            idx = self.drv.sample(st)
            return idx, self.drv.reference_ivectors(st, idx, prec)
        return self.drv.reference_readings(st, prec, _log)

    def numbers(self, st, ref):
        if self.kind == "serve":
            return self.drv.numbers(st, *ref)
        return self.drv.numbers(st.prog, ref, _log)

    def control(self, st, ref, ref_low):
        """The lower-precision reference in the program's place."""
        if self.kind == "serve":
            idx, iv = ref
            st.ivecs = {int(i): v for i, v in zip(idx, ref_low[1])}
            return self.drv.numbers(st, idx, iv)
        return self.drv.numbers(ref_low, ref, _log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the sound runs")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from bench import faults as FL
    from bench import harness
    from bench import reference as REF
    jax.config.update("jax_compilation_cache_dir",
                      harness.compile_cache_dir(ROOT))
    device = harness.device_info(1)
    cell = harness.load_cell(ROOT, args.workload)
    run = _Runner(cell, args.seconds)
    seeds = [int(s) for s in args.seeds.split(",")]
    _log(f"device {device}; cell {cell.name}; seeds {seeds}")
    out = {"sound": [], "control": [], "faults": {}}

    def emit(kind, seed, nums):
        print(json.dumps({"cell": cell.name, "kind": kind, "seed": seed,
                          "numbers": nums}), flush=True)

    for i, seed in enumerate(seeds):
        st = run.program(seed)
        ref = run.reference(st, REF.HIGHEST)
        nums = run.numbers(st, ref)
        out["sound"].append(nums)
        emit("sound", seed, nums)
        if i < args.control:
            low = run.reference(st, REF.HIGH)
            nums = run.control(st, ref, low)
            out["control"].append(nums)
            emit("control", seed, nums)
        if i < args.faults:
            for f in CELL_FAULTS[run.kind]:
                with FL.planted(f):
                    fst = run.program(seed)
                nums = run.numbers(fst, ref)
                out["faults"].setdefault(f, []).append(nums)
                emit(f"fault:{f}", seed, nums)
                del fst
        del st
        gc.collect()
    keys = list(out["sound"][0])
    summary = {k: {"lower": max(n[k] for n in out["sound"]),
                   "control": (min(n[k] for n in out["control"])
                               if out["control"] else None),
                   **{f"fault:{f}": min(n[k] for n in v)
                      for f, v in out["faults"].items()}}
               for k in keys}
    print(json.dumps({"cell": cell.name, "summary": summary,
                      "seeds": seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
