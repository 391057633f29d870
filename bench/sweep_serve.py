#!/usr/bin/env python3
"""The knee of a serving cell: the highest open-loop rate it sustains.

    python3 bench/sweep_serve.py --workload serve.vox-d72-r400.open \
        --seed 7 --rates 60,100,140,180 --seconds 10

One process, one set-up: for each rate, in the order given, the cell's
mix at that rate runs a window of ``--seconds`` through the same server
loop as the benchmark. Each rate prints one JSON line: latency p50 and
p95, the median latency of the requests due in the second and in the
last quarter of the window, the backlog when admission closed (requests
due and not yet served), and how late the generator ran (the lag from a
request's due time to its submission). A rate is sustained when every
request is served and the queue does not grow through the window: the
last quarter's median latency is at most 1.5 times the second's. The
knee is the highest rate sustained below the lowest rate that is not.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def _log(msg: str):
    print(f"[{time.perf_counter() - T0:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np
    from bench import harness
    from bench.drivers import serve
    jax.config.update("jax_compilation_cache_dir",
                      harness.compile_cache_dir(ROOT))
    device = harness.device_info(1)
    cell = harness.load_cell(ROOT, args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    _log(f"device {device}; cell {cell.name}; rates {rates}")
    cell.traffic = {**cell.traffic, "rate_per_s": max(rates)}
    state = serve.prepare(cell, args.seed, args.seconds, _log)
    which = state.which          # enough for the highest rate
    sustained = failed = None
    for rate in sorted(rates):
        traffic = {**cell.traffic, "rate_per_s": rate}
        state.due, state.lengths = serve.schedule(traffic, args.seed,
                                                  args.seconds)
        state.which = which[:len(state.due)]
        state.ivecs = {}
        t0 = time.perf_counter()
        lat, lag = serve._serve(state, state.due,
                                np.arange(len(state.due)))
        done = state.due + lat                      # offsets from start
        backlog = int(np.sum((done > args.seconds)
                             & (state.due <= args.seconds)))
        quarter = np.minimum((state.due / args.seconds * 4).astype(int), 3)
        q2 = float(np.median(lat[quarter == 1]))
        q4 = float(np.median(lat[quarter == 3]))
        ok = bool(len(state.ivecs) == len(lat) and q4 <= 1.5 * q2)
        if ok and failed is None:
            sustained = rate
        elif not ok and failed is None:
            failed = rate
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "served": len(state.ivecs),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "second_quarter_p50_ms": q2 * 1e3,
            "last_quarter_p50_ms": q4 * 1e3,
            "backlog_at_close": backlog,
            "generator_lag_p95_ms": float(np.percentile(lag, 95) * 1e3),
            "generator_lag_max_ms": float(lag.max() * 1e3),
            "wall_s": time.perf_counter() - t0, "sustained": ok}),
            flush=True)
    print(json.dumps({"cell": cell.name, "knee_per_s": sustained,
                      "rates": rates}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
