"""One run of one benchmark cell.

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root, with no registry to edit when a cell is added:

* the cell (``workloads[]``) names a configuration and a traffic mix;
* the configuration's ``file`` holds its sizes;
* ``bench/traffic/<mix>.json`` holds the mix's parameters, and its
  ``kind`` names the driver ``bench/drivers/<kind>.py`` that runs it;
* ``bench/limits/<cell>.json`` holds the limits of the numbers that
  decide ``correct``, with the readings they were set from;
* each per-layer metric is read by ``bench/metrics/<metric>.py``;
* ``bench/peaks.json`` holds the chip's peaks by ``device_kind``.

A run: set-up (TPU start, inputs from the seed, warm-up, the driver's
first steps) until the first measured step, which ``setup_s`` times
from process start; then the window, measured with the profiler off,
or with ``--trace 1`` a short window under the profiler reduced to the
per-layer metrics; then the peak device memory is read, the program's
state is dropped and the reference decides ``correct``. The last line
of standard output is the result as one JSON object, and the last lines
of standard error the numbers compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits_file: Path
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, w["config"], w["traffic"], int(w["chips"]),
                json.loads((root / cfg["file"]).read_text()), traffic,
                root / "bench" / "limits" / f"{workload}.json", e2e,
                per_layer, root)


def driver(cell: Cell):
    """The module ``bench/drivers/<kind>.py`` of the cell's mix."""
    return importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py`` (names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(has {sorted(table['devices'])})")
    return table["devices"][kind]


def compile_cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``,
    a fixed path, so only a cell's first run in a checkout compiles."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks_ = [d.memory_stats().get("peak_bytes_in_use")
              for d in jax.devices() if d.memory_stats()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileClock:
    """Compilations, and the seconds JAX spent on them, from its
    monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


@dataclass
class Reading:
    """What a per-layer metric reads: the reduced trace, the driver's
    counters over the traced window, the cell's shapes and the peaks."""
    cell: Cell
    peaks: dict
    trace: object
    counters: dict
    shapes: dict


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    compared: Dict[str, dict]
    breakdown: Optional[dict] = None

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["compared"] = self.compared
        return json.dumps(out)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, peak: dict, t0: float,
             log: Callable[[str], None]) -> Result:
    """Set-up, window and check of one run (the chip is already found)."""
    import jax
    from bench import checks as CK
    from bench import trace as TR
    drv = driver(cell)
    clock = CompileClock()
    window_s = float(cell.traffic.get("trace_seconds", seconds)) if trace \
        else float(seconds)
    state = drv.prepare(cell, seed, window_s, log)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f}s ({clock.count} compiles, "
        f"{clock.seconds:.3f}s tracing and compiling)")
    before = clock.count
    metrics: Dict[str, dict] = {}
    breakdown = None
    dev = dict(device)
    if trace:
        with TR.capture() as cap:
            with jax.profiler.TraceAnnotation(TR.WINDOW):
                counters = drv.traced_window(state, log)
        tr = cap.trace
        reading = Reading(cell, peak, tr, counters, drv.shapes(state))
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if tr is not None:
            dev.update(busy_s=TR.busy_seconds(tr), window_s=tr.window_s)
            breakdown = {"device_ops": TR.top_ops(tr),
                         "idle_gaps": TR.idle_gaps(tr)}
        attempted = int(counters.get("attempted", 0))
    else:
        out = {**drv.window(state, float(seconds), log), "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(out[m["name"]]),
                                  "unit": m["unit"]}
        attempted = int(out["attempted"])
    compiles = clock.count - before
    log(f"window: {compiles} compiles")
    dev["memory_peak_bytes"] = memory_peak_bytes()
    drv.release(state)
    values, failed = drv.check(state, log)
    ok, compared = CK.verdict(values, CK.load_limits(cell.limits_file))
    return Result(ok and failed == 0, attempted, int(failed), metrics, dev,
                  compared, breakdown)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str):
        print(f"[{time.perf_counter() - t0:8.3f}s] {msg}", file=sys.stderr,
              flush=True)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"the program is not in this checkout ({src} is missing)")
        return 2
    try:
        cell = load_cell(ROOT, args.workload)
    except (KeyError, OSError, ValueError) as e:
        log(f"cannot load the cell: {e!r}")
        return 2
    sys.path.insert(0, str(src))
    import jax
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir(ROOT))
    try:
        device = device_info(cell.chips)
        peak = peaks(device["kind"])
    except (NoChip, KeyError) as e:
        log(f"no run: {e}")
        return 3
    log(f"device {device}; cell {cell.name} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace}")
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   peak, t0, log)
    for k, c in res.compared.items():
        log(f"compared {k}: {c['value']!r} (limit {c['limit']!r})")
    print(res.line(), flush=True)
    return 0
