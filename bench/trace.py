"""The profiler trace of a run's traced window, reduced to numbers.

A traced run wraps its window in the host span ``bench.window`` and the
host work inside it in further ``bench.*`` spans (``jax.profiler
.TraceAnnotation``), so device and host events share the profiler's
clock. From the trace this module takes, for each chip (a plane named
``/device:TPU:<n>``), the events of its "XLA Ops" line clipped to the
window, and the host spans. A TPU trace names each op by its HLO text
(``%gmm_rescore.8 = f32[...] custom-call(...), custom_call_target=...``);
an op is known here by the instruction's name (``gmm_rescore.8``), with
the target of a custom call other than a Pallas kernel appended
(``custom-call.730:TopK``). Control flow (``while``, ``cond``,
``conditional``, ``call``) spans the ops of its body and is left out.
What it computes:

* busy seconds: the union of the device-op intervals, averaged over the
  devices; the idle share is 1 - busy / window;
* seconds of a kernel: the summed durations of the ops whose short
  name matches a pattern;
* the breakdown: the ops that took most device time, and the longest
  idle gaps, each named by the host span that overlaps it most.
"""
from __future__ import annotations

import contextlib
import re
import shutil
import tempfile
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
_CHIP = re.compile(r"^/device:TPU:\d+$")
_CONTAINER = re.compile(r"^(while|cond|conditional|call)(\.\d+)?$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """Short name of an op from its HLO text (see the module doc)."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    m = _TARGET.search(text)
    if m and m.group(1) != "tpu_custom_call":
        name = f"{name}:{m.group(1)}"
    return name


@dataclass(frozen=True)
class Event:
    name: str
    start: float           # seconds on the profiler's clock
    end: float


@dataclass
class Trace:
    devices: List[List[Event]]             # one list of ops per device
    spans: List[Event]                     # host spans named bench.*
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


@dataclass
class Capture:
    trace: Optional[Trace] = None


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(events: Sequence[Event], lo: float, hi: float):
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def busy_seconds(trace: Trace) -> float:
    """Union of device-op time inside the window, averaged over devices."""
    if not trace.devices:
        return 0.0
    lo, hi = trace.window
    return (sum(union_seconds(_clip(ops, lo, hi)) for ops in trace.devices)
            / len(trace.devices))


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, or None when nothing was traced."""
    if not trace.devices or trace.window_s <= 0:
        return None
    return 1.0 - busy_seconds(trace) / trace.window_s


def op_seconds(trace: Trace, pattern: str) -> float:
    """Device seconds of the ops inside the window whose short name
    matches ``pattern`` (a regular expression), averaged over devices."""
    if not trace.devices:
        return 0.0
    rx = re.compile(pattern)
    lo, hi = trace.window
    tot = 0.0
    for ops in trace.devices:
        hits = [e for e in ops if rx.search(e.name)]
        tot += sum(e - s for s, e in _clip(hits, lo, hi))
    return tot / len(trace.devices)


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[[op name, device seconds]] of the n ops that took most time."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    by: Dict[str, float] = {}
    for ops in trace.devices:
        for e in ops:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                by[e.name] = by.get(e.name, 0.0) + (t - s) / len(
                    trace.devices)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """[[host span, seconds]] of the n longest idle gaps of the first
    device inside the window, each named by the bench.* host span that
    overlaps it most ('-' when none does)."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = sorted(_clip(trace.devices[0], lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [sp for sp in trace.spans if sp.name != WINDOW]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, most = "-", 0.0
        for sp in spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > most:
                best, most = sp.name, ov
        out.append([best, e - s])
    return out


def from_profile(path: Path) -> Trace:
    """Reads an .xplane.pb written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if _CHIP.match(plane.name):
            ops = []
            for ln in plane.lines:
                if ln.name != OP_LINE:
                    continue
                for ev in ln.events:
                    name = op_name(ev.name)
                    if not _CONTAINER.match(name):
                        ops.append(Event(name, ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns)
                                         * 1e-9))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        spans.append(Event(ev.name, ev.start_ns * 1e-9,
                                           (ev.start_ns + ev.duration_ns)
                                           * 1e-9))
    win = [sp for sp in spans if sp.name == WINDOW]
    window = (win[-1].start, win[-1].end) if win else (0.0, 0.0)
    return Trace(devices, spans, window)


@contextlib.contextmanager
def capture():
    """Profiles the body with the Python tracer off; on exit the
    capture holds the reduced trace and the files are deleted. One small
    device op runs before the body: the profiler's first device event
    can stall the host for seconds, which is the profiler's cost and not
    the window's."""
    import jax
    import jax.numpy as jnp
    out = Capture()
    d = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(str(d), profiler_options=opts)
        try:
            jax.block_until_ready(jnp.ones((), jnp.float32) + 1)
            yield out
        finally:
            jax.profiler.stop_trace()
        files = sorted(d.glob("**/*.xplane.pb"))
        if files:
            out.trace = from_profile(files[-1])
    finally:
        shutil.rmtree(d, ignore_errors=True)
