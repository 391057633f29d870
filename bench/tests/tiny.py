"""A checkout of tiny cells for the benchmark's CPU tests.

``make_root(tmp)`` writes a ``BENCHMARK.json`` and the configuration,
traffic and limits files of three cells at toy widths into ``tmp``: the
harness finds them by name exactly as it finds the real cells, with no
code added. ``run(root, cell, ...)`` drives a whole run of one of them
on the CPU, skipping only the look for a chip.
"""
from __future__ import annotations

import json
from pathlib import Path

CONFIG = {
    "name": "tiny", "source": "toy widths for tests",
    "n_components": 16, "feat_dim": 6, "ivector_dim": 8,
    "posterior_top_k": 4, "posterior_floor": 0.025,
    "formulation": "augmented", "prior_offset": 100.0,
    "min_divergence": True, "update_sigma": True,
    "frames_per_utt": 32, "train_utterances": 16,
    "program": {"rescore": "sparse", "estep": "packed",
                "estep_dtype": "float32", "estep_chunk": 4},
    "generator": {"speaker_rank": 4, "channel_rank": 2,
                  "speaker_scale": 1.6, "channel_scale": 0.6},
}

TRAFFIC = {
    "tiny-realign": {"kind": "train", "realign": True,
                     "utts_per_speaker": 4, "trace_iterations": 1},
    "tiny-at-rest": {"kind": "train", "realign": False,
                     "utts_per_speaker": 4, "trace_iterations": 1},
    "tiny-serve": {"kind": "serve", "rate_per_s": 20.0,
                   "lengths": {"median": 40, "sigma": 0.25, "min": 20,
                               "max": 60},
                   "serving": {"max_batch": 4, "min_bucket": 16,
                               "max_bucket": 64},
                   "pool_utterances": 8, "utts_per_speaker": 4,
                   "check_requests": 8, "trace_seconds": 0.5},
}

CELLS = {"train.tiny.realign": "tiny-realign",
         "train.tiny.at-rest": "tiny-at-rest",
         "serve.tiny.open": "tiny-serve"}

# between what sound runs and the HIGH-precision control read on the
# CPU at these widths (seeds 3-5): stats 2.3e-8 against 3.8e-7 or more,
# i-vectors 3.1e-5 against 1.1e-4 or more
LIMITS = {"train": {"loss": 1e-4, "stats": 1e-7, "change": 1e-3},
          "serve": {"ivector_gap": 6e-5}}


def make_root(tmp: Path) -> Path:
    tmp = Path(tmp)
    for sub in ("configs", "traffic", "limits"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    for name, t in TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    for cell, traffic in CELLS.items():
        lim = LIMITS[TRAFFIC[traffic]["kind"]]
        (tmp / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
            {"numbers": {k: {"limit": v} for k, v in lim.items()}}))
    train = [c for c in CELLS if c.startswith("train")]
    serve = [c for c in CELLS if c.startswith("serve")]
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "toy",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "tests"}],
        "workloads": [{"name": c, "config": "tiny", "traffic": t,
                       "chips": 1, "why": "tests"}
                      for c, t in CELLS.items()],
        "end_to_end": [
            {"name": "em_utts_per_s", "unit": "utts/s", "better": "higher",
             "bound": 0.05, "source": "host_clock", "workloads": train},
            {"name": "extract_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock", "workloads": serve},
            {"name": "extract_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock", "workloads": serve},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "mfu.train", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "trainer",
             "moves": "em_utts_per_s", "workloads": train},
            {"name": "padded_share.serve", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "serving",
             "moves": "extract_p95_ms", "workloads": serve}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, cell: str, seed: int = 3, seconds: float = 0.5,
        trace: bool = False, log=lambda msg: None):
    """One whole run of a tiny cell on the CPU (no look for a chip)."""
    import time

    from bench import harness
    c = harness.load_cell(root, cell)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    return harness.run_cell(c, seed, seconds, trace, device, peak,
                            time.perf_counter(), log)
