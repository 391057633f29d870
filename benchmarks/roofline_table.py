"""Roofline table generator: collates the dry-run artifacts (deliverable g)
into the EXPERIMENTS.md §Roofline table + per-cell derived quantities,
plus the fused-alignment autotuner honesty table (``BENCH_autotune.json``):
every candidate schedule the cost model swept, predicted next to measured,
so drift between `analysis.roofline.align_cost_model` and reality shows up
as a committed diff instead of silent mistuning."""
from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def load_rows(mesh: str = "single"):
    rows = []
    for f in sorted(glob.glob(str(REPO / "experiments/dryrun/*.json"))):
        r = json.loads(Path(f).read_text())
        if r.get("mesh") in (mesh, {"single": "16x16", "multi": "2x16x16"}[mesh]):
            rows.append(r)
    return rows


def markdown_table(mesh: str = "single") -> str:
    rows = load_rows(mesh)
    lines = [
        "| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | dominant | "
        "useful/HLO | roofline frac | mem/dev (GB) | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | — | SKIP: {r.get('reason','')[:70]} |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | — | ERROR |")
            continue
        mem = (r['peak_memory_per_device'] or 0) / 1e9
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3f} | "
            f"{r['t_memory_s']:.3f} | {r['t_collective_s']:.3f} | "
            f"{r['dominant']} | {r['useful_flops_ratio']:.3f} | "
            f"{r['roofline_fraction']:.4f} | {mem:.1f} | |")
    return "\n".join(lines)


def summary(mesh: str = "single"):
    rows = [r for r in load_rows(mesh) if r["status"] == "ok"]
    count = {"compute": 0, "memory": 0, "collective": 0}
    for r in rows:
        count[r["dominant"]] += 1
    return {"cells_ok": len(rows), "dominant_counts": count,
            "mean_roofline_fraction":
                sum(r["roofline_fraction"] for r in rows) / max(len(rows), 1)}


# ---------------------------------------------------------------------------
# Fused-alignment autotuner: predicted-vs-measured (DESIGN.md §12)
# ---------------------------------------------------------------------------


def _measure_cell(C, K, D, F, seed=0):
    """Measure every (strategy, block_f) candidate of one autotune cell on
    the current backend and return per-candidate predicted + measured
    seconds. dma_depth candidates collapse on the jnp path (no DMA ring),
    so candidates are deduped to (strategy, block_f)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.speed import _timeit, _synthetic_full_ubm
    from repro.analysis.roofline import (align_cost_model, autotune_align,
                                         local_hardware)
    from repro.core import ubm as U
    from repro.kernels import ops

    hw = local_hardware()
    key = jax.random.PRNGKey(seed)
    ubm = _synthetic_full_ubm(key, C, D)
    pre = U.full_precisions(ubm)
    A2 = U.align_pack(pre)
    x = jax.random.normal(jax.random.fold_in(key, 2), (F, D))
    diag_ll = U.diag_loglik(ubm.to_diag(), x)
    sel = jax.lax.top_k(diag_ll, K)[1].astype(jnp.int32)

    tune = autotune_align(C, K, D, device_kind=hw.name, frames=F)
    seen, cands = set(), []
    for strategy, bf, depth, _t in tune.candidates:
        if (strategy, bf) in seen:
            continue
        seen.add((strategy, bf))
        fn = jax.jit(lambda x_, s_, strategy=strategy, bf=bf:
                     ops.gmm_rescore_fused(x_, s_, A2, strategy=strategy,
                                           block_f=bf))
        t_meas = _timeit(fn, x, sel, n=5)
        cands.append({
            "strategy": strategy, "block_f": int(bf),
            "t_predicted": align_cost_model(
                C, K, D, block_f=bf, strategy=strategy, frames=F, hw=hw),
            "t_measured": t_meas,
        })
    best = min(cands, key=lambda c: c["t_measured"])
    winner = next(c for c in cands if c["strategy"] == tune.strategy
                  and c["block_f"] == tune.block_f)
    return {
        "cell": {"C": C, "K": K, "D": D, "frames": F,
                 "device_kind": hw.name},
        "candidates": cands,
        "predicted_winner": {"strategy": tune.strategy,
                             "block_f": int(tune.block_f),
                             "dma_depth": int(tune.dma_depth)},
        "measured_winner": {"strategy": best["strategy"],
                            "block_f": best["block_f"]},
        "winner_strategy_agrees": best["strategy"] == tune.strategy,
        # regret: how much wall the model's pick leaves on the table
        # relative to the measured-best candidate (1.0 = none)
        "tuning_regret": winner["t_measured"] / best["t_measured"],
    }


def _model_cell(C, K, D, device_kind="TPU v5 lite", frames=4096):
    """Model-only cell (no such accelerator here): the full candidate
    sweep with predictions, recording where the union/full crossover sits
    at paper scale."""
    from repro.analysis.roofline import autotune_align

    tune = autotune_align(C, K, D, device_kind=device_kind, frames=frames)
    return {
        "cell": {"C": C, "K": K, "D": D, "frames": frames,
                 "device_kind": device_kind, "model_only": True},
        "candidates": [
            {"strategy": s, "block_f": int(bf), "dma_depth": int(dp),
             "t_predicted": t}
            for s, bf, dp, t in tune.candidates],
        "predicted_winner": {"strategy": tune.strategy,
                             "block_f": int(tune.block_f),
                             "dma_depth": int(tune.dma_depth)},
    }


def autotune_table(smoke: bool = False, out_path=None):
    """The `autotune` bench case: writes ``BENCH_autotune.json``.

    Measured cells run on this backend (CPU: the jnp oracle path);
    model-only cells cover the paper regime on the TPU profile, where
    the interesting crossover lives: at C=2048 the 'union' tile-union
    gather only beats streaming the whole pack once K drops below
    ~C*gather_bw/(BF_max*hbm_bw) ≈ 12 — the aggressive-pruning regime."""
    measured = ([_measure_cell(64, 8, 12, 1024)] if smoke else
                [_measure_cell(256, 16, 20, 4096),
                 _measure_cell(64, 8, 12, 4096)])
    model_only = [
        _model_cell(2048, 20, 60),   # paper §4.1 (D=60 MFCC+deltas regime)
        _model_cell(2048, 20, 72),   # paper full 72-dim features
        _model_cell(2048, 8, 72),    # aggressive pruning: union wins
        _model_cell(2048, 5, 60),
    ]
    out = {
        "smoke": smoke,
        "measured_cells": measured,
        "model_only_cells": model_only,
        "all_measured_strategies_agree": all(
            c["winner_strategy_agrees"] for c in measured),
        "max_tuning_regret": max(c["tuning_regret"] for c in measured),
    }
    p = Path(out_path) if out_path else REPO / "BENCH_autotune.json"
    p.write_text(json.dumps(out, indent=2) + "\n")
    return out


if __name__ == "__main__":
    if "autotune" in sys.argv[1:]:
        r = autotune_table(smoke="--smoke" in sys.argv[1:])
        print(json.dumps({k: v for k, v in r.items()
                          if k not in ("measured_cells",
                                       "model_only_cells")}, indent=2))
        for c in r["measured_cells"]:
            print(c["cell"], "->", c["predicted_winner"],
                  f"regret {c['tuning_regret']:.2f}")
    else:
        print(markdown_table())
        print()
        print(summary())
