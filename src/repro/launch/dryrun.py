"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory/cost analysis, derive roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # both meshes, all cells
    PYTHONPATH=src python -m repro.launch.dryrun --all --single-pod-only
Results are cached as JSON under experiments/dryrun/.
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init. Only the dry-run sees 512 placeholder devices.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.roofline import roofline_from_compiled
from repro.configs import ALL_SHAPES, ARCH_IDS, get_config, get_shape
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.sharding import make_rules, use_rules

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def model_flops_estimate(cfg, shape) -> float:
    """Useful model FLOPs for the step (6ND train / 2ND inference), counting
    matmul-active params (embedding gathers excluded, LM-head matmul
    included once)."""
    max_seq = shape.seq_len if cfg.family == "audio" else 0
    n_active = api.n_active_params(cfg, max_seq=max_seq)
    n_embed = cfg.vocab_size * cfg.d_model
    n_matmul = n_active - n_embed
    if cfg.tie_embeddings:
        n_matmul += cfg.vocab_size * cfg.d_model  # tied head matmul is real
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_matmul * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_matmul * tokens
    return 2.0 * n_matmul * shape.global_batch  # decode: one token per seq


def _shardings_for(rules, struct, axes):
    return jax.tree.map(
        lambda s, a: rules.sharding(s.shape, a), struct, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Lower + compile one cell. Returns (compiled, row_dict)."""
    if arch == "ivector-tvm":
        from repro.launch import ivector_cell
        return ivector_cell.lower_cell(shape_name, multi_pod)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cfg.shape_applicability(shape)
    if not ok:
        return None, {"arch": arch, "shape": shape_name,
                      "mesh": "multi" if multi_pod else "single",
                      "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, cfg, shape)
    max_seq = shape.seq_len if cfg.family == "audio" else 0

    batch_struct = api.input_specs(cfg, shape)
    batch_axes = api.input_axes(cfg, shape)

    with use_rules(rules):
        batch_sh = _shardings_for(rules, batch_struct, batch_axes)
        if shape.kind == "train":
            st_struct = api.state_struct(cfg, max_seq)
            st_axes = api.state_axes(cfg, max_seq)
            st_sh = _shardings_for(rules, st_struct, st_axes)
            step = api.make_train_step(cfg)
            jitted = jax.jit(step, in_shardings=(st_sh, batch_sh),
                             donate_argnums=0)
            lowered = jitted.lower(st_struct, batch_struct)
        elif shape.kind == "prefill":
            p_struct = api.params_struct(cfg, max_seq)
            p_sh = _shardings_for(rules, p_struct, api.params_axes(cfg, max_seq))
            step = api.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(p_struct, batch_struct)
        else:  # decode
            p_struct = api.params_struct(cfg, max_seq)
            p_sh = _shardings_for(rules, p_struct, api.params_axes(cfg, max_seq))
            c_struct, c_axes = api.cache_specs(cfg, shape)
            c_sh = _shardings_for(rules, c_struct, c_axes)
            step = api.make_decode_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, batch_sh),
                             donate_argnums=1)
            lowered = jitted.lower(p_struct, c_struct, batch_struct)
        compiled = lowered.compile()

    chips = mesh.size
    rep = roofline_from_compiled(
        compiled, arch=arch, shape=shape_name,
        mesh_desc="2x16x16" if multi_pod else "16x16", chips=chips,
        model_flops=model_flops_estimate(cfg, shape))
    row = rep.row()
    row["status"] = "ok"
    row["fallbacks"] = sorted(set(str(f) for f in rules.fallbacks))
    return compiled, row


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_existing: bool = True, verbose: bool = True):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    out = OUT_DIR / f"{arch}__{shape_name}__{mesh_tag}.json"
    if skip_existing and out.exists():
        row = json.loads(out.read_text())
        if row.get("status") in ("ok", "skipped"):
            print(f"[cached] {arch} x {shape_name} x {mesh_tag}: "
                  f"{row.get('status')}")
            return row
    t0 = time.time()
    try:
        compiled, row = lower_cell(arch, shape_name, multi_pod)
        row["compile_seconds"] = round(time.time() - t0, 1)
        if compiled is not None and verbose:
            try:
                print(compiled.memory_analysis())
            except Exception as e:  # CPU backend may lack memory analysis
                print("memory_analysis unavailable:", e)
            ca = compiled.cost_analysis()
            print({k: ca[k] for k in sorted(ca)
                   if k in ("flops", "bytes accessed", "transcendentals")})
    except Exception as e:
        row = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:],
               "compile_seconds": round(time.time() - t0, 1)}
    out.write_text(json.dumps(row, indent=2, default=str))
    status = row.get("status")
    extra = (f" dominant={row.get('dominant')} "
             f"rf={row.get('roofline_fraction', 0):.3f}"
             if status == "ok" else row.get("reason", row.get("error", "")))
    print(f"[{status}] {arch} x {shape_name} x {mesh_tag} "
          f"({row['compile_seconds'] if 'compile_seconds' in row else '-'}s) {extra}")
    return row


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only:
        meshes = [True]
    if args.multipod:
        meshes = [True]

    if args.all:
        n_bad = 0
        for arch in ARCH_IDS:
            for shape in ALL_SHAPES:
                for mp in meshes:
                    row = run_cell(arch, shape.name, mp,
                                   skip_existing=not args.force)
                    n_bad += row.get("status") == "error"
        print(f"done; {n_bad} errors")
        raise SystemExit(1 if n_bad else 0)

    assert args.arch and args.shape, "--arch/--shape or --all required"
    for mp in meshes:
        run_cell(args.arch, args.shape, mp, skip_existing=not args.force)


if __name__ == "__main__":
    main()
