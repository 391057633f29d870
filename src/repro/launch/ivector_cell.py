"""Dry-run cell for the paper's own model (ivector-tvm): lowers one
distributed EM macro-step (alignment -> Baum-Welch -> E-step accumulation)
on the production mesh.

Thin shims over the StatsEngine's mesh mode (core/engine.py, DESIGN.md
§11): utterances shard over the data axes, UBM components + T_c blocks
over 'model', and ALL the block math — two-stage top-K candidate
exchange, owner-local rescoring and Baum-Welch scatter, E-step
accumulation — is the engine's single `chunk_body` implementation. This
module only adapts the dry-run calling convention (raw arrays in, tagged
accumulators out) and owns the analytic FLOP model + lowering report.

Shapes (full config): C=2048, D=72, R=400, 8192 utts x 1024 frames per
macro-step — the paper's VoxCeleb setup.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.roofline import roofline_from_compiled
from repro.configs import get_shape
from repro.configs.ivector_tvm import CONFIG as IV_CONFIG
from repro.core import engine as EN
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.launch.mesh import make_production_mesh
from repro.sharding import make_rules, use_rules

f32 = jnp.float32


def sharded_align_stats(cfg, mesh, diag_gmm, full_pre, feats_c,
                        second_order: bool):
    """Alignment + Baum-Welch stats with components sharded over 'model':
    one chunk through the engine's shard_map mode (`engine.stream` with
    ``collect_nf``), returning (n [U, C], f [U, C, D], S [C, D, D]).

    The engine's `_align_sharded` provides the collectives contract this
    path used to hand-roll: local top-min(K, C_loc) per rank, all-gather
    of only the [*, P·k] candidates (never the [*, C] scores — an AG of
    68.7 GB/step replaced by ~1.5 GB/step, EXPERIMENTS.md §Perf), masked
    pmax assembly of the selected-set logliks, owner-local scatter with
    zero stats comms, and a single exit all-reduce of the packed
    accumulators over the data axes ('psum': at pod scale the
    bandwidth-optimal tree reduction beats the deterministic ordered
    fold, DESIGN.md §11).
    """
    D = feats_c.shape[-1]
    spec = EN.EngineSpec(
        n_components=cfg.n_components, top_k=cfg.posterior_top_k,
        floor=cfg.posterior_floor,
        second_order="full" if second_order else None,
        chunk=0, rescore=getattr(cfg, "rescore", "dense"))
    pack = EN.UBMPack(None, diag_gmm, full_pre, U.rescore_pack(full_pre),
                      U.align_pack(full_pre))
    # macro-step throughput beats replayability here (DESIGN.md §11)
    # repro-check: disable=DET001
    (tot,), nf = EN.stream(spec, pack, feats_c, None,
                           (EN.TotalsAccum(spec, D),), collect_nf=True,
                           mesh=mesh, exit_reduce="psum")
    S = (tot.ss if second_order
         else jnp.zeros((cfg.n_components, D, D), f32))
    return nf[0], nf[1], S


def em_macro_step(cfg, mesh, ubm_w, ubm_means, ubm_covs, T, Sigma, prior,
                  feats, utt_chunk: int = 512):
    """One jittable EM macro-step over a global batch of utterances.

    The engine scans utterance chunks through the FULL pipeline
    (alignment -> stats -> E-step accumulate) inside ONE shard_map:
    nothing frame-resident ([F, C] posteriors, [F, D^2] expansions,
    [U, R, R] posterior covariances) ever exists for more than one chunk —
    the XLA analogue of the paper's fixed-size-batch streaming (Fig. 1),
    and what the Pallas kernels fuse on real TPU. Only the packed
    [C, P]/[C, D, R] accumulators all-reduce, once, at scan exit
    ('psum' — pod-scale bandwidth over ordered-fold determinism).
    """
    ubm = U.FullGMM(ubm_w, ubm_means, ubm_covs)
    model = TV.TVModel(T=T, Sigma=Sigma, prior=prior, means=ubm_means,
                       formulation="augmented")
    spec = EN.EngineSpec(
        n_components=cfg.n_components, top_k=cfg.posterior_top_k,
        floor=cfg.posterior_floor,
        second_order="full" if cfg.update_sigma else None,
        chunk=utt_chunk, rescore=getattr(cfg, "rescore", "dense"))
    pre = TV.precompute(model, estep=getattr(cfg, "estep", "dense"))
    accums = (EN.TotalsAccum(spec, cfg.feat_dim),
              EN.TVMAccum(model, pre,
                          estep_dtype=getattr(cfg, "estep_dtype",
                                              "float32")))
    # repro-check: disable=DET001  (same throughput-over-replay tradeoff)
    (tot, acc), _ = EN.stream(spec, EN.pack_ubm(ubm), feats, None, accums,
                              mesh=mesh, exit_reduce="psum")
    C, D = cfg.n_components, cfg.feat_dim
    S = (tot.ss if cfg.update_sigma else jnp.zeros((C, D, D), f32))
    return acc, S


def input_structs(cfg, shape):
    """ShapeDtypeStructs for (ubm..., model..., feats)."""
    C, D, R = cfg.n_components, cfg.feat_dim, cfg.ivector_dim
    U_ = shape.global_batch if shape is not None else cfg.utts_per_batch
    F = cfg.frames_per_utt
    sd = jax.ShapeDtypeStruct
    return dict(
        ubm_w=sd((C,), f32), ubm_means=sd((C, D), f32),
        ubm_covs=sd((C, D, D), f32),
        T=sd((C, D, R), f32), Sigma=sd((C, D, D), f32), prior=sd((R,), f32),
        feats=sd((U_, F, D), f32),
    )


def input_axes():
    return dict(
        ubm_w=("components",), ubm_means=("components", None),
        ubm_covs=("components", None, None),
        T=("components", None, None), Sigma=("components", None, None),
        prior=(None,),
        feats=("utts", None, None),
    )


class _IvecShape:
    """Adapter: the paper model has ONE training shape (its macro-step)."""
    name = "em_step"
    kind = "train"
    seq_len = IV_CONFIG.frames_per_utt
    global_batch = IV_CONFIG.utts_per_batch


def model_flops(cfg, n_utts: int) -> float:
    """Analytic useful FLOPs for one macro-step (per DESIGN.md §6):
    alignment vec-trick matmul + BW stats + E-step solves/accumulations."""
    C, D, R, K = (cfg.n_components, cfg.feat_dim, cfg.ivector_dim,
                  cfg.posterior_top_k)
    F = n_utts * cfg.frames_per_utt
    align = 2.0 * F * 2 * D * C                    # diag preselect matmuls
    mode = getattr(cfg, "rescore", "dense")
    if mode == "sparse":
        align += 2.0 * F * K * (D * D + D)         # gather-and-rescore K
    elif mode == "fused":
        # packed-symmetric GEMM against the autotuned tile schedule
        # (DESIGN.md §12): E2 columns per row, u = tile-union rows for the
        # 'union' strategy (C/(BF·K) cut) or all C for 'full'
        from repro.analysis.roofline import autotune_align
        E2 = 1 + D + D * (D + 1) // 2
        tune = autotune_align(C, K, D, device_kind="TPU v5 lite")
        u = min(tune.block_f * K, C) if tune.strategy == "union" else C
        align += 2.0 * F * u * E2
    else:
        align += 2.0 * F * (D * D + D) * C         # dense loglik matmuls
    stats = 2.0 * F * K * (D * D + D)              # sparse accumulation
    # packed-symmetric E-step (DESIGN.md §9): the two dominant symmetric
    # contractions run on P = R(R+1)/2 columns instead of R*R
    RR = (R * (R + 1) / 2.0 if getattr(cfg, "estep", "dense") == "packed"
          else float(R * R))
    estep_L = 2.0 * n_utts * C * RR                # n @ U contraction
    estep_rhs = 2.0 * n_utts * C * D * R
    solves = n_utts * (R ** 3) / 3.0 * 2
    accum = 2.0 * n_utts * C * (RR + D * R)
    return align + stats + estep_L + estep_rhs + solves + accum


def lower_cell(shape_name: str, multi_pod: bool):
    cfg = IV_CONFIG
    if shape_name != "train_4k":
        # the paper model has a single macro-step shape; other assigned LM
        # shapes do not apply (extra arch, not one of the 40 cells)
        return None, {"arch": "ivector-tvm", "shape": shape_name,
                      "mesh": "multi" if multi_pod else "single",
                      "status": "skipped",
                      "reason": "ivector-tvm defines one EM macro-step "
                                "shape; reported under train_4k only"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, cfg, None)
    structs = input_structs(cfg, None)
    axes = input_axes()
    with use_rules(rules):
        shardings = {k: rules.sharding(structs[k].shape, axes[k])
                     for k in structs}
        fn = lambda ubm_w, ubm_means, ubm_covs, T, Sigma, prior, feats: \
            em_macro_step(cfg, mesh, ubm_w, ubm_means, ubm_covs, T, Sigma,
                          prior, feats)
        jitted = jax.jit(fn, in_shardings=tuple(
            shardings[k] for k in ("ubm_w", "ubm_means", "ubm_covs", "T",
                                   "Sigma", "prior", "feats")))
        lowered = jitted.lower(*(structs[k] for k in
                                 ("ubm_w", "ubm_means", "ubm_covs", "T",
                                  "Sigma", "prior", "feats")))
        compiled = lowered.compile()
    rep = roofline_from_compiled(
        compiled, arch="ivector-tvm", shape=shape_name,
        mesh_desc="2x16x16" if multi_pod else "16x16", chips=mesh.size,
        model_flops=model_flops(cfg, cfg.utts_per_batch))
    row = rep.row()
    row["status"] = "ok"
    row["fallbacks"] = sorted(set(str(x) for x in rules.fallbacks))
    return compiled, row
