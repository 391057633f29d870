"""Paper §4.2 speed table: alignment x-real-time, extraction x-real-time,
and vectorized-vs-naive EM speed-up (the proxy for the paper's 25x over
Kaldi's CPU implementation — both sides run on THIS machine's CPU: the
naive baseline is a per-component Python/numpy loop like a scalar CPU
implementation; ours is the batched-jitted pipeline).

The projected-TPU column scales the measured work by the dry-run roofline
terms of the ivector-tvm cell (197 TFLOP/s target vs measured CPU rate).
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BENCH_CFG, BENCH_DATA, cached
from repro.core import alignment as AL
from repro.core import engine as EN
from repro.core import stats as ST
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.core.pipeline import prepare
from repro.data.speech import FRAME_RATE


def _timeit(fn, *args, n=3):
    """Median-of-n wall time (median, not mean: the gated speedup ratios
    sit within ~1.2x and a single scheduler hiccup in a mean would flip
    them)."""
    fn(*args)  # compile / warm
    ts = []
    for _ in range(n):
        t0 = time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.time() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def naive_em_iteration(model, ubm, feats_np, top_k):
    """Deliberately scalar reference: per-utterance, per-component loops
    with numpy — the 'single-threaded CPU toolkit' baseline."""
    C, D, R = model.T.shape
    T = np.asarray(model.T, np.float64)
    Sigma = np.asarray(model.Sigma, np.float64)
    SigInv = np.linalg.inv(Sigma)
    means = np.asarray(ubm.means, np.float64)
    covs = np.asarray(ubm.covs, np.float64)
    w = np.asarray(ubm.weights, np.float64)
    Pinv = np.linalg.inv(covs)
    logdet = np.linalg.slogdet(covs)[1]
    A = np.zeros((C, R, R))
    Bacc = np.zeros((C, D, R))
    for u in range(feats_np.shape[0]):
        x = feats_np[u].astype(np.float64)
        F = x.shape[0]
        ll = np.zeros((F, C))
        for c in range(C):                      # per-component loop
            d = x - means[c]
            ll[:, c] = (np.log(w[c]) - 0.5 * logdet[c]
                        - 0.5 * np.einsum("fi,ij,fj->f", d, Pinv[c], d))
        ll -= ll.max(1, keepdims=True)
        post = np.exp(ll)
        post /= post.sum(1, keepdims=True)
        n = post.sum(0)
        f = post.T @ x
        L = np.eye(R)
        rhs = np.asarray(model.prior, np.float64).copy()
        for c in range(C):                      # per-component loop
            L += n[c] * T[c].T @ SigInv[c] @ T[c]
            rhs += T[c].T @ SigInv[c] @ f[c]
        phi = np.linalg.solve(L, rhs)
        Phi = np.linalg.inv(L)
        PP = Phi + np.outer(phi, phi)
        for c in range(C):
            A[c] += n[c] * PP
            Bacc[c] += np.outer(f[c], phi)
    return A, Bacc


def dense_full_em_step(gmm, x):
    """The RETIRED pre-engine whole-dataset EM step (benchmark baseline
    only): scores every frame at once and materializes the [F_total, D^2]
    expansion — 21 GB at the paper's §4.1 scale. Production EM streams
    through core/engine.py instead."""
    F, D = x.shape
    ll = U.full_loglik(gmm, x)
    post = jnp.exp(ll - jax.scipy.special.logsumexp(ll, 1, keepdims=True))
    n = jnp.sum(post, axis=0)
    fsum = post.T @ x
    x2 = (x[:, :, None] * x[:, None, :]).reshape(F, D * D)   # the blowup
    ssum = (post.T @ x2).reshape(-1, D, D)
    return U.full_m_step(n, fsum, ssum)


def ubm_em_compare(ubm, frames, top_k_pruned, frame_chunk=512, chunk=1):
    """One full-covariance EM iteration, old dense whole-dataset path vs
    engine-streamed: wall time + analytic peak frame-resident bytes.

    Two engine rows: exact (top_k = C, identical responsibilities) and
    pruned (Kaldi's gselect regime, which only the engine path supports).
    """
    C = ubm.n_components
    F_tot, D = frames.shape
    feats, mask = U._as_utterances(frames, None, frame_chunk)

    def engine_step_for(K):
        spec = EN.EngineSpec(n_components=C, top_k=K, floor=0.0,
                             second_order="full", chunk=chunk)

        def step(g, xs, m):
            st = EN.stream_ubm(spec, EN.pack_ubm(g), xs, m)
            return U.full_m_step(st.n, st.f, st.ss)
        return jax.jit(step)

    t_dense = _timeit(jax.jit(dense_full_em_step), ubm, frames)
    t_engine = _timeit(engine_step_for(C), ubm, feats, mask)
    t_pruned = _timeit(engine_step_for(top_k_pruned), ubm, feats, mask)
    # analytic frame-resident floats PER FRAME, per path (unfused-XLA
    # upper bounds; the Pallas kernels fuse the expansions in VMEM):
    #   dense:  [F, C] posteriors + [F, D^2] expansion, F = whole dataset
    #   engine: logliks [n, 2C] + sparse values [n, K] + x2 [n, D^2]
    #           + weighted scatter operands [n, K(D + D^2)], n = one chunk
    dense_pf = C + D * D

    def engine_pf(K):
        return 2 * C + K + D * D + K * (D + D * D)

    chunk_frames = min(chunk if chunk > 0 else feats.shape[0],
                       feats.shape[0]) * feats.shape[1]
    dense_bytes = 4 * F_tot * dense_pf
    engine_bytes = 4 * chunk_frames * engine_pf(C)
    pruned_bytes = 4 * chunk_frames * engine_pf(top_k_pruned)
    return {
        "frames_total": int(F_tot),
        "dense_step_seconds": t_dense,
        "engine_step_seconds": t_engine,
        "engine_pruned_step_seconds": t_pruned,
        "engine_pruned_top_k": int(top_k_pruned),
        "engine_chunk_frames": int(chunk_frames),
        "dense_peak_frame_bytes": int(dense_bytes),
        "engine_peak_frame_bytes": int(engine_bytes),
        "engine_pruned_peak_frame_bytes": int(pruned_bytes),
        "peak_memory_ratio_exact": dense_bytes / engine_bytes,
        "peak_memory_ratio_pruned": dense_bytes / pruned_bytes,
        # the structural win: dense grows with the dataset, engine with
        # the chunk — this ratio scales linearly in dataset size
        "frame_residency_ratio": F_tot / chunk_frames,
        "frames_per_second_engine": F_tot / t_engine,
    }


REPO_ROOT = Path(__file__).resolve().parent.parent


def _synthetic_full_ubm(key, C, D):
    means = jax.random.normal(key, (C, D)) * 2.0
    A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
    covs = jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)
    return U.FullGMM(jnp.ones((C,)) / C, means, covs)


def posterior_compare(C=256, D=20, K=16, F=4096, seed=0, reps=9):
    """The paper's headline metric (§4.2: 3000x-real-time frame
    posteriors): dense full-covariance scoring vs the sparse top-K
    gather-and-rescore path (DESIGN.md §8) vs the fused single-kernel
    pipeline (DESIGN.md §12), on the jnp execution path.

    Reports wall-clock, frames/sec, x-real-time, and trip-count-aware
    HLO FLOPs (`analysis.hlo_cost`) of the whole jitted alignment step —
    the FLOP ratios isolate the full-cov scoring work each path saves on
    the hottest shared pipeline.
    """
    from repro.analysis.hlo_cost import analyze_hlo

    key = jax.random.PRNGKey(seed)
    ubm = _synthetic_full_ubm(key, C, D)
    diag = ubm.to_diag()
    pre = U.full_precisions(ubm)
    apack = U.align_pack(pre)     # cached like serving caches rescore_pack
    frames = jax.random.normal(jax.random.fold_in(key, 2), (F, D))
    out = {"config": {"n_components": C, "feat_dim": D, "top_k": K,
                      "frames": F},
           "paper_claims": {"alignment_x_realtime": 3000},
           # full-cov rescoring term only: dense scores C, sparse scores K
           "analytic_rescore_flop_ratio": C / K}
    posts = {}
    for mode in ("dense", "sparse", "fused"):
        fn = jax.jit(lambda x, mode=mode: AL.align_frames(
            x, ubm, diag, top_k=K, floor=0.025, precomp=pre,
            rescore=mode, align_pack=apack))
        compiled = fn.lower(frames).compile()   # compile ONCE; time + walk it
        t = _timeit(compiled, frames, n=reps)
        hlo = analyze_hlo(compiled.as_text())
        posts[mode] = compiled(frames)
        out[mode] = {
            "seconds_per_call": t,
            "frames_per_second": F / t,
            "x_realtime": (F / FRAME_RATE) / t,
            "hlo_flops": hlo["flops"],
            "hlo_flops_per_frame": hlo["flops"] / F,
        }
    for mode in ("sparse", "fused"):
        out[f"hlo_flop_ratio_dense_over_{mode}"] = (
            out["dense"]["hlo_flops"] / out[mode]["hlo_flops"])
        out[f"wall_speedup_{mode}"] = (out["dense"]["seconds_per_call"]
                                       / out[mode]["seconds_per_call"])
        out[f"max_abs_posterior_diff_{mode}"] = float(jnp.max(jnp.abs(
            posts["dense"].values - posts[mode].values)))
    # legacy key (earlier BENCH artifacts gated on it)
    out["max_abs_posterior_diff"] = out["max_abs_posterior_diff_sparse"]
    return out


def paper_scale_flops(C=2048, D=60, K=20, F=4096):
    """Paper-regime HLO-FLOP bound, compile-only: every array is a
    ShapeDtypeStruct, nothing is ever executed (dense at this scale
    materialises a [F, D^2] x [D^2, C] matmul a single CPU core would
    chew on for minutes). Lowering + `analysis.hlo_cost` walks the
    compiled module for trip-count-aware FLOPs, proving the C/K cut of
    the selected-set rescore at the paper's own (C, K).

    Rows: dense, sparse, fused under the TPU-model autotuned schedule,
    and fused under the forced 'union' schedule (the pruning-regime
    tile-union gather-GEMM) — the last isolates the C/(BF*K) FLOP cut
    the fused kernel buys when the autotuner picks 'union'.
    """
    from repro.analysis.hlo_cost import analyze_hlo
    from repro.analysis.roofline import autotune_align

    sd = jax.ShapeDtypeStruct
    f32_ = jnp.float32
    E2 = 1 + D + D * (D + 1) // 2
    ubm = U.FullGMM(sd((C,), f32_), sd((C, D), f32_), sd((C, D, D), f32_))
    diag = U.DiagGMM(sd((C,), f32_), sd((C, D), f32_), sd((C, D), f32_))
    pre = (sd((C,), f32_), sd((C, D), f32_), sd((C, D, D), f32_))
    apack = sd((C, E2), f32_)
    x = sd((F, D), f32_)
    tune = autotune_align(C, K, D, device_kind="TPU v5 lite")
    out = {"config": {"n_components": C, "feat_dim": D, "top_k": K,
                      "frames": F, "compile_only": True},
           "tpu_autotune": {"strategy": tune.strategy,
                            "block_f": tune.block_f,
                            "dma_depth": tune.dma_depth}}

    def row(mode):
        fn = jax.jit(lambda x_, ubm_, diag_, pre_, ap_: AL.align_frames(
            x_, ubm_, diag_, top_k=K, floor=0.025, precomp=pre_,
            rescore=mode, align_pack=ap_))
        hlo = analyze_hlo(fn.lower(x, ubm, diag, pre, apack)
                          .compile().as_text())
        return {"hlo_flops": hlo["flops"],
                "hlo_flops_per_frame": hlo["flops"] / F}

    out["dense"] = row("dense")
    out["sparse"] = row("sparse")
    # ops autotunes for the lowering backend; the TPU-model schedule for
    # this cell is reported under "tpu_autotune" above
    out["fused_tuned"] = row("fused")
    for name in ("sparse", "fused_tuned"):
        out[f"hlo_flop_ratio_dense_over_{name}"] = (
            out["dense"]["hlo_flops"] / out[name]["hlo_flops"])
    # the union-schedule fused rescore in isolation (align_frames has no
    # schedule override; the C/(BF*K) cut is a rescore-stage property)
    from repro.kernels import ops as OPS
    bf = 8
    fn = jax.jit(lambda x_, sel_, ap_: OPS.gmm_rescore_fused(
        x_, sel_, ap_, strategy="union", block_f=bf))
    hlo = analyze_hlo(fn.lower(x, sd((F, K), jnp.int32), apack)
                      .compile().as_text())
    out["fused_union_rescore"] = {
        "block_f": bf, "hlo_flops": hlo["flops"],
        "analytic_rescore_flops": 2.0 * F * min(bf * K, C) * E2}
    dense_rescore = 2.0 * F * C * (D * D + 3 * D)  # expansion + GEMM
    out["analytic_rescore_flop_ratio_dense_over_union"] = (
        dense_rescore / out["fused_union_rescore"]["analytic_rescore_flops"])
    return out


def tvm_estep_compare(C=256, D=20, R=128, Utt=256, seed=0):
    """DESIGN.md §9: dense vs packed-symmetric TVM E-step.

    Isolates the two dominant contractions (L-assembly ``n @ U`` and
    A-accumulation ``nᵀ @ PP``) for the headline HLO-FLOP ratio
    (analytically 2R/(R+1), ≈2x at R=128), then times the full
    ``em_accumulate`` both ways plus the bf16-input mixed-precision
    variant, and reports the analytic bytes of the symmetric operands.
    Wall numbers are CPU-backend; FLOP/byte ratios are the portable
    signal (the compiled Pallas kernels realise them on TPU).
    """
    from repro.analysis.hlo_cost import analyze_hlo

    key = jax.random.PRNGKey(seed)
    ubm = _synthetic_full_ubm(key, C, D)
    model = TV.init_model(jax.random.fold_in(key, 1), ubm.means, ubm.covs,
                          R, "augmented", 100.0)
    n = jax.random.uniform(jax.random.fold_in(key, 2), (Utt, C),
                           minval=0.1, maxval=5.0)
    f = jax.random.normal(jax.random.fold_in(key, 3), (Utt, C, D))
    P = R * (R + 1) // 2
    pre_d = TV.precompute(model, estep="dense")
    pre_p = TV.precompute(model, estep="packed")
    out = {"config": {"n_components": C, "feat_dim": D, "rank": R,
                      "packed_dim": P, "utts": Utt},
           "paper_claims": {"em_speedup_vs_kaldi_cpu": 25},
           "analytic_contraction_flop_ratio": (R * R) / P}

    # -- the two dominant contractions in isolation ------------------------
    from repro.kernels import ops as OPS
    phi, Phi = TV.posterior(model, pre_d, n, f)
    PP = Phi + phi[:, :, None] * phi[:, None, :]
    PPp = OPS.pack_symmetric(PP)

    def dense_contraction(n_, U_, PP_):
        L = jnp.einsum("uc,crs->urs", n_, U_)
        A = jnp.einsum("uc,urs->crs", n_, PP_)
        return L, A

    def packed_contraction(n_, Up_, PPp_):
        return OPS.tvm_estep_l(n_, Up_), OPS.tvm_estep_a(n_, PPp_)

    rows = {}
    for name, fn, args in (
            ("dense", dense_contraction, (n, pre_d.U, PP)),
            ("packed", packed_contraction, (n, pre_p.U, PPp))):
        compiled = jax.jit(fn).lower(*args).compile()
        t = _timeit(compiled, *args)
        hlo = analyze_hlo(compiled.as_text())
        rows[name] = {"seconds_per_call": t, "hlo_flops": hlo["flops"],
                      "hlo_bytes": hlo["bytes"]}
    out["contractions"] = rows
    out["contraction_hlo_flop_ratio_dense_over_packed"] = (
        rows["dense"]["hlo_flops"] / rows["packed"]["hlo_flops"])

    # -- the full E-step accumulate (posterior solve included) -------------
    full = {}
    accs = {}
    for name, pre, dt in (("dense", pre_d, "float32"),
                          ("packed", pre_p, "float32"),
                          ("packed_bf16", pre_p, "bfloat16")):
        fn = jax.jit(lambda n_, f_, pre=pre, dt=dt: TV.em_accumulate(
            model, pre, n_, f_, estep_dtype=dt))
        compiled = fn.lower(n, f).compile()
        t = _timeit(compiled, n, f, n=7)   # gated quantity: median-of-7
        hlo = analyze_hlo(compiled.as_text())
        accs[name] = compiled(n, f)
        full[name] = {"seconds_per_call": t, "hlo_flops": hlo["flops"],
                      "hlo_bytes": hlo["bytes"]}
    out["full_estep"] = full
    out["full_estep_hlo_flop_ratio_dense_over_packed"] = (
        full["dense"]["hlo_flops"] / full["packed"]["hlo_flops"])
    out["full_estep_wall_speedup_packed"] = (
        full["dense"]["seconds_per_call"]
        / full["packed"]["seconds_per_call"])
    A_d = np.asarray(accs["dense"].A)
    A_p = np.asarray(OPS.unpack_symmetric(accs["packed"].A, R))
    A_b = np.asarray(OPS.unpack_symmetric(accs["packed_bf16"].A, R))
    scale = np.abs(A_d).max()
    out["max_rel_diff_packed_vs_dense"] = float(
        np.abs(A_p - A_d).max() / scale)
    out["max_rel_diff_bf16_vs_f32"] = float(np.abs(A_b - A_p).max() / scale)

    # -- analytic symmetric-operand memory (U_c + PP_u + A_c per batch) ----
    sym_elems = C + Utt + C   # count of symmetric [R, R] operands
    dense_bytes = 4 * sym_elems * R * R
    packed_bytes = 4 * sym_elems * P
    bf16_bytes = 2 * (C + Utt) * P + 4 * C * P  # bf16 inputs, f32 accum
    out["memory"] = {
        "dense_symmetric_operand_bytes": int(dense_bytes),
        "packed_symmetric_operand_bytes": int(packed_bytes),
        "packed_bf16_symmetric_operand_bytes": int(bf16_bytes),
        "ratio_dense_over_packed": dense_bytes / packed_bytes,
        "ratio_dense_over_packed_bf16": dense_bytes / bf16_bytes,
    }
    return out


def run_tvm_estep(smoke: bool = False, out_path=None):
    """The `tvm_estep` bench case: writes ``BENCH_tvm_estep.json`` at the
    repo root (CI runs the smoke scale so artifact generation can't
    silently rot; the committed artifact is the full R=128 run).

    Acceptance gate (full scale only — the smoke R=16 solve is too small
    for the tri-inverse fast path to matter): the packed E-step, which
    now routes through the matmul-only posterior assembly (DESIGN.md §9),
    must beat the dense cho_solve reference by >= 1.3x wall."""
    kw = (dict(C=32, D=8, R=16, Utt=48) if smoke
          else dict(C=256, D=20, R=128, Utt=256))
    r = tvm_estep_compare(**kw)
    r["smoke"] = smoke
    thr = None if smoke else 1.3
    speedup = r["full_estep_wall_speedup_packed"]
    r["gate"] = {"min_wall_speedup_packed": thr,
                 "wall_speedup_packed": speedup,
                 "passed": thr is None or speedup >= thr}
    p = Path(out_path) if out_path else REPO_ROOT / "BENCH_tvm_estep.json"
    p.write_text(json.dumps(r, indent=2) + "\n")
    if not r["gate"]["passed"]:
        print(f"GATE FAILED: packed E-step wall speedup {speedup:.3f}x "
              f"< required {thr}x vs dense", file=sys.stderr)
        raise SystemExit(1)
    return r


def run_posterior(smoke: bool = False, out_path=None):
    """The `posterior` bench case: writes the machine-readable perf
    trajectory point ``BENCH_posterior.json`` at the repo root (CI runs
    the smoke scale so the artifact generation can't silently rot).

    Acceptance gate (honored in smoke mode too): the fused alignment
    path must not lose to dense at bench scale. Full scale requires
    >= 1.0x; smoke scale (C=64: margins are a few ms on a noisy shared
    core) requires >= 0.8x — the smoke gate exists to catch structural
    regressions (fused silently falling back to a slow path), not to
    re-certify the committed full-scale number."""
    kw = (dict(C=64, D=12, K=8, F=1024) if smoke
          else dict(C=256, D=20, K=16, F=4096))
    r = posterior_compare(**kw)
    r["smoke"] = smoke
    if not smoke:
        r["paper_scale"] = paper_scale_flops()
    thr = 0.8 if smoke else 1.0
    speedup = r["wall_speedup_fused"]
    r["gate"] = {"min_wall_speedup_fused": thr,
                 "wall_speedup_fused": speedup,
                 "passed": speedup >= thr}
    p = Path(out_path) if out_path else REPO_ROOT / "BENCH_posterior.json"
    p.write_text(json.dumps(r, indent=2) + "\n")
    if not r["gate"]["passed"]:
        print(f"GATE FAILED: fused alignment wall speedup {speedup:.3f}x "
              f"< required {thr}x vs dense", file=sys.stderr)
        raise SystemExit(1)
    return r


# -- weak scaling over the sharded trainer substrate (DESIGN.md §11) -------

_SCALE_WORKER = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
spec = json.loads(sys.argv[1])
from repro.configs.ivector_tvm import SMOKE
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.launch import ivector_cell as IC
from repro.launch import mesh as MS
from repro.analysis.hlo_cost import analyze_hlo

n_dev = spec["devices"]
assert len(jax.devices()) == n_dev, (len(jax.devices()), n_dev)
cfg = SMOKE.with_overrides(**spec["overrides"])
U_tot = spec["utts_per_device"] * n_dev
key = jax.random.PRNGKey(0)
C, D = cfg.n_components, cfg.feat_dim
means = jax.random.normal(key, (C, D)) * 2.0
A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
covs = jnp.einsum('cij,ckj->cik', A, A) + jnp.eye(D)
ubm = U.FullGMM(jnp.ones((C,)) / C, means, covs)
model = TV.init_model(jax.random.fold_in(key, 3), ubm.means, ubm.covs,
                      cfg.ivector_dim, cfg.formulation, cfg.prior_offset)
feats = jax.random.normal(jax.random.fold_in(key, 2),
                          (U_tot, cfg.frames_per_utt, D))
mesh = MS.resolve_mesh((n_dev, 1), n_utts=U_tot, n_components=C)
feats, _ = TR._place(mesh, feats, None)
iter_fn = TR.make_iter_fn(cfg, mesh)
compiled = iter_fn.lower(model, ubm, feats, None).compile()
jax.block_until_ready(compiled(model, ubm, feats, None))   # warm
t0 = time.time()
reps = spec["reps"]
for _ in range(reps):
    out = compiled(model, ubm, feats, None)
jax.block_until_ready(out)
t = (time.time() - t0) / reps
hlo = analyze_hlo(compiled.as_text())
res = {
    "devices": n_dev,
    "utts": U_tot,
    "seconds_per_macro_step": t,
    "utts_per_second": U_tot / t,
    "per_device_utts_per_second": U_tot / t / n_dev,
    "all_reduce_bytes_per_macro_step": int(hlo["coll_bytes"]),
    "model_flops": IC.model_flops(cfg, U_tot),
    "model_flops_per_second": IC.model_flops(cfg, U_tot) / t,
}
if spec["naive_utts"]:
    from benchmarks.speed import naive_em_iteration
    nu = spec["naive_utts"]
    feats_np = np.asarray(feats[:nu])
    t0 = time.time()
    naive_em_iteration(model, ubm, feats_np, cfg.posterior_top_k)
    res["naive_seconds_per_utt"] = (time.time() - t0) / nu
print("SCALE_JSON " + json.dumps(res))
"""


def scale_compare(device_counts=(1, 2, 4, 8), utts_per_device=16,
                  overrides=None, naive_utts=4, reps=3):
    """Weak scaling of the sharded trainer substrate on 1..8 fake XLA
    host devices (one subprocess per count — jax locks the device count
    at first init; env via `launch.mesh.fake_device_env`).

    Each worker times one fused EM macro-step (`trainer.make_iter_fn` on
    an (n, 1) data mesh) at a FIXED per-device utterance load, walks the
    compiled HLO for the all-reduce bytes the exit reduction actually
    moves, and reports achieved useful FLOP/s against the analytic
    `launch.ivector_cell.model_flops` model. The 1-device worker also
    times the scalar naive EM baseline per-utterance, so the summary can
    state the measured fraction of the paper's 25x EM speed-up at the
    largest mesh."""
    import subprocess

    from repro.launch.mesh import fake_device_env

    overrides = dict(overrides or {})
    overrides.setdefault("estep_chunk", utts_per_device)  # 1 chunk/rank:
    # the engine's bit-exact regime (chunk partition == rank partition)
    cases = []
    for n in device_counts:
        spec = {"devices": int(n), "utts_per_device": int(utts_per_device),
                "overrides": overrides, "reps": int(reps),
                "naive_utts": int(naive_utts) if n == 1 else 0}
        env = fake_device_env(n)
        env["PYTHONPATH"] = f"{REPO_ROOT / 'src'}:{REPO_ROOT}"
        out = subprocess.run(
            [sys.executable, "-c", _SCALE_WORKER, json.dumps(spec)],
            capture_output=True, text=True, env=env, timeout=900)
        if out.returncode != 0:
            raise RuntimeError(f"scale worker ({n} devices) failed:\n"
                               f"{out.stderr[-3000:]}")
        line = [l for l in out.stdout.splitlines()
                if l.startswith("SCALE_JSON ")][-1]
        cases.append(json.loads(line[len("SCALE_JSON "):]))

    base, peak = cases[0], cases[-1]
    for c in cases:
        # ideal weak scaling keeps the macro-step time flat as devices
        # and utterances grow together
        c["weak_scaling_efficiency"] = (base["seconds_per_macro_step"]
                                        / c["seconds_per_macro_step"])
    out = {
        "config": {"utts_per_device": utts_per_device,
                   "overrides": overrides,
                   "device_counts": [int(n) for n in device_counts]},
        "paper_claims": {"em_speedup_vs_kaldi_cpu": 25},
        "cases": cases,
        "weak_scaling_efficiency_at_max": peak["weak_scaling_efficiency"],
    }
    if "naive_seconds_per_utt" in base:
        naive_s = base["naive_seconds_per_utt"] * peak["utts"]
        speedup = naive_s / peak["seconds_per_macro_step"]
        out["naive_seconds_extrapolated_at_max"] = naive_s
        out["em_speedup_vs_naive_at_max"] = speedup
        out["fraction_of_paper_25x"] = speedup / 25.0
    return out


def run_scale(smoke: bool = False, out_path=None):
    """The `scale` bench case: writes ``BENCH_scale.json`` at the repo
    root (CI runs the smoke scale so artifact generation can't silently
    rot; the committed artifact is the full 1->8 device sweep)."""
    kw = (dict(device_counts=(1, 2), utts_per_device=4, reps=1,
               naive_utts=2,
               overrides=dict(feat_dim=6, n_components=16,
                              posterior_top_k=4, ivector_dim=8,
                              frames_per_utt=32))
          if smoke else
          dict(device_counts=(1, 2, 4, 8), utts_per_device=16, reps=3,
               naive_utts=4))
    r = scale_compare(**kw)
    r["smoke"] = smoke
    p = Path(out_path) if out_path else REPO_ROOT / "BENCH_scale.json"
    p.write_text(json.dumps(r, indent=2) + "\n")
    return r


# -- resilience: guardrail overhead + recovery per fault class -------------


def resilience_compare(C=256, D=20, R=64, Utt=64, F=256, n_steps=3,
                       seed=0):
    """DESIGN.md §13: what failure-domain hardening costs and buys.

    Overhead side: the numerical guardrail (`core.guardrails.check_state`)
    runs on the host after every supervised macro-step — its median wall
    time over the step's own median gives the per-step tax the ≤5% gate
    bounds (measured directly rather than as an end-to-end on/off delta,
    which at CPU bench scale would drown in scheduler noise).

    Recovery side: one supervised run per chaos fault class (host loss,
    mid-step device loss, NaN batch, corrupted latest checkpoint,
    straggler past the step deadline), each reporting the supervisor's
    measured fault→state-restored time and whether the recovered
    trajectory is bit-exact against the clean run — the drills of
    tests/test_resilience.py, quantified.
    """
    import tempfile

    from repro.core import guardrails as GR
    from repro.distributed import fault_tolerance as FT

    key = jax.random.PRNGKey(seed)
    ubm = _synthetic_full_ubm(key, C, D)
    from repro.configs.ivector_tvm import SMOKE
    cfg = SMOKE.with_overrides(
        feat_dim=D, n_components=C, ivector_dim=R,
        posterior_top_k=min(16, C), utts_per_batch=Utt,
        frames_per_utt=F, estep_chunk=Utt, n_iters=n_steps)
    feats = jax.random.normal(jax.random.fold_in(key, 2), (Utt, F, D))
    tkey = jax.random.fold_in(key, 3)

    # -- guardrail overhead per macro-step ---------------------------------
    model = TV.init_model(tkey, ubm.means, ubm.covs, R, cfg.formulation,
                          cfg.prior_offset)
    iter_fn = TR.make_iter_fn(cfg)
    t_step = _timeit(lambda: iter_fn(model, ubm, feats, None), n=5)
    model2, tot, diag = iter_fn(model, ubm, feats, None)
    tree = TR._ckpt_tree(TR.TrainState(model=model2, ubm=ubm), tot)
    metrics = jax.tree.map(float, diag)
    jax.block_until_ready(tree)
    gts = []
    for _ in range(7):
        t0 = time.perf_counter()
        violations = GR.check_state(tree, metrics,
                                    {"avg_loglik": metrics["avg_loglik"]})
        gts.append(time.perf_counter() - t0)
    gts.sort()
    t_guard = gts[len(gts) // 2]
    assert violations == [], violations

    out = {
        "config": {"n_components": C, "feat_dim": D, "rank": R,
                   "utts": Utt, "frames_per_utt": F, "n_steps": n_steps},
        "guardrail": {
            "macro_step_seconds": t_step,
            "guardrail_seconds": t_guard,
            "overhead_fraction": t_guard / t_step,
        },
    }

    # -- recovery time per fault class -------------------------------------
    def supervised(chaos=None, policy=None, ckpt_dir=None):
        t0 = time.perf_counter()
        state, rep = TR.train_supervised(
            cfg, ubm, feats, key=tkey, ckpt_dir=ckpt_dir, chaos=chaos,
            policy=policy)
        return state, rep, time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as d:
        ref_state, ref_rep, t_clean = supervised(ckpt_dir=d)
    ref_T = np.asarray(ref_state.model.T)

    fault_cases = {
        "host_loss": dict(chaos=FT.Chaos(
            fail_at=lambda s, a: s == 2 and a == 0)),
        "device_loss_mid_step": dict(chaos=FT.Chaos(
            device_loss_at=lambda s, a: s == 1 and a == 0)),
        "nan_batch": dict(chaos=FT.Chaos(
            poison_at=lambda s, a: s == 1 and a == 0)),
        "corrupt_checkpoint": dict(chaos=FT.Chaos(
            corrupt_ckpt_at=lambda s, a: s == 1 and a == 0,
            fail_at=lambda s, a: s == 2 and a == 0)),
        "straggler_deadline": dict(
            chaos=FT.Chaos(delay_at=lambda s, a: 1e6 if (s == 1 and a == 0)
                           else 0.0),
            policy=FT.RetryPolicy(max_restarts=5, step_deadline=3600.0)),
    }
    recovery = {}
    for name, kw in fault_cases.items():
        with tempfile.TemporaryDirectory() as d:
            state, rep, wall = supervised(ckpt_dir=d, **kw)
        recovery[name] = {
            "n_restarts": rep.n_restarts,
            "faults": [f["type"] for f in rep.faults],
            "recovery_seconds": rep.faults[0]["recovery_s"],
            "run_seconds": wall,
            "overrun_vs_clean_seconds": wall - t_clean,
            "bit_exact": bool(np.array_equal(
                np.asarray(state.model.T), ref_T)),
            "skipped_corrupt": list(rep.skipped_corrupt),
        }
    out["clean_run_seconds"] = t_clean
    out["recovery"] = recovery
    out["all_fault_classes_bit_exact"] = all(
        r["bit_exact"] for r in recovery.values())
    return out


def run_resilience(smoke: bool = False, out_path=None):
    """The `resilience` bench case: writes ``BENCH_resilience.json`` at
    the repo root (CI runs the smoke scale so artifact generation can't
    silently rot; the committed artifact is the full run).

    Acceptance gates (full scale only — at smoke scale the macro-step is
    a few ms and the host-side guardrail fraction is pure noise): the
    numerical guardrail must cost <= 5% of a macro-step, and every chaos
    fault class must recover bit-exactly."""
    kw = (dict(C=32, D=8, R=16, Utt=16, F=64, n_steps=2) if smoke
          else dict(C=256, D=20, R=64, Utt=64, F=256, n_steps=3))
    r = resilience_compare(**kw)
    r["smoke"] = smoke
    thr = None if smoke else 0.05
    frac = r["guardrail"]["overhead_fraction"]
    exact = r["all_fault_classes_bit_exact"]
    r["gate"] = {"max_guardrail_overhead_fraction": thr,
                 "guardrail_overhead_fraction": frac,
                 "all_fault_classes_bit_exact": exact,
                 "passed": (thr is None or frac <= thr) and exact}
    p = (Path(out_path) if out_path
         else REPO_ROOT / "BENCH_resilience.json")
    p.write_text(json.dumps(r, indent=2) + "\n")
    if not r["gate"]["passed"]:
        print(f"GATE FAILED: guardrail overhead {frac:.4f} > allowed "
              f"{thr} per macro-step, or a fault class lost bit-exactness "
              f"(bit_exact={exact})", file=sys.stderr)
        raise SystemExit(1)
    return r


# -- streaming sessions: load, chaos, and rollout (DESIGN.md §14) ----------

_STREAM_WORKER = r"""
import json, os, signal, sys
import numpy as np
spec = json.loads(sys.argv[1])
import jax, jax.numpy as jnp
from repro.configs.ivector_tvm import SMOKE
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.serving import (IVectorExtractor, ServingConfig, SessionConfig,
                           SessionStore)

cfg = SMOKE.with_overrides(**spec["overrides"])
C, D, R = cfg.n_components, cfg.feat_dim, cfg.ivector_dim
key = jax.random.PRNGKey(0)
means = jax.random.normal(key, (C, D)) * 2.0
A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
covs = jnp.einsum('cij,ckj->cik', A, A) + jnp.eye(D)
ubm = U.FullGMM(jnp.ones((C,)) / C, means, covs)
model = TV.init_model(jax.random.fold_in(key, 3), ubm.means, ubm.covs,
                      R, cfg.formulation, cfg.prior_offset)
F = spec["chunk_frames"]
ex = IVectorExtractor(cfg, model, ubm,
                      ServingConfig(min_bucket=F, max_bucket=4 * F))
store = SessionStore(ex, SessionConfig(
    chunk_min_bucket=F, chunk_max_bucket=4 * F,
    journal_dir=spec["journal_dir"]))
mode, S, ROUNDS = spec["mode"], spec["n_sessions"], spec["n_rounds"]
if mode == "resume":
    print("RESTORED %d TORN %d" % (store.stats["restored"],
                                   store.stats["journal_torn"]), flush=True)

def chunk(i, r):
    rng = np.random.RandomState(spec["seed"] * 100003 + i * 1009 + r)
    return rng.randn(F, D).astype(np.float32)

emitted = 0
for r in range(ROUNDS):
    for i in range(S):
        sid = "s%d" % i
        s = store.session(sid)
        if s is not None and s.chunks >= r + 1:
            continue          # resume: the journal says this chunk landed
        iv, _ = store.update(sid, chunk(i, r))
        print("EMIT %s %d %s" % (sid, r, iv.tobytes().hex()), flush=True)
        emitted += 1
        if mode == "crash" and emitted == spec["crash_chunks"]:
            os.kill(os.getpid(), signal.SIGKILL)   # no cleanup, no flush
print("DONE", flush=True)
"""


def _stream_worker(spec):
    """Run one _STREAM_WORKER subprocess; returns (emits, restored)
    where emits maps (sid, round) -> i-vector hex bytes. A 'crash' run
    dies by SIGKILL (expected); any other failure raises."""
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO_ROOT / 'src'}:{REPO_ROOT}"
    out = subprocess.run(
        [sys.executable, "-c", _STREAM_WORKER, json.dumps(spec)],
        capture_output=True, text=True, env=env, timeout=900)
    if spec["mode"] == "crash":
        assert out.returncode == -signal.SIGKILL, (
            f"crash worker exited {out.returncode}, expected SIGKILL:\n"
            f"{out.stderr[-2000:]}")
    elif out.returncode != 0:
        raise RuntimeError(f"stream worker ({spec['mode']}) failed:\n"
                           f"{out.stderr[-3000:]}")
    emits, restored = {}, 0
    for line in out.stdout.splitlines():
        if line.startswith("EMIT "):
            _, sid, rnd, hexiv = line.split()
            emits[(sid, int(rnd))] = hexiv
        elif line.startswith("RESTORED "):
            restored = int(line.split()[1])
    return emits, restored


def streaming_chaos_drill(overrides, n_sessions, n_rounds, chunk_frames,
                          seed=0):
    """The kill -9 drill, all three legs as subprocesses so reference
    and crashed runs share one code path: (1) an uninterrupted run;
    (2) the same traffic killed by SIGKILL mid-stream with the journal
    on; (3) a restart that restores from the journal and finishes the
    traffic. Every post-restart emission must be bit-identical to the
    uninterrupted run's — the journal holds the accumulator bytes, so
    recovery is a read, not a recompute."""
    import tempfile
    base = {"overrides": overrides, "n_sessions": n_sessions,
            "n_rounds": n_rounds, "chunk_frames": chunk_frames,
            "seed": seed}
    # kill mid-round: some sessions have the round's chunk, some don't —
    # recovery must resume each stream at ITS OWN journal cursor
    crash_chunks = n_sessions * (n_rounds // 2) + n_sessions // 2
    ref, _ = _stream_worker(dict(base, mode="run", journal_dir=None))
    with tempfile.TemporaryDirectory() as d:
        jd = os.path.join(d, "journal")
        crash_emits, _ = _stream_worker(
            dict(base, mode="crash", journal_dir=jd,
                 crash_chunks=crash_chunks))
        resume_emits, restored = _stream_worker(
            dict(base, mode="resume", journal_dir=jd))
    assert len(crash_emits) == crash_chunks
    mismatched = [k for k, v in resume_emits.items() if ref.get(k) != v]
    union = dict(crash_emits)
    union.update(resume_emits)
    return {
        "n_sessions": n_sessions,
        "n_rounds": n_rounds,
        "chunks_before_kill": crash_chunks,
        "sessions_restored": restored,
        "emits_after_restart": len(resume_emits),
        "post_restart_emits_bit_exact": not mismatched,
        "no_emission_lost_or_duplicated": (
            union == ref and len(crash_emits) + len(resume_emits)
            == len(ref)),
        "bit_exact": (not mismatched and restored == n_sessions
                      and union == ref),
    }


def streaming_compare(C=64, D=12, R=32, n_sessions=12, n_rounds=6,
                      chunk_frames=64, burst=48, seed=0):
    """DESIGN.md §14: what the streaming serving layer costs and proves.

    Measures, on one synthetic (UBM, TVM) pair: time-to-first-ivector
    (cold with compiles, then warm); per-chunk update cost vs stream
    position (additive stats -> flat, no dependence on how much audio
    came before); the write-ahead journal's per-append cost against the
    per-chunk update (the <=5% gate, measured directly like the
    resilience guardrail); p50/p99 queue latency under a synchronized
    burst through the adaptive admission queue; a hot-swap + rollback
    under interleaved traffic (failed requests must be 0, rollback
    bit-exact); and the subprocess kill -9 chaos drill.

    The drill runs first: its children each need the device, and a
    parent that has started a JAX backend holds it (one process per
    chip)."""
    import tempfile

    from repro.api.bundle import Bundle
    from repro.configs.ivector_tvm import SMOKE
    from repro.serving import (AdmissionQueue, IVectorExtractor,
                               QueueFull, RolloutController,
                               ServingConfig, SessionConfig, SessionStore)

    overrides = dict(feat_dim=D, n_components=C, ivector_dim=R,
                     posterior_top_k=min(8, C), frames_per_utt=chunk_frames)
    chaos = streaming_chaos_drill(
        overrides, n_sessions=n_sessions, n_rounds=n_rounds,
        chunk_frames=chunk_frames, seed=seed)
    cfg = SMOKE.with_overrides(**overrides)
    key = jax.random.PRNGKey(seed)
    ubm = _synthetic_full_ubm(key, C, D)
    model = TV.init_model(jax.random.fold_in(key, 3), ubm.means, ubm.covs,
                          R, cfg.formulation, cfg.prior_offset)
    sv = ServingConfig(min_bucket=chunk_frames, max_bucket=4 * chunk_frames)

    def chunk(i, r):
        rng = np.random.RandomState(seed * 100003 + i * 1009 + r)
        return rng.randn(chunk_frames, D).astype(np.float32)

    out = {"config": {"n_components": C, "feat_dim": D, "rank": R,
                      "n_sessions": n_sessions, "n_rounds": n_rounds,
                      "chunk_frames": chunk_frames, "burst": burst}}

    # -- time-to-first-ivector + per-chunk cost vs position ----------------
    ex = IVectorExtractor(cfg, model, ubm, sv)
    store = SessionStore(ex, SessionConfig(chunk_min_bucket=chunk_frames,
                                           chunk_max_bucket=4 * chunk_frames))
    t0 = time.perf_counter()
    store.update("cold", chunk(99, 0))
    cold_first = time.perf_counter() - t0          # includes every compile
    firsts, by_position = [], [[] for _ in range(n_rounds)]
    for i in range(n_sessions):
        for r in range(n_rounds):
            t0 = time.perf_counter()
            store.update(f"s{i}", chunk(i, r))
            dt = time.perf_counter() - t0
            by_position[r].append(dt)
            if r == 0:
                firsts.append(dt)
    flat = [float(np.median(ts)) for ts in by_position]
    all_chunks = sorted(t for ts in by_position for t in ts)
    out["time_to_first_ivector"] = {
        "cold_including_compiles_s": cold_first,
        "warm_p50_s": float(np.median(firsts)),
        "warm_max_s": float(np.max(firsts)),
    }
    out["per_chunk_update"] = {
        "p50_s": float(np.median(all_chunks)),
        "p99_s": float(all_chunks[int(0.99 * (len(all_chunks) - 1))]),
        "p50_by_stream_position_s": flat,
        # additive stats: cost must not grow with accumulated audio
        "last_over_first_position": flat[-1] / flat[0],
    }

    # -- journal overhead per chunk (direct measure, <=5% gate) ------------
    with tempfile.TemporaryDirectory() as d:
        jstore = SessionStore(ex, SessionConfig(
            chunk_min_bucket=chunk_frames, chunk_max_bucket=4 * chunk_frames,
            journal_dir=d))
        jts, uts = [], []
        for r in range(max(8, n_rounds)):
            t0 = time.perf_counter()
            jstore.update("j", chunk(7, r))
            uts.append(time.perf_counter() - t0)
        rec = jstore._record(jstore.session("j"))
        for _ in range(32):
            t0 = time.perf_counter()
            jstore._journal.append(rec)
            jts.append(time.perf_counter() - t0)
        jts.sort(), uts.sort()
        t_append = jts[len(jts) // 2]
        t_update = uts[len(uts) // 2]
        bytes_per = jstore._journal.bytes / jstore._journal.records
        jstore.close_store()
    out["journal"] = {
        "append_p50_s": t_append,
        "chunk_update_p50_s": t_update,
        "overhead_fraction": t_append / t_update,
        "bytes_per_record": bytes_per,
    }

    # -- p50/p99 under a synchronized burst --------------------------------
    q = AdmissionQueue(ex, max_pending=max(8, burst // 2), store=store)
    waits, shed = [], 0
    for b in range(burst):                 # all submitted in one instant
        sid = f"s{b % n_sessions}"
        try:
            q.submit(chunk(b % n_sessions, n_rounds + b // n_sessions),
                     kind="first" if b < n_sessions else "refine", sid=sid)
        except QueueFull:
            shed += 1
    while len(q):
        for r in q.drain(q.batch_budget()).values():
            if r.ivector is not None:
                waits.append(r.wait_s)
    waits.sort()
    out["burst"] = {
        "submitted": burst,
        "served": len(waits),
        "shed_at_submit": shed,
        "shed_refine_preempted": q.stats["shed_refine"],
        "p50_latency_s": waits[len(waits) // 2],
        "p99_latency_s": waits[int(0.99 * (len(waits) - 1))],
    }

    # -- hot-swap under load: 0 failed requests, rollback bit-exact --------
    with tempfile.TemporaryDirectory() as d:
        p_same = os.path.join(d, "b_same")
        p_new = os.path.join(d, "b_new")
        Bundle(cfg=cfg, ubm=ubm, model=model).save(p_same)
        import dataclasses as _dc
        Bundle(cfg=cfg, ubm=ubm,
               model=_dc.replace(model, T=model.T * 1.01)).save(p_new)
        rc = RolloutController(ex, store=store, queue=q)
        shadow = [chunk(50 + i, 0) for i in range(4)]
        probe = ex.extract(shadow)              # pre-swap reference
        quiet_iv = store.solve("s0")            # no chunks during swaps
        errors, outcomes, rounds_served = 0, [], 0

        def tick(r):
            nonlocal errors, rounds_served
            for i in range(1, n_sessions):      # s0 stays quiescent
                try:
                    q.submit(chunk(i, 200 + r), kind="refine", sid=f"s{i}")
                except QueueFull:
                    pass                        # backpressure, not an error
            while len(q):
                for res in q.drain(q.batch_budget()).values():
                    if res.preempted or res.expired:
                        continue                # shed by policy, reported
                    if (res.ivector is None
                            or not np.isfinite(res.ivector).all()):
                        errors += 1
                    else:
                        rounds_served += 1

        tick(0)
        outcomes.append(rc.roll(p_same, shadow_utts=shadow).outcome)
        tick(1)
        outcomes.append(rc.roll(p_new, shadow_utts=shadow,
                                max_cos_dist=1.99).outcome)
        tick(2)
        rolled_back = rc.rollback()
        tick(3)
        post = rc.live.extract(shadow)
        out["rollout"] = {
            "swap_outcomes": outcomes,
            "requests_served_through_swaps": rounds_served,
            "failed_requests": errors,
            "rolled_back": rolled_back,
            "rollback_extract_bit_exact": bool(np.array_equal(probe, post)),
            "rollback_session_solve_bit_exact": bool(np.array_equal(
                quiet_iv, store.solve("s0"))),
            "draining_after_rollback": store.draining(),
        }
    out["chaos"] = chaos
    return out


def run_streaming(smoke: bool = False, out_path=None):
    """The `streaming` bench case: writes ``BENCH_streaming.json`` at
    the repo root (CI runs the smoke scale gated on bit-exact crash
    recovery; the committed artifact is the full run).

    Acceptance gates: the kill -9 drill must restore every session and
    re-emit bit-exactly; the hot-swap drill must serve through both
    swaps and the rollback with 0 failed requests and a bit-exact
    rollback; at full scale the journal append must cost <= 5% of a
    per-chunk update (at smoke scale both sides are sub-millisecond CPU
    noise, so the ratio is reported but not gated)."""
    kw = (dict(C=16, D=6, R=8, n_sessions=8, n_rounds=4,
               chunk_frames=32, burst=24)
          if smoke else
          dict(C=64, D=12, R=32, n_sessions=12, n_rounds=6,
               chunk_frames=64, burst=48))
    r = streaming_compare(**kw)
    r["smoke"] = smoke
    thr = None if smoke else 0.05
    frac = r["journal"]["overhead_fraction"]
    chaos_ok = r["chaos"]["bit_exact"]
    swap_ok = (r["rollout"]["failed_requests"] == 0
               and r["rollout"]["swap_outcomes"] == ["swapped", "swapped"]
               and r["rollout"]["rollback_extract_bit_exact"]
               and r["rollout"]["rollback_session_solve_bit_exact"])
    r["gate"] = {
        "crash_recovery_bit_exact": chaos_ok,
        "swap_zero_failed_requests_and_bit_exact_rollback": swap_ok,
        "max_journal_overhead_fraction": thr,
        "journal_overhead_fraction": frac,
        "passed": chaos_ok and swap_ok and (thr is None or frac <= thr),
    }
    p = (Path(out_path) if out_path
         else REPO_ROOT / "BENCH_streaming.json")
    p.write_text(json.dumps(r, indent=2) + "\n")
    if not r["gate"]["passed"]:
        print(f"GATE FAILED: chaos bit_exact={chaos_ok}, "
              f"swap clean={swap_ok}, journal overhead {frac:.4f} "
              f"(allowed {thr})", file=sys.stderr)
        raise SystemExit(1)
    return r


def end2end_recipe(n_iters: int = 2, seed: int = 0):
    """`recipe.run` wall time on the SMOKE-scale task: the full staged
    chain (features -> UBM -> TVM -> backend -> eval), so the perf
    trajectory covers the end-to-end pipeline, not just kernels. Data
    and UBM are prepared outside the timed region (they are shared
    across variants/seeds in every real study); the timed part is the
    train+backend+eval body one seed costs."""
    from repro.api import IVectorRecipe, prepare as api_prepare

    recipe = IVectorRecipe.from_config(BENCH_CFG, BENCH_DATA)
    data = api_prepare(BENCH_CFG, BENCH_DATA, seed=seed)
    recipe.run(data=data, seed=seed, n_iters=n_iters)   # warm/compile
    t0 = time.time()
    result = recipe.run(data=data, seed=seed, n_iters=n_iters)
    wall = time.time() - t0
    U_, F = data[0].shape[:2]
    return {
        "seconds": wall,
        "seconds_per_iter": wall / n_iters,
        "n_iters": n_iters,
        "eer": float(result.eer),
        "utts": int(U_),
        "audio_x_realtime": (U_ * F / FRAME_RATE) / wall,
    }


def run():
    def compute():
        feats, labels, ubm = prepare(BENCH_CFG, BENCH_DATA, seed=0)
        cfg = BENCH_CFG
        diag = ubm.to_diag()
        pre_ubm = U.full_precisions(ubm)
        n_utt_bench = 24

        # 1) frame alignment throughput
        frames = feats.reshape(-1, feats.shape[-1])
        align = jax.jit(lambda x: AL.align_frames(
            x, ubm, diag, top_k=cfg.posterior_top_k,
            floor=cfg.posterior_floor, precomp=pre_ubm))
        t_align = _timeit(align, frames)
        align_xrt = (frames.shape[0] / FRAME_RATE) / t_align

        # 2) i-vector extraction throughput (alignment + stats + posterior)
        model = TV.init_model(jax.random.PRNGKey(0), ubm.means, ubm.covs,
                              cfg.ivector_dim, "augmented",
                              cfg.prior_offset)
        stats_fn = TR.make_stats_fn(cfg)

        def extract(feats_):
            st = stats_fn(ubm, feats_)
            pre = TV.precompute(model)
            return TV.extract_ivectors(model, pre, st.n, st.f)
        t_ex = _timeit(extract, feats)
        audio_seconds = feats.shape[0] * feats.shape[1] / FRAME_RATE
        extract_xrt = audio_seconds / t_ex

        # 3) EM iteration: vectorized-jitted vs naive scalar baseline
        em_fn = TR.make_em_fn(cfg.with_overrides(update_sigma=False))
        st = stats_fn(ubm, feats[:n_utt_bench])

        def em_ours(n, f):
            return em_fn(model, n, f, None)
        t_ours = _timeit(em_ours, st.n, st.f)
        feats_np = np.asarray(feats[:n_utt_bench])
        t0 = time.time()
        naive_em_iteration(model, ubm, feats_np, cfg.posterior_top_k)
        t_naive = time.time() - t0

        # 4) UBM EM: retired whole-dataset dense step vs engine streaming
        ubm_em = ubm_em_compare(ubm, frames, cfg.posterior_top_k)
        return {
            "ubm_em": ubm_em,
            "alignment_x_realtime": align_xrt,
            "alignment_frames_per_s": frames.shape[0] / t_align,
            "extraction_x_realtime": extract_xrt,
            "em_iter_seconds_vectorized": t_ours,
            "em_iter_seconds_naive": t_naive,
            "em_speedup_vs_naive": t_naive / t_ours,
            "paper_claims": {"alignment_x_realtime": 3000,
                             "extraction_x_realtime": 10000,
                             "em_speedup": 25},
        }

    return cached("speed", compute)


if __name__ == "__main__":
    if "posterior" in sys.argv[1:]:
        r = run_posterior(smoke="--smoke" in sys.argv[1:])
        print(json.dumps(r, indent=2))
    elif "tvm_estep" in sys.argv[1:]:
        r = run_tvm_estep(smoke="--smoke" in sys.argv[1:])
        print(json.dumps(r, indent=2))
    elif "scale" in sys.argv[1:]:
        r = run_scale(smoke="--smoke" in sys.argv[1:])
        print(json.dumps(r, indent=2))
    elif "resilience" in sys.argv[1:]:
        r = run_resilience(smoke="--smoke" in sys.argv[1:])
        print(json.dumps(r, indent=2))
    elif "streaming" in sys.argv[1:]:
        r = run_streaming(smoke="--smoke" in sys.argv[1:])
        print(json.dumps(r, indent=2))
    elif "end2end" in sys.argv[1:]:
        print(json.dumps(end2end_recipe(), indent=2))
    else:
        r = run()
        for k, v in r.items():
            print(k, v)
