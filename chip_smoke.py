#!/usr/bin/env python3
"""Bring-up check: the paper-width i-vector train-and-serve path on a TPU,
with compiled Pallas kernels, through the normal entry points.

    python3 chip_smoke.py             # one chip: train, serve, kernel checks
    python3 chip_smoke.py --chips 4   # only the four-chip EM contract

One chip: a synthetic ragged corpus at ``configs/ivector_tvm.CONFIG``
widths (D=72) trains a full-covariance UBM (C=2048) and two realigning
TVM iterations (R=400, top-20 sparse rescoring, packed E-step) through
``launch/serve_ivector.build_state``; the result is saved as a bundle and
served: a readiness probe, ragged requests through the admission queue,
streamed sessions through the session store. Then the main-path kernels
are checked against their ``kernels/ref.py`` oracles on one paper-width
chunk.

Four chips: two EM iterations (the second realigning) of the default
data-parallel trainer on a (4, 1) mesh against the same iterations on
one chip; T, Sigma and the UBM means must agree bit for bit (DESIGN.md
§11, 'ordered' exit reduction).

Every check prints a line; any failure exits non-zero. The last line of a
passing run is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before any work. Times are host-clock walls around
work that ends in ``block_until_ready``, on the chip named in the first
line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
T0 = time.perf_counter()


def log(msg: str):
    """One output line, stamped with the seconds since start."""
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


def fail(msg: str):
    log(f"FAIL: {msg}")
    sys.exit(1)


def check(name: str, ok: bool, detail: str = ""):
    log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
    if not ok:
        fail(name)


try:
    from repro.launch.cache import enable_compile_cache
except ImportError as e:
    fail(f"the repository's src/ is not beside this script ({e})")

# the corpus: 40 speakers x 8 utterances of 256-1024 frames
N_SPEAKERS, UTTS_PER_SPEAKER = 40, 8
MIN_FRAMES, MAX_FRAMES = 256, 1024
# overrides of configs/ivector_tvm.CONFIG; widths are the config's own
OVERRIDES = {
    "n_iters": 2,              # a smoke, not a converged extractor
    "realign_interval": 1,     # realign (UBM means refresh) every iteration
}


def device_info():
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        fail(f"JAX found no TPU (platform {info['platform']!r}); this "
             "check runs only on the chip")
    return info


def block(tree):
    import jax
    return jax.block_until_ready(tree)


def data_config(seed: int, n_components: int, n_speakers: int,
                utts_per_speaker: int):
    from repro.configs.ivector_tvm import CONFIG
    from repro.data.speech import SpeechDataConfig
    return SpeechDataConfig(
        feat_dim=CONFIG.feat_dim, n_components=n_components,
        n_speakers=n_speakers, utts_per_speaker=utts_per_speaker,
        frames_per_utt=MAX_FRAMES, min_frames_per_utt=MIN_FRAMES,
        speaker_rank=16, channel_rank=8, seed=seed)


def finite_nonzero(v) -> bool:
    import numpy as np
    v = np.asarray(v)
    return bool(np.isfinite(v).all()
                and (np.linalg.norm(v.reshape(v.shape[0], -1), axis=1)
                     > 0).all())


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its monitoring
    events), so a phase's wall splits into compile and the rest."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def train_phase(cfg, seed: int, clock: CompileClock):
    import numpy as np
    from repro.launch.serve_ivector import build_state
    data_cfg = data_config(seed, cfg.n_components // 2, N_SPEAKERS,
                           UTTS_PER_SPEAKER)
    marks = [(time.perf_counter(), clock.total)]

    def on_iter(state, diag):
        block(state.model.T)
        now = (time.perf_counter(), clock.total)
        wall, comp = now[0] - marks[-1][0], now[1] - marks[-1][1]
        marks.append(now)
        what = ("corpus + UBM + TV iteration 1" if state.iteration == 1
                else f"TV iteration {state.iteration} (realigning)")
        log(f"time (chip run): {what}: {wall:.3f}s, of which "
            f"tracing + compiling {comp:.3f}s")
        log(f"  TV iteration {state.iteration}: "
            f"avg_loglik {float(diag['avg_loglik']):.4f} "
            f"mean_phi_norm {float(diag['mean_phi_norm']):.4f}")
        # fail here, before the next iteration factors a broken model
        for name, arr in (("T", state.model.T), ("Sigma", state.model.Sigma),
                          ("prior", state.model.prior)):
            check(f"TV iteration {state.iteration} {name} finite",
                  bool(np.isfinite(np.asarray(arr)).all()))
        lam = np.linalg.eigvalsh(np.asarray(state.model.Sigma, np.float64))
        check(f"TV iteration {state.iteration} Sigma positive definite",
              bool(lam.min() > 0),
              f"smallest eigenvalue {lam.min():.6e}")

    state, utts, labels = build_state(cfg, data_cfg, cfg.n_iters, seed=seed,
                                      callback=on_iter)
    frames = sum(int(u.shape[0]) for u in utts)
    log(f"  corpus: {len(utts)} utterances, {frames} frames")
    for name, arr in (("T", state.model.T), ("Sigma", state.model.Sigma),
                      ("UBM means", state.ubm.means)):
        check(f"train {name} finite", finite_nonzero(arr[None]),
              f"shape {tuple(arr.shape)}")
    return state, utts, labels


def serve_phase(cfg, state, utts, labels, seed: int, workdir: Path):
    import numpy as np
    from repro.api import artifacts as AR
    from repro.api.bundle import Bundle
    from repro.serving import (AdmissionQueue, IVectorExtractor,
                               ServingConfig, SessionConfig, SessionStore)

    path = Bundle(cfg=cfg, ubm=state.ubm, model=state.model,
                  provenance={"recipe": "chip_smoke", "seed": seed,
                              "n_iters": cfg.n_iters}).save(
                                  workdir / "bundle")
    ex = IVectorExtractor.from_bundle(
        path, ServingConfig(max_batch=8, min_bucket=MIN_FRAMES,
                            max_bucket=MAX_FRAMES))
    health = ex.health_check()
    log(f"  readiness: {health}")
    check("serve readiness", health["ok"] and health["error"] is None)

    t0 = time.perf_counter()
    ivecs = ex.extract(utts)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = ex.extract(utts)
    steady = time.perf_counter() - t0
    log(f"time (chip run): extract {len(utts)} utterances: cold ("
        f"{ex.stats['compiles']} compiles) {cold:.3f}s, steady "
        f"{steady:.3f}s")
    check("serve i-vectors finite and non-zero", finite_nonzero(ivecs),
          f"shape {ivecs.shape}")
    check("serve extraction deterministic",
          bool(np.array_equal(ivecs, again)))
    eer, _ = AR.evaluate_ivectors(cfg, ivecs, labels, seed)
    log(f"  EER on the synthetic trials: {eer:.4f}")
    check("serve EER finite", bool(np.isfinite(eer)), f"{eer:.4f}")

    q = AdmissionQueue(ex, max_pending=64)
    reqs = utts[:32]
    ids = [q.submit(u) for u in reqs]
    t0 = time.perf_counter()
    res = q.drain()
    wall = time.perf_counter() - t0
    served = [res[i] for i in ids]
    log(f"time (chip run): {len(reqs)} queued requests served in "
        f"{wall:.3f}s; queue stats {q.stats}")
    check("queue served every request",
          all(r.ivector is not None and not r.expired for r in served))
    check("queue i-vectors finite and non-zero",
          finite_nonzero(np.stack([r.ivector for r in served])))

    store = SessionStore(ex, SessionConfig(chunk_min_bucket=MIN_FRAMES,
                                           chunk_max_bucket=MAX_FRAMES))
    streamed = []
    t0 = time.perf_counter()
    for i, u in enumerate(utts[:4]):
        u = np.asarray(u)
        sid = f"stream-{i}"
        for at in range(0, u.shape[0], MIN_FRAMES):
            iv, _ = store.update(sid, u[at:at + MIN_FRAMES])
            streamed.append(iv)
        store.close(sid)
    wall = time.perf_counter() - t0
    log(f"time (chip run): {len(streamed)} streamed chunks over 4 "
        f"sessions in {wall:.3f}s; store stats {store.stats}")
    check("sessions i-vectors finite and non-zero",
          finite_nonzero(np.stack(streamed)))

    modes = {"extractor": ex.mode, "session store": store.health()["mode"]}
    degr = ex.stats["degradations"] + store.stats["degradations"]
    log(f"  guardrails: modes {modes} degradations {degr}")
    check("no demotion", degr == 0, f"degradations == {degr}")
    check("configured rescore mode kept",
          all(m == cfg.rescore for m in modes.values()),
          f"configured {cfg.rescore!r}")


def kernel_phase(cfg, state, utts):
    """Each main-path kernel, and the grouped second-order moments, on
    the chip against a float64 evaluation of its ``kernels/ref.py``
    oracle on the host, on one paper-width chunk of the trained model's
    own operands. Each output is a sum of products, so its error is
    measured against the sum of the products' magnitudes, the scale of a
    dot product's rounding error (the loglik cancels ~1e3-sized terms to
    O(100)), at the tolerances of the interpret-mode tests: 2e-5 for the
    alignment kernels (test_kernels), 1e-5 for the E-step and the moments
    (test_tvm_estep, test_kernels). A control runs the same measure on
    the oracle with its f32 inputs rounded to bf16, which is what a TPU's
    default matmul precision does; its ratio shows whether the measure
    can tell the two apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import trainer as TR
    from repro.core import tvm as TV
    from repro.core import ubm as U
    from repro.kernels import ops

    C, D, K, R = (cfg.n_components, cfg.feat_dim, cfg.posterior_top_k,
                  cfg.ivector_dim)
    x = jnp.asarray(np.concatenate([np.asarray(u) for u in utts[:2]])[:1024])
    fixed = jnp.stack([jnp.asarray(u)[:MIN_FRAMES] for u in utts[:64]])
    stats_fn = TR.make_stats_fn(cfg.with_overrides(update_sigma=False))

    @jax.jit
    def operands(ubm, model, x, fixed):
        const, lin, P = U.full_precisions(ubm)
        sel = jax.lax.top_k(U.diag_loglik(ubm.to_diag(), x), K)[1]
        st = stats_fn(ubm, fixed)
        pre = TV.precompute(model, estep="packed")
        phi, Phi = TV.posterior(model, pre, st.n, st.f)
        iu = jnp.triu_indices(R)
        PP = ops.pack_symmetric(Phi) + phi[:, iu[0]] * phi[:, iu[1]]
        return const, lin.T, P.reshape(C, D * D), sel, st.n, pre.U, PP

    ops_dev = operands(state.ubm, state.model, x, fixed)
    const, lt, Pf, sel, n, Up, PP = (np.asarray(a) for a in ops_dev)
    xh = np.asarray(x)
    gamma = np.asarray(jax.nn.softmax(ops.gmm_rescore(x, sel, *ops_dev[:3])))

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                          .astype(jnp.float32))

    def f64(*xs):
        return [np.asarray(v, np.float64) for v in xs]

    def loglik64(x, const, lt, Pf):
        x, const, lt, Pf = f64(x, const, lt, Pf)
        x2 = (x[:, :, None] * x[:, None, :]).reshape(x.shape[0], -1)
        return const[None] + x @ lt - 0.5 * x2 @ Pf.T

    def loglik_mag(x, const, lt, Pf):
        return loglik64(np.abs(x), np.abs(const), np.abs(lt), -np.abs(Pf))

    def moments64(x, g, sel):
        x, g = f64(x, g)
        W = np.zeros((x.shape[0], C))
        np.add.at(W, (np.arange(x.shape[0])[:, None], sel), g)
        x2 = (x[:, :, None] * x[:, None, :]).reshape(x.shape[0], -1)
        return W.T @ x2

    def matmul64(a, b):
        a, b = f64(a, b)
        return a @ b

    def pick(m):
        return np.take_along_axis(m, sel, axis=1)

    # (name, kernel, its device operands, f64 oracle on host operands,
    #  the host operands, |terms| scale, tolerance, control?)
    cases = [
        ("gmm_rescore", ops.gmm_rescore, (x, sel, *ops_dev[:3]),
         lambda *a: pick(loglik64(*a)), (xh, const, lt, Pf),
         pick(loglik_mag(xh, const, lt, Pf)), 2e-5, True),
        ("gmm_loglik", ops.gmm_loglik, (x, *ops_dev[:3]), loglik64,
         (xh, const, lt, Pf), loglik_mag(xh, const, lt, Pf), 2e-5, True),
        ("second_moments",
         lambda x, g, s: ops.second_moments(x, g, s, C),
         (x, jnp.asarray(gamma), sel), lambda x, g: moments64(x, g, sel),
         (xh, gamma), moments64(np.abs(xh), gamma, sel), 1e-5, True),
    ]
    for dt in ("float32", "bfloat16"):
        cast = bf16 if dt == "bfloat16" else (lambda a: a)
        cases += [
            (f"tvm_estep_l[{dt}]",
             lambda n, u, dt=dt: ops.tvm_estep_l(n, u, dtype=dt),
             (ops_dev[4], ops_dev[5]), matmul64, (cast(n), cast(Up)),
             matmul64(np.abs(cast(n)), np.abs(cast(Up))), 1e-5,
             dt == "float32"),
            (f"tvm_estep_a[{dt}]",
             lambda n, pp, dt=dt: ops.tvm_estep_a(n, pp, dtype=dt),
             (ops_dev[4], ops_dev[6]), lambda n, pp: matmul64(n.T, pp),
             (cast(n), cast(PP)),
             matmul64(np.abs(cast(n)).T, np.abs(cast(PP))), 1e-5,
             dt == "float32"),
        ]
    log(f"  kernel operands: x [{x.shape[0]}, {D}], sel [{x.shape[0]}, "
        f"{K}], C={C}, n [{n.shape[0]}, {C}], P={R * (R + 1) // 2}")
    for name, kern, args, oracle, host, scale, tol, control in cases:
        fn = jax.jit(kern)
        t0 = time.perf_counter()
        got = block(fn(*args))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        block(fn(*args))
        steady = time.perf_counter() - t0
        want = oracle(*host)
        scale = np.maximum(scale, 1e-30)
        got = np.asarray(got, np.float64)
        err = np.abs(got - want)
        ratio = float((err / (tol * scale)).max())
        log(f"time (chip run): {name} compile+first {first:.3f}s, steady "
            f"{steady * 1e3:.3f}ms")
        detail = (f"shape {got.shape} max|err| {float(err.max()):.3e} "
                  f"max|err|/({tol} x term magnitude) {ratio:.4f}")
        if control:
            ctl = oracle(*(bf16(h) if h.dtype == np.float32 else h
                           for h in host))
            cratio = float((np.abs(ctl - want) / (tol * scale)).max())
            detail += f"; control with bf16-rounded inputs {cratio:.4f}"
        check(f"kernel {name} == float64 oracle",
              got.shape == want.shape and ratio <= 1.0, detail)
        compiled = fn.lower(*args).compile().as_text()
        check(f"kernel {name} compiled to a Mosaic call",
              "tpu_custom_call" in compiled)


def one_chip(seed: int):
    from repro.configs.ivector_tvm import CONFIG
    cfg = CONFIG.with_overrides(**OVERRIDES)
    for k, v in OVERRIDES.items():
        log(f"  override {k}={v} (CONFIG: {getattr(CONFIG, k)})")
    log(f"  config: C={cfg.n_components} D={cfg.feat_dim} "
        f"R={cfg.ivector_dim} K={cfg.posterior_top_k} "
        f"rescore={cfg.rescore} estep={cfg.estep} "
        f"estep_dtype={cfg.estep_dtype}")
    state, utts, labels = train_phase(cfg, seed, CompileClock())
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".chip_smoke_") as d:
        serve_phase(cfg, state, utts, labels, seed, Path(d))
    kernel_phase(cfg, state, utts)


def four_chips(seed: int):
    """The DESIGN.md §11 contract on real chips: the default trainer's
    (4, 1) data mesh against one chip, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.ivector_tvm import CONFIG
    from repro.core import trainer as TR
    from repro.core import ubm as U
    from repro.data.speech import build_dataset, make_generator
    from repro.launch.mesh import resolve_mesh

    n_dev = len(jax.devices())
    if n_dev != 4:
        fail(f"--chips 4 needs 4 devices, found {n_dev}")
    # 64 fixed-length utterances, one 16-utterance chunk per chip: the
    # partition the bit-exact contract is stated for
    cfg = CONFIG.with_overrides(n_iters=2, realign_interval=1,
                                estep_chunk=16)
    data_cfg = dataclasses.replace(
        data_config(seed, cfg.n_components, 8, 8),
        frames_per_utt=MIN_FRAMES, min_frames_per_utt=None)
    feats, _ = build_dataset(data_cfg)
    gen, _ = make_generator(data_cfg)     # the generator's GMM is the UBM
    C = cfg.n_components
    ubm = U.FullGMM(jnp.full((C,), 1.0 / C), gen["means"], gen["covs"])
    log(f"  corpus {tuple(feats.shape)}; estep_chunk {cfg.estep_chunk}; "
        f"{cfg.n_iters} iterations, the second realigning")
    key = jax.random.PRNGKey(seed + 100)
    out = {}
    for label, mesh in (("4 chips (default mesh)", None),
                        ("1 chip", (1, 1))):
        resolved = resolve_mesh(mesh, n_utts=feats.shape[0])
        t0 = time.perf_counter()
        st = TR.train(cfg, ubm, feats, key=key, mesh=mesh)
        block(st.model.T)
        log(f"time (chip run): {label}, mesh "
            f"{dict(zip(resolved.axis_names, resolved.devices.shape))}: "
            f"{time.perf_counter() - t0:.3f}s (compiles included)")
        out[label] = st
    a, b = out["4 chips (default mesh)"], out["1 chip"]
    worst = 0.0
    exact = True
    for name, x, y in (("T", a.model.T, b.model.T),
                       ("Sigma", a.model.Sigma, b.model.Sigma),
                       ("UBM means", a.ubm.means, b.ubm.means)):
        x, y = np.asarray(x), np.asarray(y)
        rel = float(np.max(np.abs(x - y)) / np.max(np.abs(y)))
        same = bool(np.array_equal(x, y))
        worst = max(worst, rel)
        exact &= same
        log(f"  {name}: bit-exact {same}, max|diff|/max|1 chip| {rel:.3e}")
    log(f"  max relative difference {worst:.3e}")
    check("4-chip trajectory bit-exact vs 1 chip (DESIGN.md §11)", exact)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip EM contract")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax
    cache = enable_compile_cache()
    info = device_info()
    warm = len(list(Path(cache).glob("*"))) if Path(cache).is_dir() else 0
    log(f"  compile cache: {cache} ({warm} entries before this run)")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"time (chip run): whole check {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
