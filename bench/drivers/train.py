"""Training cells: total-variability EM at a configuration's widths.

The traffic file chooses between two jobs over one seeded corpus:

* ``"realign": true`` - the program's own realigning loop as
  ``repro.core.trainer.train`` runs it: before every iteration but the
  first, ``refresh_ubm`` writes the UBM means back from T, then the
  ``make_iter_fn(cfg)`` program aligns every frame, accumulates the
  Baum-Welch moments and the E-step, and runs the M-step.
* ``"realign": false`` - statistics at rest: set-up aligns the corpus
  once through ``make_stats_ll_fn(cfg)`` and every iteration is the
  ``make_em_fn(cfg)`` program on those statistics.

Set-up makes the corpus, the UBM and the starting model from the seed,
builds the loop and drives it through its first three iterations, whose
results the reference follows. The window then runs whole iterations
of the same loop until ``--seconds`` have passed, each timed to
``block_until_ready`` of the new model.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from bench import checks as CK
from bench import data as BD
from bench import reference as REF

FIRST_STEPS = 3


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def ivector_config(config: dict, traffic: dict):
    """The program's IVectorConfig of a configuration file and a mix."""
    from repro.configs.ivector_tvm import IVectorConfig
    keys = ("feat_dim", "n_components", "ivector_dim", "formulation",
            "prior_offset", "min_divergence", "update_sigma",
            "posterior_top_k", "posterior_floor", "frames_per_utt")
    kw = {k: config[k] for k in keys}
    kw.update(config.get("program", {}))
    realign = bool(traffic.get("realign", False))
    kw["realign_interval"] = 1 if realign else 0
    kw["ubm_update"] = "means"
    return IVectorConfig(**kw).validate()


@jax.jit
def _tvar(T):
    """Sum over the rank of T_c T_c^T: [C, D, D], the part of the model
    that a rotation of the i-vector space leaves unchanged."""
    return jnp.einsum("cdr,cer->cde", T, T,
                      precision=jax.lax.Precision.HIGHEST)


class _RealignLoop:
    """refresh_ubm when due, then the make_iter_fn program."""

    def __init__(self, cfg, model, ubm, feats):
        from repro.core import trainer as TR
        self.TR = TR
        self.cfg, self.model, self.ubm, self.feats = cfg, model, ubm, feats
        self.iter_fn = TR.make_iter_fn(cfg)
        self.it, self.totals, self.diag = 0, None, None

    def step(self):
        if self.TR._realign_due(self.cfg, self.it, self.model):
            with _span("bench.refresh_ubm"):
                self.ubm = self.TR.refresh_ubm(self.cfg, self.model,
                                               self.ubm, self.totals)
        with _span("bench.iteration"):
            self.model, self.totals, self.diag = self.iter_fn(
                self.model, self.ubm, self.feats)
            jax.block_until_ready(self.model)
        self.it += 1


class _AtRestLoop:
    """make_em_fn on statistics aligned once in set-up."""

    def __init__(self, cfg, model, ubm, feats):
        from repro.core import trainer as TR
        self.cfg, self.model = cfg, model
        with _span("bench.align_at_rest"):
            self.stats, (ll, frames) = TR.make_stats_ll_fn(cfg)(ubm, feats)
            self.avg_loglik = float(ll / jnp.maximum(frames, 1.0))
        self.em_fn = TR.make_em_fn(cfg)
        self.it, self.diag = 0, None

    def step(self):
        st = self.stats
        with _span("bench.iteration"):
            self.model, self.diag = self.em_fn(self.model, st.n, st.f, st.S)
            jax.block_until_ready(self.model)
        self.it += 1


@dataclass
class State:
    cell: object
    cfg: object
    realign: bool
    inputs: BD.Inputs
    feats: jax.Array
    tvar0: jax.Array
    loop: object = None
    prog: dict = field(default_factory=dict)
    window_its: int = 0
    final_finite: Optional[bool] = None


def shapes(state: State) -> dict:
    c = state.cell.config
    U, F = int(c["train_utterances"]), int(c["frames_per_utt"])
    return {"C": int(c["n_components"]), "D": int(c["feat_dim"]),
            "R": int(c["ivector_dim"]), "K": int(c["posterior_top_k"]),
            "U": U, "F": U * F, "chunk": int(state.cfg.estep_chunk),
            "realign": state.realign, "update_sigma": bool(c["update_sigma"])}


def _readings(state: State, T, Sigma, prior) -> dict:
    """The norms of the change after the first steps (shared by both
    sides): the leaves the M-step writes, T (as T T^T, which a rotation
    of the i-vector space leaves alone) and Sigma, and, logged but not
    compared, the prior and the realigned UBM means."""
    inp = state.inputs
    out = {"tvar": CK.norm(_tvar(T) - state.tvar0),
           "sigma": CK.norm(Sigma - inp.covs),
           "prior": CK.norm(prior - inp.prior)}
    if state.realign:
        out["ubm_means"] = CK.norm(REF.realigned_means(
            REF.Model(T, Sigma, prior)) - inp.means)
    return out


def prepare(cell, seed: int, seconds: float, log) -> State:
    from repro.core import tvm as TV
    from repro.core import ubm as UB
    c, traffic = cell.config, cell.traffic
    cfg = ivector_config(c, traffic)
    U, F = int(c["train_utterances"]), int(c["frames_per_utt"])
    gen = BD.generator(c, U, int(traffic["utts_per_speaker"]))
    with _span("bench.make_inputs"):
        inp = BD.inputs(gen, seed, cfg.ivector_dim, cfg.prior_offset)
        feats = BD.utterances(gen, seed, F, U)
        jax.block_until_ready((inp, feats))
    log(f"corpus {U} utterances x {F} frames, C={cfg.n_components} "
        f"D={cfg.feat_dim} R={cfg.ivector_dim} K={cfg.posterior_top_k}; "
        f"realign={traffic['realign']} estep_chunk={cfg.estep_chunk}")
    state = State(cell, cfg, bool(traffic["realign"]), inp, feats,
                  _tvar(inp.T))
    model = TV.TVModel(T=inp.T, Sigma=inp.covs, prior=inp.prior,
                       means=inp.means, formulation=cfg.formulation)
    ubm = UB.FullGMM(inp.weights, inp.means, inp.covs)
    loop = (_RealignLoop if state.realign else _AtRestLoop)(
        cfg, model, ubm, feats)
    state.loop = loop
    logliks = []
    for k in range(FIRST_STEPS):
        t0 = time.perf_counter()
        loop.step()
        log(f"iteration {k + 1} (set-up) {time.perf_counter() - t0:.3f}s")
        if state.realign:
            logliks.append(float(loop.diag["avg_loglik"]))
            if k == 0:
                tot = loop.totals
                state.prog["stats"] = {"n": CK.norm(tot.n),
                                       "f": CK.norm(tot.f),
                                       "S": CK.norm(tot.ss)}
    if not state.realign:
        logliks = [loop.avg_loglik]
        st = loop.stats
        state.prog["stats"] = {"n": CK.norm(st.n), "f": CK.norm(st.f),
                               "S": CK.norm(st.S)}
    m = loop.model
    state.prog["loglik"] = logliks
    state.prog["change"] = _readings(state, m.T, m.Sigma, m.prior)
    return state


def window(state: State, seconds: float, log) -> dict:
    U = int(state.cell.config["train_utterances"])
    times = []
    start = last = time.perf_counter()
    while last - start < seconds:
        state.loop.step()
        now = time.perf_counter()
        times.append(now - last)
        last = now
    wall = last - start
    state.window_its = len(times)
    log("window iterations (s): " + " ".join(f"{t:.4f}" for t in times))
    return {"em_utts_per_s": len(times) * U / wall, "attempted": len(times)}


def traced_window(state: State, log) -> dict:
    n = int(state.cell.traffic.get("trace_iterations", 2))
    start = time.perf_counter()
    for _ in range(n):
        state.loop.step()
    wall = time.perf_counter() - start
    state.window_its = n
    log(f"traced {n} iterations in {wall:.3f}s")
    return {"iterations": n, "wall_s": wall, "attempted": n}


def release(state: State):
    """Drops the program's state (after its final model is checked)."""
    m = state.loop.model
    state.final_finite = bool(all(
        bool(jnp.isfinite(a).all()) for a in (m.T, m.Sigma, m.prior)))
    state.loop = None
    gc.collect()


def reference_readings(state: State, prec=REF.HIGHEST, log=print) -> dict:
    """The reference's readings of the first steps, from the seed's
    inputs alone."""
    c, inp = state.cell.config, state.inputs
    K, floor = int(c["posterior_top_k"]), float(c["posterior_floor"])
    model = REF.Model(inp.T, inp.covs, inp.prior)
    ubm = REF.UBM(inp.weights, inp.means, inp.covs)
    upd = bool(c["update_sigma"])
    logliks, out = [], {}
    t0 = time.perf_counter()
    if state.realign:
        for k in range(FIRST_STEPS):
            if k:
                ubm = ubm._replace(means=REF.realigned_means(model))
            st, S = REF.align_stats(ubm, state.feats, top_k=K, floor=floor,
                                    prec=prec, second_order=upd)
            logliks.append(st.loglik / st.frames)
            if k == 0:
                out["stats"] = {"n": CK.norm(jnp.sum(st.n, 0)),
                                "f": CK.norm(jnp.sum(st.f, 0)),
                                "S": CK.norm(S)}
            model = REF.em_iteration(model, st, S, update_sigma=upd,
                                     prec=prec)
            del st, S
    else:
        st, S = REF.align_stats(ubm, state.feats, top_k=K, floor=floor,
                                prec=prec, second_order=upd)
        logliks.append(st.loglik / st.frames)
        out["stats"] = {"n": CK.norm(st.n), "f": CK.norm(st.f),
                        "S": CK.norm(S)}
        for _ in range(FIRST_STEPS):
            model = REF.em_iteration(model, st, S, update_sigma=upd,
                                     prec=prec)
        del st, S
    out["loglik"] = logliks
    out["change"] = _readings(state, model.T, model.Sigma, model.prior)
    log(f"reference ({prec}) {time.perf_counter() - t0:.3f}s")
    return out


# the change compared: T T^T and Sigma. The prior and the realigned
# means swing between sound runs as much as under the control (PERF.md):
# minimum divergence sets the prior's norm from the inverse square root
# of the i-vector covariance's spectrum, and the means are T h.
CHANGE_LEAVES = ("tvar", "sigma")


def numbers(prog: dict, ref: dict, log=print) -> dict:
    """The numbers compared: the first iteration's loss (relative gap),
    the first M-step's statistics and the change after the first steps
    (each by the worst leaf's gap of norms)."""
    stats, s_leaf = CK.worst_leaf(prog["stats"], ref["stats"])
    pick = lambda d: {k: d[k] for k in CHANGE_LEAVES}
    change, c_leaf = CK.worst_leaf(pick(prog["change"]), pick(ref["change"]))
    log(f"program {prog}")
    log(f"reference {ref}")
    log(f"worst leaves: stats {s_leaf}, change {c_leaf}")
    return {"loss": CK.worst_relative(prog["loglik"][:1], ref["loglik"][:1]),
            "stats": stats, "change": change}


def check(state: State, log) -> tuple:
    """(numbers, failed): the program's first steps against the
    reference's; the window's iterations fail when its final model is
    not finite."""
    ref = reference_readings(state, REF.HIGHEST, log)
    nums = numbers(state.prog, ref, log)
    failed = 0 if state.final_finite else max(state.window_its, 1)
    return nums, failed
