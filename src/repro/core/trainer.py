"""TVMTrainer: the paper's §3.2 five-step training loop, jitted end-to-end,
with every Fig.-2/3 variant switchable:

  formulation   'standard' | 'augmented'
  min_divergence / update_sigma / realign_interval / ubm_update

One EM iteration is ONE streamed pass through the StatsEngine
(core/engine.py): utterance chunks scan through alignment -> Baum-Welch
stats -> TVM E-step accumulation, so nothing frame-resident outlives a
chunk, then M-step + min-divergence. Because alignment is re-derived from
the UBM every pass (the paper's GPU-speed premise), realignment is just a
UBM write-back between iterations — `ubm_update` selects how much of the
UBM it refreshes ('means' = the paper's step 5; 'full' also refreshes
weights and covariances from the same streamed statistics).

The sharded mesh is the default substrate (DESIGN.md §11): every entry
point resolves a mesh (``mesh`` argument > ``cfg.mesh`` > the auto local
mesh from `launch/mesh.make_default_mesh` — a 1-device mesh on a laptop)
and runs every macro-step — alignment, TVM E-step, UBM refresh totals —
through the engine's mesh mode, so `ubm_update` and `realign` work
identically at N devices. ``macro_batch`` streams each iteration through
the double-buffered `data.speech.prefetch_to_device` iterator instead of
one resident batch.

Long runs checkpoint through `checkpoint/manager.py` (``ckpt_dir``):
model + UBM + last-pass sufficient stats are saved every
``ckpt_interval`` iterations and restored transparently on restart.
`train_supervised` wraps the same macro-step in
`distributed/fault_tolerance.run_supervised` for elastic resume: an
injected failure costs exactly one macro-step and the restart resumes
bit-exactly from the last checkpoint.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as CM
from repro.configs.ivector_tvm import IVectorConfig
from repro.core import engine as EN
from repro.core import guardrails as GR
from repro.core import stats as ST
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.data import speech as DS
from repro.distributed import fault_tolerance as FT
from repro.launch import mesh as MS

f32 = jnp.float32


@dataclass
class TrainState:
    model: TV.TVModel
    ubm: U.FullGMM
    iteration: int = 0


def _spec(cfg: IVectorConfig, second_order: bool) -> EN.EngineSpec:
    return EN.EngineSpec(
        n_components=cfg.n_components, top_k=cfg.posterior_top_k,
        floor=cfg.posterior_floor,
        second_order="full" if second_order else None,
        chunk=cfg.estep_chunk, rescore=cfg.rescore)


def _resolve_mesh(cfg: IVectorConfig, mesh, n_utts: int):
    """The trainer-side mesh default: explicit argument > ``cfg.mesh`` >
    auto local mesh. Always returns a concrete Mesh (possibly 1-device)."""
    return MS.resolve_mesh(mesh if mesh is not None else cfg.mesh,
                           n_utts=n_utts, n_components=cfg.n_components)


def _data_sharding(mesh, ndim: int):
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    return NamedSharding(mesh, P(data_axes, *([None] * (ndim - 1))))


def _place(mesh, feats, mask):
    """Shard the batch over the mesh's data axes ONCE per call site, so
    per-iteration jit calls never re-shard host-resident features."""
    if mesh is None or mesh.size == 1:
        return feats, mask
    feats = jax.device_put(feats, _data_sharding(mesh, 3))
    if mask is not None:
        mask = jax.device_put(mask, _data_sharding(mesh, 2))
    return feats, mask


def _align_and_stats(cfg: IVectorConfig, ubm: U.FullGMM, feats,
                     second_order: bool, mask=None, mesh=None) -> ST.BWStats:
    """feats: [U, F, D] -> BWStats (n [U,C], f [U,C,D], S [C,D,D]|None)
    via the engine's streamed chunk body. ``mask`` ([U, F], optional)
    marks valid frames; padding contributes exactly nothing."""
    return EN.stream_bw(_spec(cfg, second_order), EN.pack_ubm(ubm),
                        feats, mask, mesh=mesh)[0]


@functools.lru_cache(maxsize=64)
def make_stats_fn(cfg: IVectorConfig, mesh=None):
    return jax.jit(lambda ubm, feats, mask=None: _align_and_stats(
        cfg, ubm, feats, cfg.update_sigma, mask=mask, mesh=mesh))


@functools.lru_cache(maxsize=64)
def make_stats_ll_fn(cfg: IVectorConfig, mesh=None):
    """Like make_stats_fn but also returns the (loglik, frames) aux."""
    spec = _spec(cfg, cfg.update_sigma)
    return jax.jit(lambda ubm, feats, mask=None: EN.stream_bw(
        spec, EN.pack_ubm(ubm), feats, mask, mesh=mesh))


def _finish_iteration(cfg: IVectorConfig, model: TV.TVModel,
                      tot: EN.UBMStats, acc: TV.EMAccum):
    """M-step + min-divergence from one pass's merged accumulators — the
    shared tail of the fused iteration, the macro-batched iteration, and
    the supervised step (one implementation, three drivers)."""
    S_m = None
    if cfg.update_sigma:
        S_m = tot.ss
        if model.formulation == "standard":
            S_m = ST.center(ST.BWStats(tot.n[None], tot.f[None],
                                       tot.ss), model.means).S
    diag = {"mean_phi_norm": jnp.linalg.norm(TV.mean_phi(model, acc)),
            "avg_loglik": tot.loglik / jnp.maximum(tot.frames, 1.0)}
    model = TV.m_step(model, acc, S_m, cfg.update_sigma)
    if cfg.min_divergence:
        model = TV.min_divergence(model, acc)
    return model, diag


@functools.lru_cache(maxsize=64)
def make_em_fn(cfg: IVectorConfig):
    """(model, stats) -> (new_model, diagnostics); one EM iteration from
    precomputed Baum-Welch statistics (benchmarks and stats-at-rest use;
    the training loop streams stats and E-step fused — make_iter_fn)."""

    def em_iter(model: TV.TVModel, n, f, S_tot):
        if model.formulation == "standard":
            st = ST.center(ST.BWStats(n, f, S_tot), model.means)
            n_, f_, S_ = st.n, st.f, st.S
        else:
            n_, f_, S_ = n, f, S_tot
        pre = TV.precompute(model, estep=cfg.estep)
        acc = TV.em_accumulate_scan(model, pre, n_, f_,
                                    chunk=cfg.estep_chunk,
                                    estep_dtype=cfg.estep_dtype)
        diag = {"mean_phi_norm": jnp.linalg.norm(TV.mean_phi(model, acc))}
        model = TV.m_step(model, acc, S_ if cfg.update_sigma else None,
                          cfg.update_sigma)
        if cfg.min_divergence:
            model = TV.min_divergence(model, acc)
        return model, diag

    return jax.jit(em_iter)


def _iter_accums(cfg: IVectorConfig, spec: EN.EngineSpec,
                 model: TV.TVModel, feat_dim: int):
    pre = EN.pin(TV.precompute(model, estep=cfg.estep))
    center = model.means if model.formulation == "standard" else None
    return (EN.TotalsAccum(spec, feat_dim),
            EN.TVMAccum(model, pre, center_means=center,
                        estep_dtype=cfg.estep_dtype))


@functools.lru_cache(maxsize=64)
def make_iter_fn(cfg: IVectorConfig, mesh=None):
    """(model, ubm, feats, mask) -> (new_model, totals, diagnostics).

    One fused streamed EM iteration: the engine scans utterance chunks
    through the canonical chunk body feeding TWO accumulators — global
    sufficient stats (TotalsAccum: the Σ-update and the UBM refresh) and
    the TVM E-step (TVMAccum) — then M-step + min-divergence. ``totals``
    (engine.UBMStats) is what `refresh_ubm` consumes at realignment.
    With a >1-device ``mesh`` the whole pass runs in the engine's
    shard_map mode; the M-step consumes the exit-psummed accumulators.
    """
    track_S = cfg.update_sigma or cfg.ubm_update == "full"
    spec = _spec(cfg, track_S)

    def iter_fn(model: TV.TVModel, ubm: U.FullGMM, feats, mask=None):
        pack = EN.pack_ubm(ubm)
        accums = _iter_accums(cfg, spec, model, feats.shape[-1])
        (tot, acc), _ = EN.stream(spec, pack, feats, mask, accums,
                                  mesh=mesh)
        model, diag = _finish_iteration(cfg, model, tot, acc)
        return model, tot, diag

    return jax.jit(iter_fn)


@functools.lru_cache(maxsize=64)
def make_batch_accum_fn(cfg: IVectorConfig, mesh=None):
    """(model, ubm, feats_b, mask_b) -> (UBMStats, EMAccum) for ONE
    macro-batch — the per-batch unit the prefetch-consuming loop merges
    (`merge_totals` / `tvm.merge_accums`) before `make_mstep_fn`."""
    track_S = cfg.update_sigma or cfg.ubm_update == "full"
    spec = _spec(cfg, track_S)

    def fn(model, ubm, feats_b, mask_b=None):
        pack = EN.pack_ubm(ubm)
        accums = _iter_accums(cfg, spec, model, feats_b.shape[-1])
        (tot, acc), _ = EN.stream(spec, pack, feats_b, mask_b, accums,
                                  mesh=mesh)
        return tot, acc

    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def make_mstep_fn(cfg: IVectorConfig):
    return jax.jit(lambda model, tot, acc:
                   _finish_iteration(cfg, model, tot, acc))


def merge_totals(a: EN.UBMStats, b: EN.UBMStats) -> EN.UBMStats:
    """Associative merge of finalized sufficient statistics (None ss
    merges with None)."""
    return jax.tree.map(jnp.add, a, b)


# ---------------------------------------------------------------------------
# Realignment write-back (§3.2 step 5, generalized)
# ---------------------------------------------------------------------------


def refresh_ubm(cfg: IVectorConfig, model: TV.TVModel, ubm: U.FullGMM,
                totals: Optional[EN.UBMStats], *,
                update_weights: Optional[bool] = None,
                update_covs: Optional[bool] = None) -> U.FullGMM:
    """UBM write-back for realignment. 'means' rewrites only the means
    from the T column; 'full' additionally refreshes the weights and the
    (PSD-floored) covariances from the previous iteration's streamed
    sufficient statistics. With both refresh flags disabled, 'full'
    degenerates to exactly the 'means' behaviour.
    """
    full = cfg.ubm_update == "full"
    update_weights = full if update_weights is None else update_weights
    update_covs = full if update_covs is None else update_covs
    means = TV.updated_ubm_means(model)
    weights, covs = ubm.weights, ubm.covs
    if update_weights:
        weights = U.renormalised_weights(totals.n)
    if update_covs:
        n_safe = jnp.maximum(totals.n, 1e-6)
        fbar = totals.f / n_safe[:, None]
        covs = (totals.ss / n_safe[:, None, None]
                - means[:, :, None] * fbar[:, None, :]
                - fbar[:, :, None] * means[:, None, :]
                + means[:, :, None] * means[:, None, :])
        covs = U.psd_floor(covs)
    return U.FullGMM(weights, means, covs)


def _realign_due(cfg: IVectorConfig, it: int, model: TV.TVModel) -> bool:
    return (cfg.realign_interval > 0 and it > 0
            and it % cfg.realign_interval == 0
            and model.formulation == "augmented"
            and cfg.ubm_update != "none")


# ---------------------------------------------------------------------------
# Training loop + extraction
# ---------------------------------------------------------------------------


def _ckpt_tree(state: TrainState, totals: Optional[EN.UBMStats]):
    """Fixed-structure checkpoint pytree (placeholder zeros keep the
    manifest stable whether or not second-order stats are tracked)."""
    C, D = state.ubm.means.shape
    n = jnp.zeros((C,), f32)
    f = jnp.zeros((C, D), f32)
    ss = jnp.zeros((C, D, D), f32)
    if totals is not None:
        n, f = totals.n, totals.f
        if totals.ss is not None:
            ss = totals.ss
    return {"model": state.model, "ubm": state.ubm,
            "n": n, "f": f, "ss": ss}


def train(cfg: IVectorConfig, ubm: U.FullGMM, feats,
          n_iters: Optional[int] = None, key=None, callback=None,
          mask=None, ckpt_dir=None, ckpt_interval: int = 1,
          ckpt_keep: int = 3, mesh=None, macro_batch: int = 0,
          prefetch: int = 2) -> TrainState:
    """Full training loop on in-memory features [U, F, D].

    ``mask`` ([U, F], optional) marks valid frames (ragged batches train
    exactly). With ``ckpt_dir`` the loop saves model + UBM + last-pass
    stats every ``ckpt_interval`` iterations and transparently resumes
    from the latest checkpoint on restart (bit-identical trajectory).

    ``mesh``: a `jax.sharding.Mesh`, a ``(data, model)`` tuple, or None
    (``cfg.mesh``, else the auto local mesh) — the substrate every
    macro-step runs on. A 1-device mesh is bit-identical to the
    historical single-device path; a larger mesh reproduces it up to the
    exit-psum summation order (DESIGN.md §11). ``macro_batch`` > 0
    streams each iteration through `data.speech.prefetch_to_device` in
    ``macro_batch``-utterance slices (double-buffered H2D) instead of one
    resident device batch.
    """
    # the fixed default seed is the documented reproducibility contract
    # repro-check: disable=SRC002
    key = key if key is not None else jax.random.PRNGKey(0)
    model = TV.init_model(key, ubm.means, ubm.covs, cfg.ivector_dim,
                          cfg.formulation, cfg.prior_offset)
    state = TrainState(model=model, ubm=ubm)
    n_iters = n_iters or cfg.n_iters
    mesh = _resolve_mesh(cfg, mesh, feats.shape[0])
    batched = bool(macro_batch) and 0 < macro_batch < feats.shape[0]
    if not batched:
        feats, mask = _place(mesh, feats, mask)

    prev: Optional[EN.UBMStats] = None
    start = 0
    mgr = None
    if ckpt_dir is not None:
        mgr = CM.CheckpointManager(ckpt_dir, save_interval=ckpt_interval,
                                   keep=ckpt_keep)
        if mgr.has_checkpoint():
            # newest VERIFIED checkpoint: a torn/tampered latest write
            # falls back instead of resuming from garbage (DESIGN.md §13)
            tree, step, _ = mgr.restore_latest_verified(
                _ckpt_tree(state, None))
            state.model = tree["model"]
            state.ubm = tree["ubm"]
            prev = EN.UBMStats(tree["n"], tree["f"], tree["ss"],
                               jnp.zeros((), f32), jnp.zeros((), f32))
            start = min(int(step), n_iters)
            state.iteration = start

    realign_possible = (cfg.realign_interval > 0
                        and cfg.ubm_update != "none"
                        and cfg.formulation == "augmented")

    if batched:
        return _train_batched(cfg, state, feats, mask, n_iters, start,
                              prev, mgr, callback, mesh, macro_batch,
                              prefetch, realign_possible)

    # When realignment can never fire the UBM is static, so alignment is
    # computed ONCE and the Baum-Welch stats are reused across EM
    # iterations; the fused per-iteration streaming pass only runs when a
    # write-back can actually change the alignments.
    if realign_possible:
        iter_fn = make_iter_fn(cfg, mesh)
        for it in range(start, n_iters):
            if _realign_due(cfg, it, state.model):
                state.ubm = refresh_ubm(cfg, state.model, state.ubm, prev)
            state.model, prev, diag = iter_fn(state.model, state.ubm,
                                              feats, mask)
            state.iteration = it + 1
            if mgr is not None:
                mgr.maybe_save(state.iteration, _ckpt_tree(state, prev),
                               extra={"iteration": state.iteration})
            if callback is not None:
                callback(state, diag)
        return state

    st, (ll, frames) = make_stats_ll_fn(cfg, mesh)(state.ubm, feats, mask)
    avg_ll = ll / jnp.maximum(frames, 1.0)
    em_fn = make_em_fn(cfg)
    for it in range(start, n_iters):
        state.model, diag = em_fn(state.model, st.n, st.f, st.S)
        state.iteration = it + 1
        if mgr is not None:
            mgr.maybe_save(state.iteration, _ckpt_tree(state, None),
                           extra={"iteration": state.iteration})
        if callback is not None:
            callback(state, {**diag, "avg_loglik": avg_ll})
    return state


def _train_batched(cfg, state, feats, mask, n_iters, start, prev, mgr,
                   callback, mesh, macro_batch, prefetch,
                   realign_possible):
    """Per-iteration loop over prefetched macro-batches: each EM pass
    streams ``macro_batch``-utterance slices through the engine (next
    slice's H2D overlapping the current slice's compute), merging the
    per-batch accumulators; one M-step per full pass."""
    sharding = _data_sharding(mesh, 3) if mesh.size > 1 else None
    msharding = _data_sharding(mesh, 2) if mesh.size > 1 else None
    batch_fn = make_batch_accum_fn(cfg, mesh)
    mstep_fn = make_mstep_fn(cfg)
    for it in range(start, n_iters):
        if realign_possible and _realign_due(cfg, it, state.model):
            state.ubm = refresh_ubm(cfg, state.model, state.ubm, prev)
        tot = acc = None
        for fb, mb in DS.prefetch_to_device(
                DS.iter_batches(feats, mask, macro_batch), size=prefetch,
                sharding=(sharding, msharding)):
            t, a = batch_fn(state.model, state.ubm, fb, mb)
            tot = t if tot is None else merge_totals(tot, t)
            acc = a if acc is None else TV.merge_accums(acc, a)
        state.model, diag = mstep_fn(state.model, tot, acc)
        prev = tot
        state.iteration = it + 1
        if mgr is not None:
            mgr.maybe_save(state.iteration, _ckpt_tree(state, prev),
                           extra={"iteration": state.iteration})
        if callback is not None:
            callback(state, diag)
    return state


class _StepFeed:
    """Step-indexed feed for `fault_tolerance.run_supervised`: the batch
    is the (already device-resident) full macro-batch every step, so the
    data cursor is just the step counter — deterministic, resumable.
    ``gain`` is a float leaf the chaos NaN-batch injector can poison; the
    step multiplies features by it (exactly 1.0 normally — bit-inert)."""

    def __init__(self):
        self.step = 0

    def next(self):
        b = {"it": np.asarray(self.step, np.int64),
             "gain": np.asarray(1.0, np.float32)}
        self.step += 1
        return b

    def state(self):
        return {"step": self.step}

    def restore(self, st):
        self.step = int(st.get("step", 0))


def train_supervised(cfg: IVectorConfig, ubm: U.FullGMM, feats,
                     n_iters: Optional[int] = None, key=None, mask=None,
                     ckpt_dir=None, ckpt_keep: int = 3,
                     ckpt_keep_every: int = 0, mesh=None,
                     fail_at=None, max_restarts: Optional[int] = None,
                     policy: Optional[FT.RetryPolicy] = None,
                     guardrail=None, chaos: Optional[FT.Chaos] = None):
    """Elastic training: the SAME macro-step as `train` (fused streamed
    EM pass + realignment write-back), driven by
    `distributed/fault_tolerance.run_supervised` with a checkpoint every
    macro-step. An `InjectedFailure` (``fail_at(step, attempt)``) lands in
    the worst-case window — after a step, before its checkpoint — so a
    failure costs exactly that one macro-step and the restart resumes
    bit-exactly from the previous one (f32 npz round-trips exactly;
    alignment is a pure function of the restored model/UBM).

    Resilience policy (DESIGN.md §13) comes from ``cfg`` unless
    overridden: ``policy`` defaults to the config's restart/backoff/
    deadline knobs, ``guardrail`` to `core.guardrails.make_guardrail`
    when ``cfg.guardrail`` is set, and the safety-ladder escalation
    (``cfg.escalate_after`` consecutive rollbacks at one step → next
    `guardrails.escalation_ladder` config) rebuilds the jitted step
    in-place. ``chaos`` injects drill faults.

    Returns (TrainState, SupervisorReport).
    """
    if ckpt_dir is None:
        raise ValueError("train_supervised requires ckpt_dir")
    # the fixed default seed is the documented reproducibility contract
    # repro-check: disable=SRC002
    key = key if key is not None else jax.random.PRNGKey(0)
    n_steps = n_iters or cfg.n_iters
    mesh = _resolve_mesh(cfg, mesh, feats.shape[0])
    feats, mask = _place(mesh, feats, mask)

    def init_state_fn():
        model = TV.init_model(key, ubm.means, ubm.covs, cfg.ivector_dim,
                              cfg.formulation, cfg.prior_offset)
        return _ckpt_tree(TrainState(model=model, ubm=ubm), None)

    def make_step_fn(c: IVectorConfig):
        iter_fn = make_iter_fn(c, mesh)

        def step_fn(tree, batch):
            it = int(batch["it"])
            model, gmm = tree["model"], tree["ubm"]
            prev = EN.UBMStats(tree["n"], tree["f"], tree["ss"],
                               jnp.zeros((), f32), jnp.zeros((), f32))
            if _realign_due(c, it, model):
                gmm = refresh_ubm(c, model, gmm, prev)
            # gain is exactly 1.0 outside chaos drills: x * 1.0 is
            # bit-exact, and a poisoned (NaN) gain floods the features so
            # the guardrail trips on the resulting state
            model, tot, diag = iter_fn(model, gmm,
                                       feats * batch["gain"], mask)
            return _ckpt_tree(TrainState(model=model, ubm=gmm), tot), diag

        return step_fn

    if policy is None:
        policy = FT.RetryPolicy(
            max_restarts=(cfg.max_restarts if max_restarts is None
                          else max_restarts),
            backoff=cfg.retry_backoff, step_deadline=cfg.step_deadline,
            escalate_after=cfg.escalate_after)
    if guardrail is None and cfg.guardrail:
        guardrail = GR.make_guardrail(GR.GuardrailConfig(
            loglik_drop_tol=cfg.guardrail_loglik_drop))

    ladder = iter(GR.escalation_ladder(cfg))
    escalated: list = []

    def on_escalate():
        c2 = next(ladder, None)
        if c2 is None:
            return None
        escalated.append(c2)
        return make_step_fn(c2)

    ckpt = CM.CheckpointManager(ckpt_dir, save_interval=1, keep=ckpt_keep,
                                keep_every=ckpt_keep_every)
    report = FT.run_supervised(
        init_state_fn=init_state_fn, train_step_fn=make_step_fn(cfg),
        data_factory=_StepFeed, n_steps=n_steps, ckpt=ckpt,
        fail_at=fail_at, policy=policy, guardrail=guardrail,
        on_escalate=on_escalate, chaos=chaos)
    tree, _, _ = ckpt.restore_latest_verified(init_state_fn())
    state = TrainState(model=tree["model"], ubm=tree["ubm"],
                       iteration=report.final_step)
    return state, report


def extract(cfg: IVectorConfig, state: TrainState, feats,
            mask=None, mesh=None) -> jax.Array:
    """i-vectors for [U, F, D] features using the trained model + UBM.

    ``mask`` ([U, F], optional) marks valid frames so padded variable-
    length batches extract identically to their unpadded utterances.
    ``mesh`` shards the stats pass like `train` (per-utterance n/f are
    bit-identical across meshes; see DESIGN.md §11).
    """
    mesh = _resolve_mesh(cfg, mesh, feats.shape[0])
    feats, mask = _place(mesh, feats, mask)
    stats_fn = make_stats_fn(cfg, mesh)
    st = stats_fn(state.ubm, feats, mask)
    model = state.model
    if model.formulation == "standard":
        stc = ST.center(ST.BWStats(st.n, st.f, None), model.means)
        n_, f_ = stc.n, stc.f
    else:
        n_, f_ = st.n, st.f
    pre = TV.precompute(model, estep=cfg.estep)
    return TV.extract_ivectors(model, pre, n_, f_,
                               estep_dtype=cfg.estep_dtype)
