"""Chaos drills for the resilience subsystem (DESIGN.md §13).

One drill per fault class, each proving automatic recovery:

  * injected host loss (worst-case window: after step, before checkpoint)
    and mid-step device loss -> resume costs <= 1 macro-step, bit-exact;
  * NaN batch -> guardrail rollback to the last good checkpoint,
    bit-exact final trajectory;
  * corrupted latest checkpoint -> restore falls back to the newest
    VERIFIED one, costing <= 1 retained interval, bit-exact;
  * straggler blowing the per-step deadline -> attempt abandoned,
    restart, bit-exact;
  * runtime fused-kernel failure in serving -> session demotes
    fused -> sparse -> dense and keeps answering (bitwise equal to a
    dense session), never dies.

Plus the supporting contracts: checkpoint sha256/torn-write detection and
retention anchors, deterministic retry backoff, the step-0 eager
checkpoint (restart-before-first-interval bug), safety-ladder
escalation, `shard_for_host` reassignment, and bundle tamper refusal.
"""
import dataclasses

import numpy as np
import pytest

import jax

from repro.api.bundle import Bundle
from repro.checkpoint import manager as CM
from repro.configs.ivector_tvm import SMOKE
from repro.core import guardrails as GR
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.core.engine import RESCORE_LADDER, degrade_rescore
from repro.distributed import fault_tolerance as FT
from repro.serving import (AdmissionQueue, IVectorExtractor, QueueFull,
                           ServingConfig)

CFG = SMOKE.with_overrides(n_iters=3)
KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    C, D = CFG.n_components, CFG.feat_dim
    feats = rng.standard_normal((8, 32, D)).astype(np.float32)
    gmm = U.FullGMM(np.full((C,), 1.0 / C, np.float32),
                    rng.standard_normal((C, D)).astype(np.float32),
                    np.stack([np.eye(D, dtype=np.float32)] * C))
    return feats, gmm


@pytest.fixture(scope="module")
def reference(setup, tmp_path_factory):
    """Uninterrupted supervised run: the trajectory every drill must
    reproduce bit-for-bit after recovery."""
    feats, gmm = setup
    d = tmp_path_factory.mktemp("ref")
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY, ckpt_dir=d)
    assert rep.n_restarts == 0 and not rep.faults
    return state


def _assert_bit_exact(state, reference):
    np.testing.assert_array_equal(np.asarray(state.model.T),
                                  np.asarray(reference.model.T))
    np.testing.assert_array_equal(np.asarray(state.model.Sigma),
                                  np.asarray(reference.model.Sigma))


# ---------------------------------------------------------------------------
# Training drills: one per fault class
# ---------------------------------------------------------------------------


def test_chaos_drill_host_loss_bit_exact(setup, reference, tmp_path):
    """Host lost in the worst-case window (step done, checkpoint not):
    exactly one restart, <= 1 macro-step recomputed, bit-exact result."""
    feats, gmm = setup
    chaos = FT.Chaos(fail_at=lambda s, a: s == 2 and a == 0)
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY,
                                     ckpt_dir=tmp_path, chaos=chaos)
    assert rep.n_restarts == 1
    assert [f["type"] for f in rep.faults] == ["InjectedFailure"]
    assert rep.faults[0]["recovery_s"] is not None
    _assert_bit_exact(state, reference)


def test_chaos_drill_device_loss_mid_step(setup, reference, tmp_path):
    """Device lost MID-step: the in-flight update is discarded and the
    step recomputes from the checkpoint — still <= 1 macro-step."""
    feats, gmm = setup
    chaos = FT.Chaos(device_loss_at=lambda s, a: s == 1 and a == 0)
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY,
                                     ckpt_dir=tmp_path, chaos=chaos)
    assert rep.n_restarts == 1
    _assert_bit_exact(state, reference)


def test_chaos_drill_nan_batch_guardrail_rollback(setup, reference,
                                                  tmp_path):
    """A NaN batch floods the step's state; the guardrail catches it
    BEFORE the checkpoint (a bad state never reaches disk) and rolls
    back; the retried step is clean and the trajectory is bit-exact."""
    feats, gmm = setup
    chaos = FT.Chaos(poison_at=lambda s, a: s == 1 and a == 0)
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY,
                                     ckpt_dir=tmp_path, chaos=chaos)
    assert rep.rollbacks == 1
    assert [f["type"] for f in rep.faults] == ["GuardrailViolation"]
    # the poisoned state was never checkpointed: every on-disk step
    # still verifies
    ckpt = CM.CheckpointManager(tmp_path)
    for s in ckpt.steps():
        ckpt.verify_step(s)
    _assert_bit_exact(state, reference)


def test_chaos_drill_corrupted_checkpoint(setup, reference, tmp_path):
    """The newest checkpoint is corrupted on disk; the restart walks back
    to the newest VERIFIED one — cost <= 1 retained interval (here one
    step, recomputed deterministically), bit-exact."""
    feats, gmm = setup
    chaos = FT.Chaos(corrupt_ckpt_at=lambda s, a: s == 2 and a == 0,
                     fail_at=lambda s, a: s == 3 and a == 0)
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY,
                                     ckpt_dir=tmp_path, chaos=chaos)
    assert rep.skipped_corrupt == [2]
    assert rep.n_restarts == 1
    _assert_bit_exact(state, reference)


def test_chaos_drill_straggler_deadline(setup, reference, tmp_path):
    """An injected straggler delay blows the per-attempt step deadline:
    the attempt is killed (DeadlineExceeded), the restart is clean."""
    feats, gmm = setup
    policy = FT.RetryPolicy(max_restarts=5, step_deadline=60.0)
    chaos = FT.Chaos(
        delay_at=lambda s, a: 120.0 if (s == 1 and a == 0) else 0.0)
    state, rep = TR.train_supervised(CFG, gmm, feats, key=KEY,
                                     ckpt_dir=tmp_path, policy=policy,
                                     chaos=chaos)
    assert [f["type"] for f in rep.faults] == ["DeadlineExceeded"]
    _assert_bit_exact(state, reference)


def test_chaos_restart_budget_exhausted(setup, tmp_path):
    """A fault on EVERY attempt exhausts max_restarts and propagates —
    the supervisor never spins forever."""
    feats, gmm = setup
    with pytest.raises(FT.InjectedFailure):
        TR.train_supervised(CFG, gmm, feats, key=KEY, ckpt_dir=tmp_path,
                            max_restarts=2,
                            chaos=FT.Chaos(fail_at=lambda s, a: s == 1))


# ---------------------------------------------------------------------------
# Guardrail unit behaviour
# ---------------------------------------------------------------------------


def _good_tree(setup):
    feats, gmm = setup
    model = TV.init_model(KEY, gmm.means, gmm.covs, CFG.ivector_dim,
                          CFG.formulation, CFG.prior_offset)
    return TR._ckpt_tree(TR.TrainState(model=model, ubm=gmm), None)


def test_guardrail_passes_good_state(setup):
    assert GR.check_state(_good_tree(setup)) == []


def test_guardrail_catches_each_violation(setup):
    tree = _good_tree(setup)
    t = jax.tree.map(lambda x: x, tree)
    t["model"] = dataclasses.replace(
        t["model"], T=np.asarray(t["model"].T).copy() * np.nan)
    assert any("model.T" in v for v in GR.check_state(t))

    t = jax.tree.map(lambda x: x, tree)
    w = np.asarray(t["ubm"].weights).copy()
    w[0] = -0.5
    t["ubm"] = U.FullGMM(w, t["ubm"].means, t["ubm"].covs)
    got = GR.check_state(t)
    assert any("negative" in v for v in got)
    assert any("simplex" in v for v in got)

    t = jax.tree.map(lambda x: x, tree)
    covs = np.asarray(t["ubm"].covs).copy()
    covs[0, 0, 0] = -1.0
    t["ubm"] = U.FullGMM(t["ubm"].weights, t["ubm"].means, covs)
    assert any("ubm.covs" in v for v in GR.check_state(t))

    t = jax.tree.map(lambda x: x, tree)
    t["n"] = np.asarray([-1.0] + [1.0] * (CFG.n_components - 1),
                        np.float32)
    assert any("negative occupancies" in v for v in GR.check_state(t))


def test_guardrail_loglik_watchdog(setup):
    tree = _good_tree(setup)
    ok = GR.check_state(tree, {"avg_loglik": -10.0},
                        {"avg_loglik": -10.2})
    assert ok == []
    bad = GR.check_state(tree, {"avg_loglik": -200.0},
                         {"avg_loglik": -10.0})
    assert any("diverged" in v for v in bad)
    nonfinite = GR.check_state(tree, {"avg_loglik": float("nan")})
    assert any("non-finite" in v for v in nonfinite)


def test_guardrail_hook_resets_on_rollback(setup):
    """make_guardrail carries prev metrics; reset() (called by the
    supervisor on restart) clears the watchdog so the recomputed step is
    not compared against the poisoned attempt's metrics."""
    tree = _good_tree(setup)
    hook = GR.make_guardrail()
    assert hook(tree, {"avg_loglik": -10.0}) == []
    assert any("diverged" in v for v in hook(tree, {"avg_loglik": -999.0}))
    hook.reset()
    assert hook(tree, {"avg_loglik": -999.0}) == []


# ---------------------------------------------------------------------------
# Safety ladder
# ---------------------------------------------------------------------------


def test_guardrail_escalation_ladder_order():
    cfg = SMOKE.with_overrides(estep_dtype="bfloat16", rescore="fused")
    rungs = [(c.estep_dtype, c.rescore) for c in GR.escalation_ladder(cfg)]
    assert rungs == [("float32", "fused"), ("float32", "sparse"),
                     ("float32", "dense")]
    assert GR.escalation_ladder(SMOKE.with_overrides(rescore="dense")) == []
    assert degrade_rescore("dense") is None
    assert [degrade_rescore(m) for m in RESCORE_LADDER[:-1]] == \
        list(RESCORE_LADDER[1:])


def test_guardrail_escalation_swaps_step_fn(tmp_path):
    """Supervisor-level: a step that keeps violating escalates after
    `escalate_after` consecutive rollbacks, and the escalated step fn
    completes the run."""
    ckpt = CM.CheckpointManager(tmp_path, save_interval=1, keep=3)
    calls = {"bad": 0, "good": 0}

    def bad_step(state, batch):
        calls["bad"] += 1
        return {"x": state["x"] * np.nan}, {}

    def good_step(state, batch):
        calls["good"] += 1
        return {"x": state["x"] + 1.0}, {}

    def guardrail(state, metrics):
        x = np.asarray(state["x"])
        return [] if np.isfinite(x).all() else ["x non-finite"]

    rep = FT.run_supervised(
        init_state_fn=lambda: {"x": np.zeros((2,), np.float32)},
        train_step_fn=bad_step, data_factory=TR._StepFeed, n_steps=2,
        ckpt=ckpt, policy=FT.RetryPolicy(max_restarts=6, escalate_after=2),
        guardrail=guardrail, on_escalate=lambda: good_step)
    assert rep.final_step == 2
    assert rep.escalations == 1
    assert rep.rollbacks == 2 and calls["bad"] == 2 and calls["good"] == 2


# ---------------------------------------------------------------------------
# Retry policy
# ---------------------------------------------------------------------------


def test_resilience_retry_backoff_deterministic():
    p = FT.RetryPolicy(backoff=0.5, backoff_cap=4.0, jitter=0.25)
    d = [p.delay(k) for k in (1, 2, 3, 4, 5, 6)]
    assert d == [p.delay(k) for k in (1, 2, 3, 4, 5, 6)]  # deterministic
    base = [0.5, 1.0, 2.0, 4.0, 4.0, 4.0]                 # exp, capped
    for got, b in zip(d, base):
        assert b <= got <= b * 1.25                        # jittered up
    assert len(set(d[3:])) == 3       # jitter de-synchronises equal bases
    assert FT.RetryPolicy(backoff=0.0).delay(3) == 0.0


def test_resilience_supervisor_sleeps_backoff(tmp_path):
    ckpt = CM.CheckpointManager(tmp_path, save_interval=1, keep=2)
    slept = []
    rep = FT.run_supervised(
        init_state_fn=lambda: {"x": np.zeros((1,), np.float32)},
        train_step_fn=lambda s, b: ({"x": s["x"] + 1.0}, {}),
        data_factory=TR._StepFeed, n_steps=3, ckpt=ckpt,
        chaos=FT.Chaos(fail_at=lambda s, a: s == 1 and a < 2),
        policy=FT.RetryPolicy(max_restarts=5, backoff=0.25),
        sleep=slept.append)
    assert rep.n_restarts == 2
    assert len(slept) == 2 and slept[1] > slept[0] >= 0.25


def test_resilience_nonretryable_propagates(tmp_path):
    ckpt = CM.CheckpointManager(tmp_path, save_interval=1)

    def boom(state, batch):
        raise ZeroDivisionError("a real bug, not a fault")

    with pytest.raises(ZeroDivisionError):
        FT.run_supervised(
            init_state_fn=lambda: {"x": np.zeros((1,), np.float32)},
            train_step_fn=boom, data_factory=TR._StepFeed, n_steps=1,
            ckpt=ckpt)


# ---------------------------------------------------------------------------
# Step-0 eager checkpoint (the restart-before-first-interval bug)
# ---------------------------------------------------------------------------


class _RecordingFeed(TR._StepFeed):
    restored_with = None

    def restore(self, st):
        _RecordingFeed.restored_with = dict(st)
        super().restore(st)


def test_resilience_step0_checkpoint_covers_early_failure(tmp_path):
    """With a sparse save interval, a failure BEFORE the first interval
    must still restart from a recorded cursor: step-0 state is saved
    eagerly, so the restore path is exercised (not the fresh-init path,
    which would replay batches with no record)."""
    ckpt = CM.CheckpointManager(tmp_path, save_interval=5, keep=3)
    _RecordingFeed.restored_with = None
    rep = FT.run_supervised(
        init_state_fn=lambda: {"x": np.zeros((1,), np.float32)},
        train_step_fn=lambda s, b: ({"x": s["x"] + 1.0}, {}),
        data_factory=_RecordingFeed, n_steps=3, ckpt=ckpt,
        chaos=FT.Chaos(fail_at=lambda s, a: s == 2 and a == 0))
    # the restart restored the step-0 checkpoint's recorded cursor
    assert _RecordingFeed.restored_with == {"step": 0}
    assert rep.final_step == 3 and rep.n_restarts == 1
    assert 0 in CM.all_steps(tmp_path)


# ---------------------------------------------------------------------------
# Checkpoint integrity + retention
# ---------------------------------------------------------------------------


def _save_steps(d, steps):
    for s in steps:
        CM.save(d, s, {"x": np.full((4,), float(s), np.float32)})


def test_chaos_checkpoint_sha256_tamper_detection(tmp_path):
    _save_steps(tmp_path, [1, 2])
    CM.verify(tmp_path, 2)
    FT.corrupt_latest_checkpoint(tmp_path)
    with pytest.raises(CM.CheckpointCorruption, match="sha256"):
        CM.verify(tmp_path, 2)
    assert CM.latest_verified_step(tmp_path) == 1
    with pytest.raises(CM.CheckpointCorruption):
        CM.restore(tmp_path, {"x": np.zeros((4,), np.float32)}, step=2)


def test_chaos_checkpoint_torn_write_detection(tmp_path):
    _save_steps(tmp_path, [1, 2])
    npz = tmp_path / "step_00000002" / "arrays.npz"
    npz.write_bytes(npz.read_bytes()[: npz.stat().st_size // 2])
    with pytest.raises(CM.CheckpointCorruption):
        CM.verify(tmp_path, 2)
    mgr = CM.CheckpointManager(tmp_path)
    tree, step, _ = mgr.restore_latest_verified(
        {"x": np.zeros((4,), np.float32)})
    assert step == 1 and mgr.skipped_corrupt == [2]
    np.testing.assert_array_equal(np.asarray(tree["x"]),
                                  np.full((4,), 1.0, np.float32))


def test_chaos_checkpoint_missing_manifest(tmp_path):
    _save_steps(tmp_path, [1])
    (tmp_path / "step_00000001" / "manifest.json").unlink()
    with pytest.raises(CM.CheckpointCorruption, match="manifest"):
        CM.verify(tmp_path, 1)
    assert CM.latest_verified_step(tmp_path) is None
    with pytest.raises(CM.CheckpointCorruption):
        CM.CheckpointManager(tmp_path).restore_latest_verified(
            {"x": np.zeros((4,), np.float32)})


def test_chaos_torn_save_kill_between_arrays_and_manifest(tmp_path):
    """Kill -9 during `CheckpointManager.save` between the arrays.npz
    write and the manifest commit: the staging dir was never renamed,
    so the torn state is INVISIBLE to restore (atomicity, not
    detection); `restore_latest_verified` serves the previous step with
    nothing to skip, and `clean_stale_tmp` reclaims the debris."""
    _save_steps(tmp_path, [1, 2])
    # exactly what save() leaves when killed at that point: a .tmp_*
    # staging dir holding arrays.npz, no manifest, no rename
    stage = tmp_path / ".tmp_killed"
    stage.mkdir()
    np.savez(stage / "arrays.npz", x=np.full((4,), 3.0, np.float32))
    assert CM.latest_step(tmp_path) == 2          # staging is invisible
    mgr = CM.CheckpointManager(tmp_path)
    _, step, _ = mgr.restore_latest_verified(
        {"x": np.zeros((4,), np.float32)})
    assert step == 2 and mgr.skipped_corrupt == []
    assert CM.clean_stale_tmp(tmp_path) == [".tmp_killed"]
    assert not stage.exists()
    # the non-atomic variant (a committed step dir whose manifest never
    # landed — e.g. a reordering filesystem) is skipped loudly, not read
    broken = tmp_path / "step_00000003"
    broken.mkdir()
    np.savez(broken / "arrays.npz", x=np.full((4,), 3.0, np.float32))
    mgr2 = CM.CheckpointManager(tmp_path)
    _, step, _ = mgr2.restore_latest_verified(
        {"x": np.zeros((4,), np.float32)})
    assert step == 2 and mgr2.skipped_corrupt == [3]


def test_chaos_session_journal_inherits_torn_write_guarantee(tmp_path):
    """The streaming session WAL (serving/session.py) honours the same
    contract as the checkpoint store: a record torn by a mid-append
    kill is dropped whole at replay — never half-applied — and the
    verified prefix survives byte-for-byte."""
    from repro.serving.session import SessionJournal
    n1, f1 = np.arange(4, dtype=np.float32), np.ones((4, 3), np.float32)
    j, _ = SessionJournal.open(tmp_path / "wal.log", 4, 3)
    j.append({"kind": "update", "sid": "s", "seq": 1, "n": n1, "f": f1})
    j.append({"kind": "update", "sid": "s", "seq": 2,
              "n": n1 * 2, "f": f1 * 2})
    j.close()
    wal = tmp_path / "wal.log"
    wal.write_bytes(wal.read_bytes()[:-15])       # kill mid-append
    j2, recs = SessionJournal.open(wal, 4, 3)
    assert j2.torn_tail
    assert len(recs) == 1 and recs[0]["seq"] == 1
    np.testing.assert_array_equal(recs[0]["n"], n1)
    np.testing.assert_array_equal(recs[0]["f"], f1)
    j2.close()


def test_chaos_checkpoint_retention_keeps_anchors(tmp_path):
    mgr = CM.CheckpointManager(tmp_path, save_interval=1, keep=2,
                               keep_every=4)
    for s in range(1, 10):
        mgr.maybe_save(s, {"x": np.full((2,), float(s), np.float32)})
    # newest `keep` (8, 9) + every-4th anchors (4, 8)
    assert mgr.steps() == [4, 8, 9]


# ---------------------------------------------------------------------------
# shard_for_host (straggler reassignment)
# ---------------------------------------------------------------------------


def test_resilience_shard_for_host_reassignment():
    assert FT.shard_for_host(0, 3, 8) == 3                 # identity
    assert FT.shard_for_host(0, 11, 8) == 3                # wraps
    remap = {2: 5, 6: 0}
    assert FT.shard_for_host(7, 2, 8, remap) == 5          # straggler's
    assert FT.shard_for_host(7, 6, 8, remap) == 0          # shard moved
    assert FT.shard_for_host(7, 3, 8, remap) == 3          # others keep
    assert FT.shard_for_host(7, 3, 8, {}) == 3             # empty map


# ---------------------------------------------------------------------------
# Serving drills
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(setup):
    feats, gmm = setup
    cfg = CFG.with_overrides(rescore="fused", n_iters=1)
    state = TR.train(cfg, gmm, feats, n_iters=1)
    sv = ServingConfig(max_batch=4, min_bucket=16, max_bucket=64)
    return cfg, state, sv


def test_serving_chaos_kernel_degradation(served):
    """A failing fused kernel demotes the LIVE session fused -> sparse ->
    dense; requests keep being answered, and the fully-demoted session is
    bitwise identical to a session configured dense from the start."""
    cfg, state, sv = served
    rng = np.random.default_rng(1)
    utt = rng.standard_normal((20, cfg.feat_dim)).astype(np.float32)
    ex = IVectorExtractor.from_state(cfg, state, sv)
    ex._chaos_fail_modes = {"fused", "sparse"}
    with pytest.warns(RuntimeWarning, match="demoting to") as rec:
        iv = ex.extract([utt])
    # each demotion is announced, naming the mode and the exception
    assert [str(w.message).split(";")[1].strip() for w in rec] == [
        "demoting to 'sparse'", "demoting to 'dense'"]
    assert "injected sparse-kernel failure" in str(rec[1].message)
    assert ex.mode == "dense" and ex.stats["degradations"] == 2
    dense = IVectorExtractor.from_state(
        cfg.with_overrides(rescore="dense"), state, sv)
    np.testing.assert_array_equal(iv, dense.extract([utt]))
    # session survived and keeps serving without further demotion
    iv2 = ex.extract([utt])
    assert np.isfinite(iv2).all() and ex.stats["degradations"] == 2


def test_serving_chaos_all_modes_failing_raises(served):
    cfg, state, sv = served
    ex = IVectorExtractor.from_state(cfg, state, sv)
    ex._chaos_fail_modes = set(RESCORE_LADDER)
    with pytest.raises(RuntimeError), pytest.warns(RuntimeWarning,
                                                   match="demoting to"):
        ex.extract([np.zeros((8, cfg.feat_dim), np.float32)])


def test_serving_guardrail_truncation_flag(served):
    cfg, state, sv = served
    rng = np.random.default_rng(2)
    long = rng.standard_normal((sv.max_bucket + 50,
                                cfg.feat_dim)).astype(np.float32)
    short = rng.standard_normal((10, cfg.feat_dim)).astype(np.float32)
    ex = IVectorExtractor.from_state(cfg, state, sv)
    iv, infos = ex.extract([long, short], return_info=True)
    assert infos[0].truncated and not infos[1].truncated
    assert infos[0].n_frames == sv.max_bucket
    assert ex.stats["truncated"] == 1
    # truncation == extracting the clipped prefix (explicit, not lossy+silent)
    np.testing.assert_array_equal(
        iv[0], ex.extract([long[:sv.max_bucket]])[0])


def test_serving_guardrail_nonfinite_frames_inert(served):
    """NaN/Inf frames are masked out (masking is exactly inert), flagged
    per-request, and counted — never propagated into the i-vector."""
    cfg, state, sv = served
    rng = np.random.default_rng(3)
    u = rng.standard_normal((20, cfg.feat_dim)).astype(np.float32)
    poisoned = u.copy()
    poisoned[5] = np.nan
    poisoned[11] = np.inf
    ex = IVectorExtractor.from_state(cfg, state, sv)
    iv, infos = ex.extract([poisoned], return_info=True)
    assert infos[0].nonfinite_frames == 2 and not infos[0].empty
    assert np.isfinite(iv).all()
    clean = np.delete(u, [5, 11], axis=0)
    np.testing.assert_allclose(iv[0], ex.extract([clean])[0],
                               rtol=0, atol=1e-5)


def test_serving_guardrail_empty_request_flagged(served):
    cfg, state, sv = served
    all_nan = np.full((6, cfg.feat_dim), np.nan, np.float32)
    ex = IVectorExtractor.from_state(cfg, state, sv)
    iv, infos = ex.extract([np.zeros((0, cfg.feat_dim), np.float32),
                            all_nan], return_info=True)
    assert infos[0].empty and infos[1].empty
    assert not iv.any() and ex.stats["empty"] == 2


def test_serving_guardrail_health_probe(served):
    cfg, state, sv = served
    ex = IVectorExtractor.from_state(cfg, state, sv)
    h = ex.health_check()
    assert h["ok"] and h["error"] is None and h["latency_s"] > 0
    assert ex.stats["requests"] == 0      # the canary is not traffic
    # a broken fused kernel is absorbed DURING the probe: readiness
    # reports ok on the demoted mode instead of failing at traffic time
    ex2 = IVectorExtractor.from_state(cfg, state, sv)
    ex2._chaos_fail_modes = {"fused"}
    with pytest.warns(RuntimeWarning, match="'fused' failed"):
        h2 = ex2.health_check()
    assert h2["ok"] and h2["mode"] == "sparse" and h2["degradations"] == 1


def test_serving_chaos_admission_queue_sheds_load(served):
    cfg, state, sv = served
    rng = np.random.default_rng(4)
    utt = rng.standard_normal((12, cfg.feat_dim)).astype(np.float32)
    ex = IVectorExtractor.from_state(cfg, state, sv)
    now = {"t": 0.0}
    q = AdmissionQueue(ex, max_pending=2, default_timeout=5.0,
                       clock=lambda: now["t"])
    a = q.submit(utt)
    b = q.submit(utt, timeout=20.0)
    with pytest.raises(QueueFull):
        q.submit(utt)                      # bounded: shed, not buffered
    now["t"] = 10.0                        # a expired while queued
    res = q.drain()
    assert res[a].expired and res[a].ivector is None
    assert not res[b].expired and np.isfinite(res[b].ivector).all()
    assert res[b].wait_s == 10.0
    assert q.stats == {"submitted": 2, "shed_full": 1,
                       "shed_deadline": 1, "shed_refine": 0, "served": 1}
    assert len(q) == 0


# ---------------------------------------------------------------------------
# Bundle tamper refusal
# ---------------------------------------------------------------------------


def test_chaos_bundle_tamper_refused(served, tmp_path):
    """Flip ONE byte of a saved bundle's array payload: load must refuse
    (integrity error), never return corrupt arrays."""
    cfg, state, _ = served
    path = Bundle(cfg=cfg, ubm=state.ubm, model=state.model).save(
        tmp_path / "bundle")
    assert Bundle.load(path) is not None    # pristine loads fine
    npz = path / f"step_{0:08d}" / "arrays.npz"
    raw = bytearray(npz.read_bytes())
    raw[len(raw) // 2] ^= 0x01
    npz.write_bytes(bytes(raw))
    with pytest.raises((CM.CheckpointCorruption, ValueError)):
        Bundle.load(path)
