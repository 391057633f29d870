"""Streaming serving-resilience tests (DESIGN.md §14): crash-safe
session stores + write-ahead journal, zero-downtime bundle rollout, and
the overload-control extensions to the admission queue."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.bundle import Bundle
from repro.configs.ivector_tvm import SMOKE as IV_SMOKE
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.serving import (AdmissionQueue, IVectorExtractor, QueueFull,
                           RolloutController, ServingConfig, SessionConfig,
                           SessionJournal, SessionStore)

KEY = jax.random.PRNGKey(7)
C, D, R = 8, 5, 6


def _toy_ubm(key):
    means = jax.random.normal(key, (C, D)) * 2
    A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
    covs = jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)
    return U.FullGMM(jnp.ones((C,)) / C, means, covs)


def _cfg(formulation="augmented", rescore="sparse"):
    # rescore='sparse' leaves exactly one ladder step (-> dense), so the
    # degradation tests are deterministic on any backend
    return IV_SMOKE.with_overrides(feat_dim=D, n_components=C,
                                   ivector_dim=R, posterior_top_k=4,
                                   formulation=formulation, rescore=rescore)


def _extractor(formulation="augmented", rescore="sparse",
               serving=None, model=None):
    cfg = _cfg(formulation, rescore)
    ubm = _toy_ubm(jax.random.fold_in(KEY, 40))
    if model is None:
        model = TV.init_model(jax.random.fold_in(KEY, 41), ubm.means,
                              ubm.covs, R, formulation, prior_offset=10.0)
    sv = serving or ServingConfig(min_bucket=16, max_bucket=128)
    return IVectorExtractor(cfg, model, ubm, sv)


def _scfg(**kw):
    kw.setdefault("chunk_min_bucket", 16)
    kw.setdefault("chunk_max_bucket", 64)
    return SessionConfig(**kw)


def _chunk(seed, F=20):
    return np.random.RandomState(seed).randn(F, D).astype(np.float32)


# ---------------------------------------------------------------------------
# SessionStore: incremental accumulation == batch extraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("formulation", ["standard", "augmented"])
def test_session_incremental_matches_batch(formulation):
    """Chunk-by-chunk accumulation + mean_only re-solve produces the
    same i-vector (fp tolerance) as one batch extraction of the whole
    utterance — additivity of BW statistics over chunk boundaries."""
    ex = _extractor(formulation)
    store = SessionStore(ex, _scfg())
    chunks = [_chunk(s, F) for s, F in [(0, 20), (1, 7), (2, 33), (3, 64)]]
    iv = None
    for ch in chunks:
        iv, _ = store.update("s", ch)
    iv_batch = ex.extract([np.concatenate(chunks, 0)])[0]
    np.testing.assert_allclose(iv, iv_batch, rtol=1e-4, atol=1e-4)


def test_session_emission_refines_over_chunks():
    """Every chunk yields a usable i-vector; each solve sees strictly
    more frames (time-to-first-ivector is one chunk, not the stream)."""
    store = SessionStore(_extractor(), _scfg())
    frames = []
    for s in range(4):
        iv, info = store.update("s", _chunk(s))
        assert np.isfinite(iv).all() and np.linalg.norm(iv) > 0
        assert info.seq == s + 1
        frames.append(store.session("s").frames)
    assert frames == sorted(frames) and frames[0] < frames[-1]


def test_session_chunk_validation_and_empty():
    """NaN frames are masked (counted, not propagated); an all-invalid
    chunk contributes exactly nothing to the accumulators."""
    store = SessionStore(_extractor(), _scfg())
    iv1, _ = store.update("s", _chunk(0))
    n_before = store.session("s").n.copy()
    bad = np.full((8, D), np.nan, np.float32)
    iv2, info = store.update("s", bad)
    assert info.empty and info.nonfinite_frames == 8
    np.testing.assert_array_equal(store.session("s").n, n_before)
    np.testing.assert_array_equal(iv1, iv2)   # same stats -> same solve
    # over-long chunks truncate to the power-of-two cap, flagged
    _, info = store.update("s", _chunk(1, F=500))
    assert info.truncated and info.n_frames == 64 and info.bucket == 64


def test_session_degradation_ladder():
    """A failing rescore kernel demotes the session's binding down the
    ladder and keeps serving (the batch extractor's contract)."""
    store = SessionStore(_extractor(rescore="sparse"), _scfg())
    store._chaos_fail_modes = {"sparse"}
    with pytest.warns(RuntimeWarning, match="'sparse' failed .*demoting "
                      "to 'dense'"):
        iv, _ = store.update("s", _chunk(0))
    assert np.isfinite(iv).all()
    assert store._live.mode == "dense"
    assert store.stats["degradations"] == 1


def test_session_ttl_eviction():
    clock = [0.0]
    store = SessionStore(_extractor(), _scfg(ttl_s=10.0),
                         clock=lambda: clock[0])
    store.update("a", _chunk(0))
    clock[0] = 5.0
    store.update("b", _chunk(1))
    clock[0] = 20.0
    store.update("c", _chunk(2))   # sweep runs on every update
    assert "a" not in store and "b" not in store and "c" in store
    assert store.stats["evicted_ttl"] == 2


def test_session_lru_eviction_under_memory_budget():
    ex = _extractor()
    budget = 2 * 4 * (C + C * D) + 1     # room for exactly 2 sessions
    store = SessionStore(ex, _scfg(max_bytes=budget))
    assert store.max_sessions == 2
    store.update("a", _chunk(0))
    store.update("b", _chunk(1))
    store.update("a", _chunk(2))         # refresh a: b becomes LRU
    store.update("c", _chunk(3))
    assert "b" not in store and "a" in store and "c" in store
    assert store.stats["evicted_lru"] == 1
    h = store.health()
    assert h["used_bytes"] <= h["budget_bytes"]


# ---------------------------------------------------------------------------
# SessionStore: write-ahead journal, crash recovery
# ---------------------------------------------------------------------------


def test_session_journal_restore_bit_exact(tmp_path):
    """Kill the store (no clean shutdown), rebuild from the journal:
    state bytes, the re-solve, AND the next chunk's emission are all
    bit-identical to an uninterrupted store."""
    ex = _extractor()
    cfg = _scfg(journal_dir=str(tmp_path / "j"))
    store = SessionStore(ex, cfg)
    sids = [f"s{i}" for i in range(3)]
    for r in range(3):
        for i, sid in enumerate(sids):
            store.update(sid, _chunk(10 * r + i))
    ref = {sid: (store.session(sid).n.copy(), store.session(sid).f.copy(),
                 store.solve(sid).copy()) for sid in sids}
    del store                              # crash: no close, no flush call
    restored = SessionStore(ex, cfg)
    assert restored.stats["restored"] == len(sids)
    for sid in sids:
        s = restored.session(sid)
        np.testing.assert_array_equal(s.n, ref[sid][0])
        np.testing.assert_array_equal(s.f, ref[sid][1])
        np.testing.assert_array_equal(restored.solve(sid), ref[sid][2])
        assert s.chunks == 3
    # the NEXT emission matches an uninterrupted run bit-for-bit
    uninterrupted = SessionStore(ex, _scfg())
    for r in range(3):
        for i, sid in enumerate(sids):
            uninterrupted.update(sid, _chunk(10 * r + i), emit=False)
    for i, sid in enumerate(sids):
        iv_resumed, _ = restored.update(sid, _chunk(99 + i))
        iv_straight, _ = uninterrupted.update(sid, _chunk(99 + i))
        np.testing.assert_array_equal(iv_resumed, iv_straight)


def test_session_journal_torn_tail_skipped(tmp_path):
    """A crash mid-append tears the last record; replay drops exactly
    that record (checkpoint torn-write semantics) and later appends
    extend a clean log."""
    ex = _extractor()
    cfg = _scfg(journal_dir=str(tmp_path))
    store = SessionStore(ex, cfg)
    ivs = [store.update("s", _chunk(i))[0] for i in range(3)]
    store.close_store()
    wal = tmp_path / "wal.log"
    size = wal.stat().st_size
    with open(wal, "r+b") as fh:
        fh.truncate(size - 10)             # tear the 3rd update record
    restored = SessionStore(ex, cfg)
    assert restored.stats["journal_torn"] == 1
    assert restored.session("s").chunks == 2
    np.testing.assert_array_equal(restored.solve("s"), ivs[1])
    restored.update("s", _chunk(7))        # append onto the healed log
    restored.close_store()
    again = SessionStore(ex, cfg)
    assert again.stats["journal_torn"] == 0
    assert again.session("s").chunks == 3


def test_session_journal_close_tombstone(tmp_path):
    """Closed (and LRU/TTL-evicted) sessions never resurrect on
    restore: eviction writes a tombstone record."""
    ex = _extractor()
    cfg = _scfg(journal_dir=str(tmp_path))
    store = SessionStore(ex, cfg)
    store.update("keep", _chunk(0))
    store.update("done", _chunk(1))
    assert store.close("done") is not None
    store.close_store()
    restored = SessionStore(ex, cfg)
    assert "keep" in restored and "done" not in restored


def test_session_journal_compaction(tmp_path):
    """Beyond the byte budget the WAL is rewritten atomically to one
    record per live session; recovery stays bit-exact."""
    ex = _extractor()
    cfg = _scfg(journal_dir=str(tmp_path), journal_compact_bytes=4096)
    store = SessionStore(ex, cfg)
    for i in range(24):                    # each record is a few hundred B
        store.update(f"s{i % 2}", _chunk(i))
    assert store.stats["compactions"] >= 1
    assert (tmp_path / "wal.log").stat().st_size <= 4096 + 1024
    ref = {sid: store.solve(sid) for sid in ("s0", "s1")}
    store.close_store()
    restored = SessionStore(ex, cfg)
    for sid in ("s0", "s1"):
        np.testing.assert_array_equal(restored.solve(sid), ref[sid])
        assert restored.session(sid).chunks == 12


def test_session_journal_header_mismatch_rejected(tmp_path):
    """A journal written for another model's (C, D) refuses to replay —
    restoring it would corrupt every session silently."""
    j, _ = SessionJournal.open(tmp_path / "wal.log", C, D)
    j.close()
    with pytest.raises(ValueError, match="does not match"):
        SessionJournal.open(tmp_path / "wal.log", C + 1, D)


# ---------------------------------------------------------------------------
# Rollout: gated hot-swap + rollback
# ---------------------------------------------------------------------------


def _bundle_pair(tmp_path):
    """Two saved bundles: one identical to the live model, one with a
    perturbed T (a 'new model'), plus the live extractor."""
    ex = _extractor()
    p_same = tmp_path / "b_same"
    p_new = tmp_path / "b_new"
    Bundle(cfg=ex.cfg, ubm=ex.ubm, model=ex.model).save(p_same)
    model2 = dataclasses.replace(ex.model, T=ex.model.T * 1.01)
    Bundle(cfg=ex.cfg, ubm=ex.ubm, model=model2).save(p_new)
    return ex, p_same, p_new


def test_rollout_identical_bundle_gates_bit_exact(tmp_path):
    """Same content hash -> the shadow gate REQUIRES bit-exact parity,
    and an identical rebuilt artifact swaps cleanly."""
    ex, p_same, _ = _bundle_pair(tmp_path)
    rc = RolloutController(ex)
    utts = [_chunk(i, 40) for i in range(3)]
    rep = rc.roll(p_same, shadow_utts=utts)
    assert rep.outcome == "swapped"
    assert rep.parity["same_content"] and rep.parity["bit_exact"]
    assert rep.candidate_hash == rep.live_hash
    assert rc.live is not ex and rc.prev is ex


def test_rollout_swap_and_rollback_bit_exact(tmp_path):
    """Swap to a new model under interleaved traffic, then roll back:
    post-rollback outputs are bit-identical to pre-swap (the old
    extractor object survives with its compiled jits)."""
    ex, _, p_new = _bundle_pair(tmp_path)
    store = SessionStore(ex, _scfg())
    store.update("live-session", _chunk(0))
    rc = RolloutController(ex, store=store)
    utts = [_chunk(i, 40) for i in range(3)]
    before = ex.extract(utts)
    iv_sess_before = store.solve("live-session")
    rep = rc.roll(p_new, shadow_utts=utts, policy="migrate")
    assert rep.outcome == "swapped"
    assert rep.sessions["migrated"] == 1
    after_swap = rc.live.extract(utts)
    assert not np.array_equal(before, after_swap)   # genuinely new model
    assert np.isfinite(store.solve("live-session")).all()
    assert rc.rollback()
    assert rc.live is ex
    np.testing.assert_array_equal(rc.live.extract(utts), before)
    np.testing.assert_array_equal(store.solve("live-session"),
                                  iv_sess_before)
    assert store.draining() == 0


def test_rollout_drain_policy_pins_old_sessions(tmp_path):
    """policy='drain': existing sessions keep the bundle that opened
    them; new sessions bind to the new bundle; closing the last drained
    session releases the old bundle."""
    ex, _, p_new = _bundle_pair(tmp_path)
    store = SessionStore(ex, _scfg())
    store.update("old1", _chunk(0))
    store.update("old2", _chunk(1))
    rc = RolloutController(ex, store=store)
    rep = rc.roll(p_new, shadow_utts=[_chunk(9, 40)], policy="drain")
    assert rep.outcome == "swapped"
    assert rep.sessions == {"migrated": 0, "pinned_to_old": 2}
    store.update("new1", _chunk(2))
    assert store.draining() == 2
    old_binding = store.session("old1").binding
    assert store.session("new1").binding is not old_binding
    store.close("old1")
    store.close("old2")
    assert store.draining() == 0
    assert store.stats["drained_bundles"] == 1


def test_rollout_rejects_corrupt_bundle(tmp_path):
    """A tampered bundle fails integrity at shadow-load: rejected
    before it ever sees traffic, live extractor untouched."""
    ex, p_same, _ = _bundle_pair(tmp_path)
    step_dir = next(p_same.glob("step_*"))
    npz = step_dir / "arrays.npz"
    raw = bytearray(npz.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    npz.write_bytes(bytes(raw))
    rc = RolloutController(ex)
    rep = rc.roll(p_same, shadow_utts=[_chunk(0, 40)])
    assert rep.outcome == "rejected"
    assert "shadow-load failed" in rep.reason
    assert rc.live is ex and rc.prev is None


def test_rollout_auto_rollback_on_post_swap_failure(tmp_path):
    """A candidate that passes canary + parity but fails the post-swap
    probe is rolled back automatically; the old extractor serves."""
    ex, p_same, _ = _bundle_pair(tmp_path)
    rc = RolloutController(ex)
    cand = IVectorExtractor.from_bundle(p_same, serving=ex.serving)
    calls = {"n": 0}
    orig = cand.health_check

    def flaky_probe():
        calls["n"] += 1
        h = orig()
        if calls["n"] >= 2:                # canary passes, post-swap fails
            h = dict(h, ok=False, error="induced post-swap fault")
        return h

    cand.health_check = flaky_probe
    rc.shadow_load = lambda path: cand
    rep = rc.roll("ignored", shadow_utts=[_chunk(0, 40)])
    assert rep.outcome == "rolled_back"
    assert "post-swap probe failed" in rep.reason
    assert rc.live is ex and rc.prev is None


# ---------------------------------------------------------------------------
# Overload control: preemption, adaptive batching, readiness payload
# ---------------------------------------------------------------------------


def test_streaming_refine_preempted_for_first_response():
    """On a full queue a first-response admission sheds the refinement
    with the slackest deadline; a refinement is shed outright."""
    clock = [0.0]
    q = AdmissionQueue(_extractor(), max_pending=2,
                       clock=lambda: clock[0])
    r_tight = q.submit(_chunk(0, 40), kind="refine", timeout=5.0)
    r_slack = q.submit(_chunk(1, 40), kind="refine", timeout=50.0)
    with pytest.raises(QueueFull):
        q.submit(_chunk(2, 40), kind="refine")
    r_first = q.submit(_chunk(2, 40), kind="first")
    assert q.stats["shed_refine"] == 1 and q.stats["shed_full"] == 1
    res = q.drain()
    assert res[r_slack].preempted and res[r_slack].ivector is None
    assert not res[r_tight].expired and not res[r_first].expired


def test_streaming_adaptive_batch_budget():
    """The drain budget grows in power-of-two steps with depth, between
    min_batch and the extractor's max_batch."""
    ex = _extractor(serving=ServingConfig(min_bucket=16, max_bucket=128,
                                          max_batch=8))
    q = AdmissionQueue(ex, max_pending=64, min_batch=1)
    assert q.batch_budget() == 1           # idle: minimum latency
    for i in range(3):
        q.submit(_chunk(i, 40))
    assert q.batch_budget() == 4
    for i in range(20):
        q.submit(_chunk(10 + i, 40))
    assert q.batch_budget() == 8           # capped at max_batch


def test_streaming_budgeted_drain_serves_first_before_refine():
    """Under a budget, first-response chunks are served before
    refinements (earliest deadline first); leftovers stay queued and
    shed only when their own deadline passes."""
    clock = [0.0]
    q = AdmissionQueue(_extractor(), max_pending=8,
                       clock=lambda: clock[0])
    r_ref = [q.submit(_chunk(i, 40), kind="refine", timeout=30.0)
             for i in range(2)]
    r_first = [q.submit(_chunk(3 + i, 40), kind="first", timeout=30.0)
               for i in range(2)]
    res = q.drain(budget=2)
    assert sorted(res) == sorted(r_first)  # firsts won the budget
    assert len(q) == 2                     # refinements still queued
    clock[0] = 31.0                        # their deadline passes
    res2 = q.drain(budget=2)
    assert all(res2[r].expired for r in r_ref)
    assert q.stats["shed_deadline"] == 2


def test_streaming_queue_routes_sessions_and_reports_health():
    """sid-tagged requests route through the session store; `health`
    exposes depth, budget, shed counters, rescore mode, and the store —
    the readiness payload the probes consume."""
    ex = _extractor()
    store = SessionStore(ex, _scfg())
    q = AdmissionQueue(ex, max_pending=8, store=store)
    rid1 = q.submit(_chunk(0), kind="first", sid="sA")
    rid2 = q.submit(_chunk(1, 40))          # stateless batch request
    res = q.drain(q.batch_budget())
    assert res[rid1].sid == "sA" and res[rid1].info.first_chunk
    assert np.isfinite(res[rid1].ivector).all()
    assert res[rid2].sid is None
    assert store.session("sA").chunks == 1
    h = q.health()
    assert h["ok"] and h["mode"] == ex.mode
    for key in ("depth", "max_pending", "batch_budget", "shed_full",
                "shed_deadline", "shed_refine", "served", "submitted"):
        assert key in h["queue"]
    assert h["sessions"]["sessions_open"] == 1
    assert h["extractor"]["ok"]
