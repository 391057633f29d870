"""Masked variable-length extraction + serving-session tests, plus the
alignment-floor and chunked-E-step regression tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ivector_tvm import SMOKE as IV_SMOKE
from repro.core import alignment as AL
from repro.core import backend as BK
from repro.core import stats as ST
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.data.speech import (SpeechDataConfig, build_dataset,
                               build_ragged_dataset, utterance_lengths)
from repro.serving import IVectorExtractor, ServingConfig

KEY = jax.random.PRNGKey(0)


def _toy_ubm(key, C=8, D=5):
    means = jax.random.normal(key, (C, D)) * 2
    A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
    covs = jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)
    return U.FullGMM(jnp.ones((C,)) / C, means, covs)


def _toy_state(formulation, C=8, D=5, R=6):
    ubm = _toy_ubm(jax.random.fold_in(KEY, 30), C, D)
    model = TV.init_model(jax.random.fold_in(KEY, 31), ubm.means, ubm.covs,
                          R, formulation, prior_offset=10.0)
    return TR.TrainState(model=model, ubm=ubm)


def _cfg(formulation, C=8, D=5, R=6):
    return IV_SMOKE.with_overrides(feat_dim=D, n_components=C,
                                   ivector_dim=R, posterior_top_k=4,
                                   formulation=formulation)


# ---------------------------------------------------------------------------
# Tentpole: padded-and-masked == unpadded (stats and i-vectors)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("formulation", ["standard", "augmented"])
def test_masked_padding_equivalence(formulation):
    """Garbage padding frames + mask yield the same BW stats and i-vectors
    as the unpadded utterance (both formulations)."""
    cfg = _cfg(formulation)
    state = _toy_state(formulation)
    F, Fp, D = 40, 64, 5
    x = jax.random.normal(jax.random.fold_in(KEY, 32), (2, F, D))
    # garbage includes overflow-scale, inf, and NaN frames: masking must
    # keep all of them out of the statistics (where-mask, not multiply)
    garbage = 1e25 * jax.random.normal(jax.random.fold_in(KEY, 33),
                                       (2, Fp - F, D))
    garbage = garbage.at[:, 0, :].set(jnp.inf).at[:, 1, :].set(jnp.nan)
    xp = jnp.concatenate([x, garbage], axis=1)
    mask = jnp.concatenate([jnp.ones((2, F)), jnp.zeros((2, Fp - F))],
                           axis=1)

    st_ref = TR._align_and_stats(cfg, state.ubm, x, True)
    st_pad = TR._align_and_stats(cfg, state.ubm, xp, True, mask=mask)
    np.testing.assert_allclose(np.asarray(st_pad.n), np.asarray(st_ref.n),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_pad.f), np.asarray(st_ref.f),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_pad.S), np.asarray(st_ref.S),
                               rtol=1e-5, atol=1e-4)

    iv_ref = np.asarray(TR.extract(cfg, state, x))
    iv_pad = np.asarray(TR.extract(cfg, state, xp, mask=mask))
    np.testing.assert_allclose(iv_pad, iv_ref, rtol=1e-4, atol=1e-4)


def test_masked_frames_contribute_nothing():
    """An all-zero mask produces exactly zero statistics."""
    cfg = _cfg("augmented")
    state = _toy_state("augmented")
    x = jax.random.normal(jax.random.fold_in(KEY, 34), (1, 16, 5))
    st = TR._align_and_stats(cfg, state.ubm, x, True,
                             mask=jnp.zeros((1, 16)))
    assert float(jnp.abs(st.n).max()) == 0.0
    assert float(jnp.abs(st.f).max()) == 0.0
    assert float(jnp.abs(st.S).max()) == 0.0


# ---------------------------------------------------------------------------
# Serving session: bucketing + micro-batching match per-utterance extraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("formulation", ["standard", "augmented"])
def test_extractor_matches_per_utterance_extract(formulation):
    cfg = _cfg(formulation)
    state = _toy_state(formulation)
    lengths = [10, 17, 16, 33, 7, 64, 40, 12, 50]   # spans 3+ buckets
    utts = [jax.random.normal(jax.random.fold_in(KEY, 40 + i), (L, 5))
            for i, L in enumerate(lengths)]
    ex = IVectorExtractor.from_state(
        cfg, state, ServingConfig(max_batch=4, min_bucket=16))
    got = ex.extract(utts)
    assert got.shape == (len(utts), cfg.ivector_dim)
    assert len(ex.buckets()) >= 3
    for i, u in enumerate(utts):
        want = np.asarray(BK.length_norm(
            TR.extract(cfg, state, u[None])))[0]
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4)


def test_extractor_caches_compiles_per_bucket():
    cfg = _cfg("augmented")
    state = _toy_state("augmented")
    ex = IVectorExtractor.from_state(
        cfg, state, ServingConfig(max_batch=2, min_bucket=16))
    utts = [jax.random.normal(jax.random.fold_in(KEY, 60 + i), (L, 5))
            for i, L in enumerate([9, 14, 16, 11, 15, 8])]
    ex.extract(utts)
    ex.extract(utts)
    assert ex.buckets() == [16]          # one power-of-two bucket
    assert ex.stats["compiles"] == 1     # reused across calls and batches
    assert ex.stats["requests"] == 12


def test_extractor_truncation_lands_on_bucket_grid():
    """A request truncated at max_frames must land in an exact
    power-of-two bucket: an off-grid max_bucket (here 100) previously
    made every truncated request a fresh off-bucket jit. Truncation now
    targets the largest on-grid bucket <= max_bucket."""
    cfg = _cfg("augmented")
    state = _toy_state("augmented")
    ex = IVectorExtractor.from_state(
        cfg, state, ServingConfig(min_bucket=16, max_bucket=100))
    assert ex._cap == 64                 # 16 * 2^2; 128 would exceed 100
    assert ex.bucket_for(300) == 64
    long_u = np.asarray(
        jax.random.normal(jax.random.fold_in(KEY, 70), (300, 5)),
        np.float32)
    iv, infos = ex.extract([long_u], return_info=True)
    assert infos[0].truncated
    assert infos[0].n_frames == 64 and infos[0].bucket == 64
    assert ex.buckets() == [64]          # on-grid: no off-bucket compile
    # truncation == extracting the kept prefix directly, bit-for-bit
    iv_prefix = ex.extract([long_u[:64]])
    np.testing.assert_array_equal(iv, iv_prefix)
    assert ex.stats["compiles"] == 1     # the prefix reused the jit


def test_extractor_counts_rows_and_compiles_per_mode_and_bucket():
    """Each request fills one row of one batch, so ``requests`` counts
    the filled rows of the batches sent to the device; ``compiles``
    counts the first call of each (mode, bucket) pair, so a demotion
    that recompiles a bucket is counted; the health-check canary is not
    traffic and leaves the row and batch counts alone."""
    cfg = _cfg("augmented")
    state = _toy_state("augmented")
    ex = IVectorExtractor.from_state(
        cfg, state, ServingConfig(max_batch=4, min_bucket=16))
    utts = [np.asarray(jax.random.normal(jax.random.fold_in(KEY, 80 + i),
                                         (L, 5)), np.float32)
            for i, L in enumerate([9, 14, 16, 11, 15, 30])]
    ex.extract(utts)            # bucket 16: a batch of 4 and one of 1
    assert ex.stats["batches"] == 3 and ex.stats["requests"] == 6
    assert ex.stats["compiles"] == 2            # (sparse, 16), (sparse, 32)
    h = ex.health_check()
    assert h["ok"] and ex.stats["requests"] == 6
    assert ex.stats["batches"] == 3
    ex._chaos_fail_modes = {ex.mode}
    with pytest.warns(RuntimeWarning, match="demoting"):
        ex.extract(utts[:1])
    assert ex.mode == "dense"
    assert ex.stats["compiles"] == 3            # (dense, 16) is new
    assert ex.stats["requests"] == 7 and ex.stats["batches"] == 4
    ex.extract(utts[:1])
    assert ex.stats["compiles"] == 3


def test_admission_queue_reports_time_queued_before_the_drain():
    """``wait_s`` runs from submit to the result: the time queued before
    the drain and the drain's own work, on the queue's clock; a request
    shed at a drain waited until that drain began."""
    from repro.serving.guard import AdmissionQueue
    cfg = _cfg("augmented")
    state = _toy_state("augmented")
    ex = IVectorExtractor.from_state(
        cfg, state, ServingConfig(max_batch=4, min_bucket=16))
    now = {"t": 0.0}

    def clock():
        now["t"] += 0.5         # every reading of the clock takes 0.5 s
        return now["t"]

    q = AdmissionQueue(ex, default_timeout=100.0, clock=clock)
    utt = np.ones((12, 5), np.float32)
    a = q.submit(utt)           # at 0.5
    b = q.submit(utt)           # at 1.0
    res = q.drain()             # starts at 1.5, results at 2.0
    assert res[a].wait_s == 1.5 and res[b].wait_s == 1.0
    assert not res[a].expired and res[a].ivector is not None
    c = q.submit(utt, timeout=0.1)   # at 2.5; expires before the drain
    res = q.drain()                  # starts at 3.0
    assert res[c].expired and res[c].ivector is None
    assert res[c].wait_s == 0.5


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------


def test_alignment_floor_keeps_argmax():
    """A floor above every selected posterior must keep the arg-max
    component instead of zeroing the frame out of the statistics."""
    ubm = _toy_ubm(jax.random.fold_in(KEY, 70))
    x = jax.random.normal(jax.random.fold_in(KEY, 71), (32, 5))
    post = AL.align_frames(x, ubm, ubm.to_diag(), top_k=4, floor=0.9)
    s = np.asarray(jnp.sum(post.values, axis=1))
    np.testing.assert_allclose(s, np.ones_like(s), atol=1e-5)
    assert np.isfinite(np.asarray(post.values)).all()
    # the surviving mass sits on the per-frame arg-max component
    v = np.asarray(post.values)
    assert (v.max(axis=1) > 0.0).all()


def test_em_accumulate_scan_ragged_tail():
    """U % chunk != 0 must chunk exactly, not fall back to unchunked."""
    model = _toy_state("augmented").model
    pre = TV.precompute(model)
    n = jax.random.uniform(jax.random.fold_in(KEY, 80), (13, 8),
                           minval=0.5, maxval=5.0)
    f = jax.random.normal(jax.random.fold_in(KEY, 81), (13, 8, 5))
    want = TV.em_accumulate(model, pre, n, f)
    got = TV.em_accumulate_scan(model, pre, n, f, chunk=4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ragged_sampler_deterministic_prefixes():
    dc = SpeechDataConfig(feat_dim=6, n_components=8, n_speakers=4,
                          utts_per_speaker=3, frames_per_utt=40,
                          min_frames_per_utt=10, speaker_rank=4,
                          channel_rank=2)
    lens = utterance_lengths(dc)
    assert ((lens >= 10) & (lens <= 40)).all()
    assert len(set(lens.tolist())) > 1
    utts, labels = build_ragged_dataset(dc)
    assert [u.shape[0] for u in utts] == lens.tolist()
    # ragged utterances are prefixes of the fixed-length dataset
    fixed, labels2 = build_dataset(dc)
    assert (labels == labels2).all()
    for u, full in zip(utts, fixed):
        np.testing.assert_allclose(np.asarray(u),
                                   np.asarray(full[:u.shape[0]]),
                                   rtol=1e-6, atol=1e-6)
    utts2, _ = build_ragged_dataset(dc)
    for a, b in zip(utts, utts2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
