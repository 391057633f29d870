"""Packed-symmetric mixed-precision TVM E-step (DESIGN.md §9).

Covers the acceptance surface of the packed path: ops wrappers vs the
dense oracles on ragged U / odd-P shapes, bf16-vs-f32 tolerance bounds,
packed==dense through posterior / em_accumulate / m_step, zero-occupancy
robustness, the Cholesky-based precompute, the mean-only posterior, and
trainer convergence parity `estep='packed'` vs `'dense'`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ivector_tvm import SMOKE as IV_SMOKE
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.core.pipeline import evaluate_state
from repro.data.speech import SpeechDataConfig, build_dataset
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


def _toy_model(key, C=12, D=6, R=9, formulation="augmented"):
    means = jax.random.normal(key, (C, D))
    A = jax.random.normal(jax.random.fold_in(key, 2), (C, D, D)) * 0.2
    covs = jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)
    return TV.init_model(jax.random.fold_in(key, 3), means, covs, R,
                         formulation, prior_offset=10.0)


def _toy_stats(key, Utt=17, C=12, D=6):
    n = jax.random.uniform(key, (Utt, C), minval=0.3, maxval=4.0)
    f = jax.random.normal(jax.random.fold_in(key, 1), (Utt, C, D))
    return n, f


def _packed_operands(key, Utt, C, R):
    n = jax.random.uniform(key, (Utt, C), minval=0.0, maxval=3.0)
    M = jax.random.normal(jax.random.fold_in(key, 1), (C, R, R))
    Up = ref.pack_symmetric(M + jnp.swapaxes(M, 1, 2))
    S = jax.random.normal(jax.random.fold_in(key, 2), (Utt, R, R))
    PPp = ref.pack_symmetric(S + jnp.swapaxes(S, 1, 2))
    return n, Up, PPp


# ---------------------------------------------------------------------------
# ops wrappers vs the ref oracles: ragged shapes, odd P, interpret kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Utt,C,R,blocks", [
    (37, 16, 5, dict(block_u=8, block_p=8, block_c=8)),      # ragged U, P=15
    (64, 24, 13, dict(block_u=16, block_p=16, block_c=16)),  # odd P=91
    (5, 7, 4, dict(block_u=8, block_p=8, block_c=8)),        # everything tiny
    (129, 33, 8, dict(block_u=32, block_p=16, block_c=16)),  # all ragged
])
def test_ops_wrappers_match_ref_ragged(Utt, C, R, blocks):
    """Pad-and-clip Pallas wrappers == jnp oracles to ≤1e-5 (f32) on
    ragged U / odd-P cases — no block-divisibility assumptions leak."""
    n, Up, PPp = _packed_operands(k(1), Utt, C, R)
    P = R * (R + 1) // 2
    want_l, want_a = ref.tvm_estep_l(n, Up), ref.tvm_estep_a(n, PPp)
    with ops.use_pallas(True):
        got_l = ops.tvm_estep_l(n, Up, **blocks)
        got_a = ops.tvm_estep_a(n, PPp, **blocks)
    assert got_l.shape == (Utt, P) and got_a.shape == (C, P)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_a, want_a, rtol=1e-5, atol=1e-5)


def test_ops_wrappers_match_dense_einsum():
    """The packed contractions are exactly the dense einsums after
    unpacking (the oracle of the oracle)."""
    Utt, C, R = 23, 11, 6
    n, Up, PPp = _packed_operands(k(2), Utt, C, R)
    Ud = ref.unpack_symmetric(Up, R)
    PPd = ref.unpack_symmetric(PPp, R)
    np.testing.assert_allclose(
        ref.unpack_symmetric(ref.tvm_estep_l(n, Up), R),
        jnp.einsum("uc,crs->urs", n, Ud), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        ref.unpack_symmetric(ref.tvm_estep_a(n, PPp), R),
        jnp.einsum("uc,urs->crs", n, PPd), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pallas", [False, True])
def test_bf16_tolerance_bounds(pallas):
    """bf16 inputs + f32 accumulation: relative error bounded by bf16's
    ~8-bit mantissa (few 1e-2 of the result scale), far tighter than
    bf16-accumulation would give; both execution paths obey the bound."""
    Utt, C, R = 40, 24, 10
    n, Up, PPp = _packed_operands(k(3), Utt, C, R)
    with ops.use_pallas(pallas):
        f32_l = ops.tvm_estep_l(n, Up, dtype="float32")
        bf_l = ops.tvm_estep_l(n, Up, dtype="bfloat16")
        f32_a = ops.tvm_estep_a(n, PPp, dtype="float32")
        bf_a = ops.tvm_estep_a(n, PPp, dtype="bfloat16")
    assert bf_l.dtype == jnp.float32 and bf_a.dtype == jnp.float32
    for got, want in ((bf_l, f32_l), (bf_a, f32_a)):
        rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        assert rel < 3e-2, rel
        assert rel > 0.0   # the knob actually changes the compute dtype


# ---------------------------------------------------------------------------
# precompute: Cholesky-based solve
# ---------------------------------------------------------------------------


def test_mode_knobs_reject_unknown_values():
    """Typos in the new knobs raise instead of silently running dense/f32
    (same contract as alignment's `rescore` validation)."""
    model = _toy_model(k(30))
    n, Up, _ = _packed_operands(k(31), 4, model.T.shape[0], model.rank)
    with pytest.raises(ValueError, match="estep"):
        TV.precompute(model, estep="Packed")
    with pytest.raises(ValueError, match="dtype"):
        ops.tvm_estep_l(n, Up, dtype="fp16")


def test_precompute_cholesky_matches_inverse():
    model = _toy_model(k(4))
    pre = TV.precompute(model)
    SigInv = jnp.linalg.inv(model.Sigma)
    Pj_inv = jnp.einsum("cde,cer->cdr", SigInv, model.T)
    U_inv = jnp.einsum("cdr,cds->crs", model.T, Pj_inv)
    np.testing.assert_allclose(pre.Pj, Pj_inv, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(pre.U, U_inv, rtol=1e-4, atol=1e-4)


def test_precompute_near_singular_sigma_stays_finite():
    """Near-singular residual covariances (rank-1 + the COV_FLOOR-scale
    jitter the M-step guarantees, condition ~1e5) must not poison Pj/U —
    the point of cho_solve over an explicit inv. The solve must also be
    backward-stable: Σ Pj reproduces T."""
    C, D, R = 6, 8, 5
    model = _toy_model(k(5), C=C, D=D, R=R)
    v = jax.random.normal(k(6), (C, D)) * 3.0
    sick = (TV.COV_FLOOR * jnp.eye(D)[None]
            + v[:, :, None] * v[:, None, :])      # rank-1 + floor jitter
    model = TV.TVModel(model.T, sick.astype(jnp.float32), model.prior,
                       model.means, model.formulation)
    for estep in ("dense", "packed"):
        pre = TV.precompute(model, estep=estep)
        assert np.isfinite(np.asarray(pre.U)).all()
        assert np.isfinite(np.asarray(pre.Pj)).all()
    pre = TV.precompute(model, estep="dense")
    resid = float(jnp.max(jnp.abs(
        jnp.einsum("cde,cer->cdr", sick, pre.Pj) - model.T)))
    Pj_inv = jnp.einsum("cde,cer->cdr", jnp.linalg.inv(sick), model.T)
    resid_inv = float(jnp.max(jnp.abs(
        jnp.einsum("cde,cer->cdr", sick, Pj_inv) - model.T)))
    # f32 at condition ~1e5 leaves ~eps*cond residual either way; the
    # solve must be at least as backward-stable as the explicit inverse
    assert resid <= resid_inv * 1.2 + 1e-6, (resid, resid_inv)


# ---------------------------------------------------------------------------
# packed == dense through the E-step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("formulation", ["standard", "augmented"])
def test_posterior_packed_equals_dense(formulation):
    model = _toy_model(k(7), formulation=formulation)
    n, f = _toy_stats(k(8))
    pre_d = TV.precompute(model, estep="dense")
    pre_p = TV.precompute(model, estep="packed")
    assert not pre_d.packed and pre_p.packed
    phi_d, Phi_d = TV.posterior(model, pre_d, n, f)
    phi_p, Phi_p = TV.posterior(model, pre_p, n, f)
    np.testing.assert_allclose(phi_p, phi_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Phi_p, Phi_d, rtol=1e-5, atol=1e-5)


def test_posterior_mean_only_equals_full():
    """The mean-only path (no posterior-covariance materialisation)
    returns the same phi — the serving/pipeline scoring perf fix is free.
    Dense stays bit-identical (same cho_solve, narrower RHS); the packed
    fast path reassociates the triangular solve (Giᵀ(Gi·rhs) vs
    (GiᵀGi)·rhs, DESIGN.md §9/§12), so it agrees to fp tolerance only."""
    model = _toy_model(k(9))
    n, f = _toy_stats(k(10))
    for estep in ("dense", "packed"):
        pre = TV.precompute(model, estep=estep)
        phi_full, Phi = TV.posterior(model, pre, n, f)
        phi_mean, none = TV.posterior(model, pre, n, f, mean_only=True)
        assert none is None and Phi is not None
        if estep == "dense":
            np.testing.assert_array_equal(np.asarray(phi_mean),
                                          np.asarray(phi_full))
        else:
            np.testing.assert_allclose(np.asarray(phi_mean),
                                       np.asarray(phi_full),
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            TV.extract_ivectors(model, pre, n, f),
            phi_full - model.prior[None],
            rtol=1e-6 if estep == "dense" else 1e-4,
            atol=0.0 if estep == "dense" else 1e-5)


@pytest.mark.parametrize("chunk", [5, 17, 100])   # ragged tails + one-shot
def test_em_accumulate_packed_equals_dense(chunk):
    model = _toy_model(k(11))
    n, f = _toy_stats(k(12))
    pre_d = TV.precompute(model, estep="dense")
    pre_p = TV.precompute(model, estep="packed")
    acc_d = TV.em_accumulate_scan(model, pre_d, n, f, chunk=chunk)
    acc_p = TV.em_accumulate_scan(model, pre_p, n, f, chunk=chunk)
    R = model.rank
    assert acc_p.A.shape == (n.shape[1], R * (R + 1) // 2)
    np.testing.assert_allclose(ops.unpack_symmetric(acc_p.A, R), acc_d.A,
                               rtol=1e-5, atol=1e-5)
    for a, b in ((acc_p.B, acc_d.B), (acc_p.h, acc_d.h),
                 (acc_p.H, acc_d.H), (acc_p.n_tot, acc_d.n_tot)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # the packed accumulator feeds the SAME M-step result
    m_d = TV.m_step(model, acc_d, None, False)
    m_p = TV.m_step(model, acc_p, None, False)
    np.testing.assert_allclose(m_p.T, m_d.T, rtol=1e-4, atol=1e-4)
    md_d = TV.min_divergence(model, acc_d)
    md_p = TV.min_divergence(model, acc_p)
    # min-divergence whitening goes through an eigendecomposition whose
    # eigenvector SIGNS are arbitrary under fp-last-bit input differences
    # (the packed fast path agrees to ~1e-7, not bit-exactly) — compare
    # the sign-invariant per-component subspace T_c T_cᵀ, as the trainer
    # parity test does
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("cdr,cer->cde", md_p.T, md_p.T)),
        np.asarray(jnp.einsum("cdr,cer->cde", md_d.T, md_d.T)),
        rtol=1e-4, atol=1e-4)


def test_zero_occupancy_components_and_empty_utterances():
    """Zero-occupancy components and all-zero (fully masked) utterances
    stay finite and identical across modes; an empty utterance's
    posterior mean is exactly the prior — no NaN/inf leaks from the
    packed contractions' zero rows/columns."""
    model = _toy_model(k(13))
    n, f = _toy_stats(k(14))
    n = n.at[:, 3].set(0.0).at[:, 7].set(0.0)     # dead components
    f = f.at[:, 3].set(0.0).at[:, 7].set(0.0)
    n = n.at[5].set(0.0)                          # empty utterance
    f = f.at[5].set(0.0)
    outs = {}
    for estep in ("dense", "packed"):
        pre = TV.precompute(model, estep=estep)
        phi, Phi = TV.posterior(model, pre, n, f)
        acc = TV.em_accumulate(model, pre, n, f)
        assert np.isfinite(np.asarray(phi)).all()
        assert np.isfinite(np.asarray(Phi)).all()
        for leaf in acc:
            assert np.isfinite(np.asarray(leaf)).all()
        outs[estep] = (phi, Phi)
        np.testing.assert_allclose(phi[5], model.prior, rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_allclose(outs["packed"][0], outs["dense"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["packed"][1], outs["dense"][1],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# trainer convergence parity on the tiny system config
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_system():
    dc = SpeechDataConfig(feat_dim=6, n_components=8, n_speakers=10,
                          utts_per_speaker=5, frames_per_utt=40,
                          speaker_rank=5, channel_rank=2,
                          speaker_scale=0.8, channel_scale=0.8)
    feats, labels = build_dataset(dc)
    frames = feats.reshape(-1, feats.shape[-1])
    ubm = U.train_ubm(frames, 8, jax.random.PRNGKey(3), diag_iters=3,
                      full_iters=2)
    return feats, labels, ubm


def test_trainer_parity_packed_vs_dense(tiny_system):
    """`train(estep='packed')` reproduces the `'dense'` trajectory: in
    f32 the packed E-step is the same math reassociated, so the final T
    and the EER must agree far inside the tiny config's ensemble std
    (~percent scale, EXPERIMENTS.md §Ensembles)."""
    feats, labels, ubm = tiny_system
    base = IV_SMOKE.with_overrides(
        feat_dim=6, n_components=8, ivector_dim=10, posterior_top_k=4,
        lda_dim=6, n_iters=3)
    states, eers = {}, {}
    for estep in ("dense", "packed"):
        cfg = base.with_overrides(estep=estep)
        states[estep] = TR.train(cfg, ubm, feats, n_iters=3,
                                 key=jax.random.PRNGKey(7))
        eers[estep] = evaluate_state(cfg, states[estep], feats, labels)
    # min-divergence whitening goes through eigh, whose eigenvector SIGNS
    # are arbitrary under fp-last-bit differences — compare the
    # sign-invariant per-component subspace T_c T_cᵀ, not T itself
    TTt = {e: jnp.einsum("cdr,cer->cde", states[e].model.T,
                         states[e].model.T) for e in states}
    np.testing.assert_allclose(np.asarray(TTt["packed"]),
                               np.asarray(TTt["dense"]),
                               rtol=5e-3, atol=5e-3)
    assert abs(eers["packed"] - eers["dense"]) < 0.01, eers


def test_trainer_bf16_estep_trains(tiny_system):
    """The mixed-precision knob end to end: bf16 E-step contractions
    still converge to a working extractor (finite, separates speakers at
    an EER near the f32 run's)."""
    feats, labels, ubm = tiny_system
    cfg = IV_SMOKE.with_overrides(
        feat_dim=6, n_components=8, ivector_dim=10, posterior_top_k=4,
        lda_dim=6, n_iters=3, estep="packed", estep_dtype="bfloat16")
    state = TR.train(cfg, ubm, feats, n_iters=3,
                     key=jax.random.PRNGKey(7))
    ivecs = np.asarray(TR.extract(cfg, state, feats))
    assert np.isfinite(ivecs).all()
    eer = evaluate_state(cfg, state, feats, labels)
    assert eer < 0.45, eer


# ---------------------------------------------------------------------------
# The matmul-only posterior-assembly fast path (DESIGN.md §12)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("R,block", [(5, 16), (16, 16), (37, 16), (64, 8)])
def test_tri_inverse_matches_triangular_solve(R, block):
    """Blocked matmul-only triangular inverse == the lapack reference,
    across ragged ranks (non-multiples of the block), with the inverse
    strictly lower-triangular like its input."""
    key = k(60)
    M = jax.random.normal(key, (6, R, R))
    L = jnp.matmul(M, jnp.swapaxes(M, -1, -2)) + 3.0 * jnp.eye(R)
    G = jnp.linalg.cholesky(L)
    Gi = ops.tri_inverse(G, block=block)
    resid = np.abs(np.asarray(jnp.matmul(G, Gi)) - np.eye(R)).max()
    assert resid < 1e-5, resid
    want = jax.scipy.linalg.solve_triangular(
        G, jnp.broadcast_to(jnp.eye(R), G.shape), lower=True)
    np.testing.assert_allclose(np.asarray(Gi), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # strictly triangular: no garbage above the diagonal
    iu = np.triu_indices(R, 1)
    assert np.abs(np.asarray(Gi)[:, iu[0], iu[1]]).max() == 0.0


def test_posterior_packed_fast_path_matches_cho_solve():
    """The packed posterior assembly (tri_inverse + syrk, never a
    batched cho_solve) agrees with the dense cho_solve reference on both
    phi and Phi, and em_accumulate's direct packed PP assembly (never a
    dense [U, R, R] PP) matches the dense accumulator."""
    model = _toy_model(k(61), C=10, D=5, R=23)   # ragged vs block=16
    n, f = _toy_stats(k(62), Utt=13, C=10, D=5)
    pre_d = TV.precompute(model, estep="dense")
    pre_p = TV.precompute(model, estep="packed")
    phi_d, Phi_d = TV.posterior(model, pre_d, n, f)
    phi_p, Phi_p = TV.posterior(model, pre_p, n, f)
    np.testing.assert_allclose(np.asarray(phi_p), np.asarray(phi_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Phi_p), np.asarray(Phi_d),
                               rtol=1e-4, atol=1e-5)
    acc_d = TV.em_accumulate(model, pre_d, n, f)
    acc_p = TV.em_accumulate(model, pre_p, n, f)
    R = model.rank
    np.testing.assert_allclose(
        np.asarray(ops.unpack_symmetric(acc_p.A, R)), np.asarray(acc_d.A),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(acc_p.B), np.asarray(acc_d.B),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# M-step T update: Cholesky + tri_inverse against a float64 solve
# ---------------------------------------------------------------------------


def _mstep_accum(key, C, D, R, prior0, estep):
    """EMAccum built as the E-step builds it: A_c = Σ_u n_uc (Phi_u +
    phi_u phi_uᵀ), phi_u[0] about ``prior0`` (100 in the augmented
    formulation), and component 0 never occupied (A_0 = 0, B_0 = 0)."""
    Utt = 3 * R
    n = np.asarray(jax.random.uniform(key, (Utt, C), minval=0.0,
                                      maxval=3.0), np.float64)
    n[:, 0] = 0.0
    phi = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                       (Utt, R)), np.float64)
    phi[:, 0] += prior0
    M = np.asarray(jax.random.normal(jax.random.fold_in(key, 2),
                                     (Utt, R, R)), np.float64) * 0.3
    Phi = M @ np.swapaxes(M, 1, 2) / R + 0.05 * np.eye(R)
    A = np.einsum("uc,urs->crs", n, Phi + phi[:, :, None] * phi[:, None])
    f = np.asarray(jax.random.normal(jax.random.fold_in(key, 3),
                                     (Utt, C, D)), np.float64) * n[..., None]
    B = np.einsum("ucd,ur->cdr", f, phi)
    A32 = jnp.asarray(A, jnp.float32)
    if estep == "packed":
        A32 = ops.pack_symmetric(A32)
    acc = TV.EMAccum(A=A32, B=jnp.asarray(B, jnp.float32),
                     h=jnp.zeros((R,)), H=jnp.zeros((R, R)),
                     n_tot=jnp.asarray(n.sum(0), jnp.float32),
                     n_utts=jnp.asarray(Utt, jnp.float32))
    return acc, A, B


@pytest.mark.parametrize("estep", ["packed", "dense"])
@pytest.mark.parametrize("R", [12, 21])   # tri_inverse: one block; 11 + 10
@pytest.mark.parametrize("prior0", [0.0, 100.0])   # standard, augmented
def test_m_step_T_matches_float64_solve(estep, R, prior0):
    """T_c = B_c (A_c + 1e-6 I)⁻¹ from the Cholesky + tri_inverse path
    agrees with a float64 numpy solve of the same A and B, for packed and
    dense accumulators, odd and even R, an augmented-style A (phi[0]
    about 100) and a zero-occupancy component (T_0 = 0, finite)."""
    C, D = 6, 5
    model = _toy_model(k(70), C=C, D=D, R=R)
    acc, A, B = _mstep_accum(k(71 + R), C, D, R, prior0, estep)
    T = np.asarray(TV.m_step(model, acc, None, False).T, np.float64)
    assert np.isfinite(T).all()
    want = np.swapaxes(np.linalg.solve(A + 1e-6 * np.eye(R),
                                       np.swapaxes(B, 1, 2)), 1, 2)
    np.testing.assert_array_equal(T[0], 0.0)
    rel = (np.linalg.norm(T[1:] - want[1:], axis=(1, 2))
           / np.linalg.norm(want[1:], axis=(1, 2)))
    assert rel.max() < 5e-6, rel
