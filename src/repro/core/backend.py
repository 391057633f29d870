"""Scoring backend: centring, whitening, length-norm, LDA, two-covariance
PLDA, EER — the paper's §4.1 evaluation chain. Training of the small
projection/scoring models runs on host (numpy/scipy); scoring is jnp."""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg as sla

f32 = jnp.float32


def length_norm(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-10)


def whitener(x) -> Tuple[jax.Array, jax.Array]:
    """(mean, W) with W whitening the centred data."""
    mu = jnp.mean(x, axis=0)
    xc = x - mu
    cov = xc.T @ xc / x.shape[0] + 1e-6 * jnp.eye(x.shape[1])
    lam, Q = jnp.linalg.eigh(cov)
    W = (Q * jnp.maximum(lam, 1e-10) ** -0.5) @ Q.T
    return mu, W


class LDA(NamedTuple):
    mean: jax.Array
    proj: jax.Array  # [D, K]


def train_lda(x, labels, out_dim: int) -> LDA:
    """Classic Fisher LDA via generalized eigenproblem Sb v = λ Sw v."""
    x = np.asarray(x, np.float64)
    labels = np.asarray(labels)
    classes = np.unique(labels)
    mu = x.mean(axis=0)
    D = x.shape[1]
    Sw = np.zeros((D, D))
    Sb = np.zeros((D, D))
    for c in classes:
        xc = x[labels == c]
        mc = xc.mean(axis=0)
        d = xc - mc
        Sw += d.T @ d
        g = (mc - mu)[:, None]
        Sb += xc.shape[0] * (g @ g.T)
    Sw = Sw / x.shape[0] + 1e-4 * np.eye(D)
    Sb = Sb / x.shape[0]
    evals, evecs = sla.eigh(Sb, Sw)
    order = np.argsort(evals)[::-1][:out_dim]
    return LDA(jnp.asarray(mu, f32), jnp.asarray(evecs[:, order], f32))


def apply_lda(lda: LDA, x):
    return (x - lda.mean) @ lda.proj


class PLDA(NamedTuple):
    mean: jax.Array
    B: jax.Array  # between-class covariance
    W: jax.Array  # within-class covariance


def train_plda(x, labels) -> PLDA:
    """Two-covariance PLDA from moment estimates."""
    x = np.asarray(x, np.float64)
    labels = np.asarray(labels)
    classes = np.unique(labels)
    mu = x.mean(axis=0)
    D = x.shape[1]
    Sw = np.zeros((D, D))
    means = []
    for c in classes:
        xc = x[labels == c]
        mc = xc.mean(axis=0)
        means.append(mc)
        d = xc - mc
        Sw += d.T @ d
    Sw = Sw / x.shape[0]
    M = np.stack(means) - mu
    Sb = M.T @ M / len(classes)
    eye = np.eye(D)
    return PLDA(jnp.asarray(mu, f32), jnp.asarray(Sb + 1e-6 * eye, f32),
                jnp.asarray(Sw + 1e-6 * eye, f32))


def _spd_inverse(M):
    """SPD inverse + logdet via Cholesky (identity-RHS ``cho_solve``).

    The sanctioned path (DESIGN.md §9, rule NUM002): ``jnp.linalg.inv``
    pivots an LU factorisation, which is exactly what goes unstable on
    the near-singular within-class covariances PLDA sees after LDA;
    the Cholesky solve is backward-stable on the same inputs. The solve
    result is symmetrised (fp round-off breaks exact symmetry) so the
    quadratic forms downstream stay symmetric.
    """
    chol = jnp.linalg.cholesky(M)
    eye = jnp.eye(M.shape[-1], dtype=M.dtype)
    Minv = jax.scipy.linalg.cho_solve((chol, True), eye)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    return 0.5 * (Minv + Minv.T), logdet


def _plda_coeffs(plda: PLDA):
    """(Q, P, const) of the two-covariance LLR quadratic form:

    llr = log N([x;y]; 0, [[T, B],[B, T]]) - log N([x;y]; 0, [[T, 0],[0, T]])
    with T = B + W; expands to 0.5 x'Qx + 0.5 y'Qy + x'Py + const.

    T = B + W is SPD and so is its Schur complement S = T - B T^{-1} B
    (the joint same-speaker covariance [[T, B],[B, T]] is PD whenever W
    is), so both inverses run through Cholesky, and the joint logdet
    follows from the Schur determinant identity
    det([[T, B],[B, T]]) = det(T) det(S) — no LU-based ``slogdet`` of
    the 2D x 2D block matrix.
    """
    B, W = plda.B, plda.W
    T = B + W
    Tinv, logdet_T = _spd_inverse(T)
    S = T - B @ Tinv @ B          # Schur complement
    Sinv, logdet_S = _spd_inverse(S)
    Q = Tinv - Sinv               # x'Qx coefficient
    P = Sinv @ B @ Tinv           # cross coefficient
    # logdet_joint - 2 logdet_T == (logdet_T + logdet_S) - 2 logdet_T
    const = -0.5 * (logdet_S - logdet_T)
    return Q, P, const


def _plda_terms(plda: PLDA, enroll, test):
    """(x Q x-terms [N], y Q y-terms [M], x P [N, R], y [M, R], const).

    The f32 products run at HIGHEST precision: with a near-singular W the
    quadratic and cross terms are ~1e4 and cancel to an O(1) score, so
    the TPU's default bf16 rounding of matmul inputs would swamp it."""
    Q, P, const = _plda_coeffs(plda)
    x = enroll - plda.mean
    y = test - plda.mean
    hi = jax.lax.Precision.HIGHEST
    qx = jnp.sum(jnp.dot(x, Q, precision=hi) * x, axis=1)
    qy = jnp.sum(jnp.dot(y, Q, precision=hi) * y, axis=1)
    return qx, qy, jnp.dot(x, P, precision=hi), y, const


def plda_score_matrix(plda: PLDA, enroll, test) -> jax.Array:
    """LLR for every (enroll, test) pair: [N_enroll, N_test].

    The cross term is a broadcast multiply-and-sum, not a matmul, so each
    entry rounds exactly as ``plda_score_pairs`` does: under the
    cancellation above, a matmul's different summation order moves the
    score by ~1e-4 relative."""
    qx, qy, xP, y, const = _plda_terms(plda, enroll, test)
    cross = jnp.sum(xP[:, None, :] * y[None, :, :], axis=-1)
    return 0.5 * (qx[:, None] + qy[None, :]) + cross + const


def plda_score_pairs(plda: PLDA, enroll, test) -> jax.Array:
    """LLR for N aligned (enroll[i], test[i]) trial pairs: [N].

    O(N) — trial-list evaluation must not build the full N x N score
    matrix only to read its diagonal.
    """
    qx, qy, xP, y, const = _plda_terms(plda, enroll, test)
    cross = jnp.sum(xP * y, axis=1)
    return 0.5 * (qx + qy) + cross + const


def eer(scores, labels) -> float:
    """Equal error rate; scores: [N], labels: [N] (1 target, 0 nontarget)."""
    s = np.asarray(scores, np.float64)
    l = np.asarray(labels)
    order = np.argsort(s)
    l_sorted = l[order]
    n_tar = max(int(l_sorted.sum()), 1)
    n_non = max(int((1 - l_sorted).sum()), 1)
    # sweeping the threshold upward: miss grows, false-alarm shrinks
    miss = np.concatenate([[0.0], np.cumsum(l_sorted) / n_tar])
    fa = np.concatenate([[1.0], 1.0 - np.cumsum(1 - l_sorted) / n_non])
    idx = np.argmin(np.abs(miss - fa))
    return float(0.5 * (miss[idx] + fa[idx]))
