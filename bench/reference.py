"""Plain reference of i-vector extractor training and extraction.

Written from the algorithm (Kenny's total-variability EM in the
augmented Kaldi formulation, the paper's section 2-3) and independent of
the program under test: it imports nothing from it and is given only the
benchmark's own seeded inputs. Everything is dense and straightforward:

* alignment: diagonal-covariance preselection of the top K components,
  their full-covariance log-likelihoods (the dense quadratic form, then
  a gather), softmax over the K, Kaldi's posterior floor with the
  arg-max kept, renormalisation;
* Baum-Welch statistics: zeroth and first order on the device from
  dense posteriors; the second order on the host in float64, one
  component at a time over the frames that gave it weight;
* E-step: full [R, R] precisions and posterior covariances by Cholesky
  solves, accumulators A (dense [C, R, R]), B, h, H;
* M-step: T_c = B_c A_c^-1, Sigma from the residual second moments with
  Kaldi's variance floor (a tenth of the occupancy-weighted mean
  residual, applied in the positive-semidefinite order), then minimum
  divergence with the Householder step of the augmented formulation;
* realignment: the UBM means become T_c[:, 0] * prior[0].

Every contraction runs at the precision it is given: HIGHEST, float32
as the configuration states, for the reference, and HIGH (three bf16
passes) for the control that the comparison has to reject.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
_LOG2PI = 1.8378770664093453
COV_FLOOR = 1e-4
VAR_FLOOR_FACTOR = 0.1


def _ein(spec: str, a, b, prec):
    """Two-operand einsum at ``prec``. HIGH is three bf16 passes (the
    high and low bf16 parts of each operand, the low-by-low product
    left out); the CPU runs every float32 dot exactly whatever the
    precision asks, so there the three passes are spelled out."""
    if prec == HIGH and jax.default_backend() == "cpu":
        def split(x):
            hi = x.astype(jnp.bfloat16).astype(f32)
            return hi, (x - hi).astype(jnp.bfloat16).astype(f32)
        (ah, al), (bh, bl) = split(a), split(b)
        return sum(jnp.einsum(spec, x, y, precision=HIGHEST)
                   for x, y in ((ah, bh), (ah, bl), (al, bh)))
    return jnp.einsum(spec, a, b, precision=prec)


class Model(NamedTuple):
    T: jax.Array        # [C, D, R]
    Sigma: jax.Array    # [C, D, D]
    prior: jax.Array    # [R]


class UBM(NamedTuple):
    weights: jax.Array  # [C]
    means: jax.Array    # [C, D]
    covs: jax.Array     # [C, D, D]


class Stats(NamedTuple):
    n: jax.Array        # [U, C]
    f: jax.Array        # [U, C, D]
    loglik: float       # summed over valid frames
    frames: float


# ---------------------------------------------------------------------------
# Alignment and zeroth/first-order statistics
# ---------------------------------------------------------------------------


def _coeffs(ubm: UBM, prec):
    w, mu, covs = ubm
    C, D = mu.shape
    var = jnp.diagonal(covs, axis1=1, axis2=2)
    dconst = (-0.5 * (jnp.sum(jnp.log(var), 1) + D * _LOG2PI
                      + jnp.sum(mu * mu / var, 1)) + jnp.log(w))
    chol = jnp.linalg.cholesky(covs)
    eye = jnp.broadcast_to(jnp.eye(D, dtype=f32), covs.shape)
    P = jax.scipy.linalg.cho_solve((chol, True), eye)
    P = 0.5 * (P + P.transpose(0, 2, 1))
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=1, axis2=2)), 1)
    lin = _ein("cij,cj->ci", P, mu, prec)
    const = (-0.5 * (logdet + D * _LOG2PI
                     + _ein("ci,ci->c", mu, lin, prec))
             + jnp.log(w))
    return (dconst, (mu / var).T, (-0.5 / var).T, const, lin.T,
            P.reshape(C, D * D))


def _align_block(coeffs, x, mask, top_k: int, floor: float, prec):
    """x [N, D] -> (posteriors [N, K], component ids [N, K], lse [N])."""
    dconst, dlin, dquad, const, lin, Pf = coeffs
    N, D = x.shape
    ds = (dconst[None] + _ein("nd,dc->nc", x, dlin, prec)
          + _ein("nd,dc->nc", x * x, dquad, prec))
    _, sel = jax.lax.top_k(ds, top_k)
    x2 = (x[:, :, None] * x[:, None, :]).reshape(N, D * D)
    ll = (const[None] + _ein("nd,dc->nc", x, lin, prec)
          - 0.5 * _ein("ne,ce->nc", x2, Pf, prec))
    sel_ll = jnp.take_along_axis(ll, sel, axis=1)
    lse = jax.scipy.special.logsumexp(sel_ll, axis=1)
    post = jnp.exp(sel_ll - lse[:, None])
    keep = post >= floor
    best = jax.nn.one_hot(jnp.argmax(post, 1), top_k, dtype=bool)
    keep = keep | (~jnp.any(keep, 1, keepdims=True) & best)
    post = jnp.where(keep, post, 0.0)
    post = post / jnp.maximum(jnp.sum(post, 1, keepdims=True), 1e-10)
    valid = mask > 0
    return (jnp.where(valid[:, None], post, 0.0), sel,
            jnp.where(valid, lse, 0.0))


@functools.partial(jax.jit, static_argnames=("top_k", "floor", "block",
                                             "prec"))
def _align(ubm: UBM, feats, mask, *, top_k, floor, block, prec):
    U, F, D = feats.shape
    C = ubm.means.shape[0]
    coeffs = _coeffs(ubm, prec)

    def one(args):
        xb, mb = args                                  # [b, F, D], [b, F]
        b = xb.shape[0]
        post, sel, lse = _align_block(coeffs, xb.reshape(b * F, D),
                                      mb.reshape(b * F), top_k, floor,
                                      prec)
        rows = jnp.repeat(jnp.arange(b * F), top_k)
        gamma = jnp.zeros((b * F, C), f32).at[rows, sel.reshape(-1)].add(
            post.reshape(-1)).reshape(b, F, C)
        n = jnp.sum(gamma, axis=1)
        f = _ein("bfc,bfd->bcd", gamma, xb, prec)
        return (n, f, post.reshape(b, F, top_k), sel.reshape(b, F, top_k),
                jnp.sum(lse))

    g = U // block
    out = jax.lax.map(one, (feats.reshape(g, block, F, D),
                            mask.reshape(g, block, F)))
    n, f, post, sel, lse = out
    return (n.reshape(U, C), f.reshape(U, C, -1),
            post.reshape(U * F, top_k), sel.reshape(U * F, top_k),
            jnp.sum(lse))


def second_moments(x: np.ndarray, post: np.ndarray, sel: np.ndarray,
                   C: int) -> np.ndarray:
    """S_c = sum_t gamma_tc x_t x_t^T in float64 on the host, one
    component at a time over the (frame, slot) pairs with weight."""
    x = np.asarray(x, np.float64)
    w = np.asarray(post).reshape(-1)
    comp = np.asarray(sel).reshape(-1)
    K = np.asarray(post).shape[1]
    keep = np.flatnonzero(w > 0)
    order = keep[np.argsort(comp[keep], kind="stable")]
    bounds = np.searchsorted(comp[order], np.arange(C + 1))
    D = x.shape[1]
    S = np.zeros((C, D, D))
    for c in range(C):
        pairs = order[bounds[c]:bounds[c + 1]]
        if pairs.size:
            xc = x[pairs // K]
            S[c] = (xc * w[pairs, None]).T @ xc
    return S


def _divisor(U: int, most: int) -> int:
    """The largest divisor of U that is at most ``most`` (and >= 1)."""
    return max(b for b in range(1, max(1, min(most, U)) + 1) if U % b == 0)


def align_stats(ubm: UBM, feats, mask=None, *, top_k: int, floor: float,
                prec=HIGHEST, block_frames: int = 8192,
                second_order: bool = True):
    """Statistics of utterances [U, F, D] (mask [U, F]) against the UBM,
    aligned about ``block_frames`` frames at a time:
    (Stats, S [C, D, D] float64 or None)."""
    U, F, D = feats.shape
    if mask is None:
        mask = jnp.ones((U, F), f32)
    block = _divisor(U, block_frames // F)
    n, f, post, sel, ll = _align(ubm, feats, mask, top_k=top_k,
                                 floor=float(floor), block=block, prec=prec)
    S = None
    if second_order:
        S = second_moments(np.asarray(feats).reshape(U * F, D),
                           np.asarray(post), np.asarray(sel),
                           ubm.means.shape[0])
    return Stats(n, f, float(ll), float(jnp.sum(mask))), S


# ---------------------------------------------------------------------------
# E-step, M-step, minimum divergence
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("prec",))
def _precompute(model: Model, prec):
    chol = jnp.linalg.cholesky(model.Sigma)
    Pj = jax.scipy.linalg.cho_solve((chol, True), model.T)     # [C, D, R]
    Uc = _ein("cdr,cds->crs", model.T, Pj, prec)
    return Pj, 0.5 * (Uc + Uc.transpose(0, 2, 1))


def _posterior(model: Model, Pj, Uc, n, f, prec, mean_only: bool):
    R = model.prior.shape[0]
    L = jnp.eye(R, dtype=f32) + _ein("uc,crs->urs", n, Uc, prec)
    rhs = model.prior[None] + _ein("cdr,ucd->ur", Pj, f, prec)
    chol = jnp.linalg.cholesky(L)
    phi = jax.scipy.linalg.cho_solve((chol, True), rhs[..., None])[..., 0]
    if mean_only:
        return phi, None
    eye = jnp.broadcast_to(jnp.eye(R, dtype=f32), L.shape)
    return phi, jax.scipy.linalg.cho_solve((chol, True), eye)


@functools.partial(jax.jit, static_argnames=("prec",), donate_argnums=(0,))
def _estep_block(acc, model: Model, Pj, Uc, nb, fb, prec):
    """Adds one block of utterances to the accumulators (A, B, h, H)."""
    phi, Phi = _posterior(model, Pj, Uc, nb, fb, prec, False)
    A, B, h, H = acc
    PP = Phi + phi[:, :, None] * phi[:, None, :]
    dphi = phi - model.prior[None]
    return (A + _ein("uc,urs->crs", nb, PP, prec),
            B + _ein("ucd,ur->cdr", fb, phi, prec),
            h + jnp.sum(dphi, 0),
            H + jnp.sum(Phi, 0) + _ein("ur,us->rs", dphi, dphi, prec))


def _estep(model: Model, n, f, *, block: int, prec):
    """Accumulators (A, B, h, H, n_tot, n_utts) over all utterances, a
    block at a time: the dense [C, R, R] A is updated in place."""
    Pj, Uc = _precompute(model, prec)
    U, C = n.shape
    D, R = model.T.shape[1:]
    acc = (jnp.zeros((C, R, R), f32), jnp.zeros((C, D, R), f32),
           jnp.zeros((R,), f32), jnp.zeros((R, R), f32))
    for s in range(0, U, block):
        acc = _estep_block(acc, model, Pj, Uc, n[s:s + block],
                           f[s:s + block], prec)
    return acc + (jnp.sum(n, 0), jnp.asarray(U, f32))


def _floor_covariances(covs, floor, prec):
    D = floor.shape[0]
    L = jnp.linalg.cholesky(floor)
    Li = jax.scipy.linalg.solve_triangular(L, jnp.eye(D, dtype=f32),
                                           lower=True)
    M = _ein("cjk,lk->cjl", _ein("ij,cjk->cik", Li, covs, prec), Li, prec)
    lam, Q = jnp.linalg.eigh(0.5 * (M + M.transpose(0, 2, 1)))
    M = _ein("cir,cjr->cij", Q * jnp.maximum(lam, 1.0)[:, None, :], Q,
             prec)
    return _ein("cjk,lk->cjl", _ein("ij,cjk->cik", L, M, prec), L, prec)


@functools.partial(jax.jit, static_argnames=("update_sigma", "prec"))
def _mstep(model: Model, acc, S, *, update_sigma, prec):
    A, B, h, H, n_tot, n_utts = acc
    R = model.prior.shape[0]
    A_reg = A + 1e-6 * jnp.eye(R, dtype=f32)[None]
    T = jnp.linalg.solve(A_reg, B.transpose(0, 2, 1)).transpose(0, 2, 1)
    Sigma = model.Sigma
    if update_sigma:
        TB = _ein("cdr,cer->cde", T, B, prec)
        resid = S - 0.5 * (TB + TB.transpose(0, 2, 1))
        D = resid.shape[1]
        floor = (VAR_FLOOR_FACTOR * jnp.sum(resid, 0)
                 / jnp.maximum(jnp.sum(n_tot), 1e-6))
        floor = 0.5 * (floor + floor.T) + COV_FLOOR * jnp.eye(D, dtype=f32)
        Sigma = _floor_covariances(
            resid / jnp.maximum(n_tot, 1e-6)[:, None, None], floor, prec)
    # minimum divergence, augmented formulation
    nu = jnp.maximum(n_utts, 1.0)
    dh = h / nu
    G = H / nu - dh[:, None] * dh[None, :] + 1e-8 * jnp.eye(R, dtype=f32)
    hh = dh + model.prior
    lam, Q = jnp.linalg.eigh(G)
    lam = jnp.maximum(lam, 1e-10)
    P1 = (Q * (lam ** -0.5)[None, :]).T
    P1_inv = Q * (lam ** 0.5)[None, :]
    p1h = _ein("rs,s->r", P1, hh, prec)
    h_t = p1h / jnp.maximum(jnp.linalg.norm(p1h), 1e-10)
    e1 = jnp.zeros((R,), f32).at[0].set(1.0)
    alpha = jnp.maximum(2.0 * (1.0 - h_t[0]), 1e-10) ** -0.5
    a = alpha * h_t - alpha * e1
    degenerate = (1.0 - h_t[0]) < 1e-8
    P2 = jnp.where(degenerate, jnp.eye(R, dtype=f32),
                   jnp.eye(R, dtype=f32) - 2.0 * a[:, None] * a[None, :])
    T = _ein("cdr,rt->cdt", T, _ein("rs,st->rt", P1_inv, P2, prec), prec)
    prior = jnp.where(degenerate, p1h, _ein("rs,s->r", P2, p1h, prec))
    return Model(T, Sigma, prior)


def em_iteration(model: Model, stats: Stats, S: Optional[np.ndarray], *,
                 update_sigma: bool, prec=HIGHEST, block: int = 64) -> Model:
    """One EM iteration (E-step, M-step, minimum divergence)."""
    acc = _estep(model, stats.n, stats.f, block=_divisor(
        stats.n.shape[0], block), prec=prec)
    S_dev = None if S is None else jnp.asarray(S, f32)
    return _mstep(model, acc, S_dev, update_sigma=update_sigma, prec=prec)


def realigned_means(model: Model) -> jax.Array:
    """UBM means after realignment: T_c[:, 0] * prior[0]."""
    return model.T[:, :, 0] * model.prior[0]


# ---------------------------------------------------------------------------
# Extraction
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("prec",))
def _extract(model: Model, n, f, prec):
    Pj, Uc = _precompute(model, prec)
    phi, _ = _posterior(model, Pj, Uc, n, f, prec, True)
    iv = phi - model.prior[None]
    return iv / jnp.maximum(jnp.linalg.norm(iv, axis=1, keepdims=True),
                            1e-10)


def extract(ubm: UBM, model: Model, feats, mask, *, top_k: int,
            floor: float, prec=HIGHEST) -> np.ndarray:
    """Length-normalised i-vectors of padded utterances [U, F, D] with
    valid-frame mask [U, F]: alignment, statistics, posterior mean."""
    st, _ = align_stats(ubm, feats, mask, top_k=top_k, floor=floor,
                        prec=prec, second_order=False)
    return np.asarray(_extract(model, st.n, st.f, prec))
