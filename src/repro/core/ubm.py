"""Universal background models: diagonal- and full-covariance GMMs with EM.

The full-covariance log-likelihood is evaluated densely as an MXU matmul via
the quadratic-form vec-trick (see DESIGN.md §2):

    loglik[f, c] = const_c + x_f . lin_c - 0.5 * vec(x_f x_f^T) . vec(P_c)

with P_c the precision matrix — [F, D^2] @ [D^2, C] instead of gathered
per-component quadratic forms. ``repro.kernels.gmm_loglik`` provides the
fused Pallas kernel (expansion built in VMEM); this module's jnp path is the
oracle and the CPU execution path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32
# f32 contractions run at HIGHEST precision: a TPU's default f32 matmul
# rounds its inputs to bf16
HI = jax.lax.Precision.HIGHEST
_LOG2PI = 1.8378770664093453


@dataclass
class DiagGMM:
    weights: jax.Array  # [C]
    means: jax.Array    # [C, D]
    vars: jax.Array     # [C, D]

    @property
    def n_components(self):
        return self.weights.shape[0]


@dataclass
class FullGMM:
    weights: jax.Array  # [C]
    means: jax.Array    # [C, D]
    covs: jax.Array     # [C, D, D]

    @property
    def n_components(self):
        return self.weights.shape[0]

    def to_diag(self) -> DiagGMM:
        d = jnp.diagonal(self.covs, axis1=1, axis2=2)
        return DiagGMM(self.weights, self.means, d)


# ---------------------------------------------------------------------------
# Log-likelihoods
# ---------------------------------------------------------------------------


def diag_coeffs(gmm: DiagGMM) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(const [C], lin [D, C], quad [D, C]) natural parameters of the diag
    log-likelihood — the single source of this coefficient math (the
    sharded path in ``launch/ivector_cell.py`` shards these over 'model')."""
    inv = 1.0 / gmm.vars
    const = (-0.5 * (jnp.sum(jnp.log(gmm.vars), axis=1)
                     + gmm.means.shape[1] * _LOG2PI
                     + jnp.sum(gmm.means ** 2 * inv, axis=1))
             + jnp.log(gmm.weights))
    return (const.astype(f32), (gmm.means * inv).T.astype(f32),
            (-0.5 * inv).T.astype(f32))


def diag_loglik_from_coeffs(x, const, lin, quad) -> jax.Array:
    """x: [F, D] with ``diag_coeffs`` output (possibly a component shard)
    -> [F, C] per-component log-likelihood (+ log weight). Accumulation
    is pinned to f32 (rule NUM001): bf16 feature chunks must widen in
    the MXU, not carry a bf16 partial sum."""
    return (const[None]
            + jnp.dot(x, lin, precision=HI, preferred_element_type=f32)
            + jnp.dot(x * x, quad, precision=HI,
                      preferred_element_type=f32)).astype(f32)


def diag_loglik(gmm: DiagGMM, x) -> jax.Array:
    """x: [F, D] -> [F, C] per-component log-likelihood (+ log weight)."""
    return diag_loglik_from_coeffs(x, *diag_coeffs(gmm))


def full_precisions(gmm: FullGMM) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(const [C], lin [C, D], P [C, D, D]) for the vec-trick evaluation."""
    chol = jnp.linalg.cholesky(gmm.covs)
    # precision via identity-RHS cho_solve on the factor already in hand
    # (DESIGN.md §9 / rule NUM002: LU-based `inv` is banned — it is the
    # path that poisoned precomputes on near-singular Σ in PR 4), then
    # symmetrised: the solve round-off would otherwise leak asymmetry
    # into the vec-trick quadratic form
    D = gmm.covs.shape[-1]
    P = jax.scipy.linalg.cho_solve(
        (chol, True),
        jnp.broadcast_to(jnp.eye(D, dtype=gmm.covs.dtype), gmm.covs.shape))
    P = 0.5 * (P + P.transpose(0, 2, 1))
    logdet = 2.0 * jnp.sum(
        jnp.log(jnp.diagonal(chol, axis1=1, axis2=2)), axis=1)
    lin = jnp.einsum("cij,cj->ci", P, gmm.means, precision=HI)
    const = (-0.5 * (logdet + gmm.means.shape[1] * _LOG2PI
                     + jnp.einsum("ci,ci->c", gmm.means, lin,
                                  precision=HI))
             + jnp.log(gmm.weights))
    return const.astype(f32), lin.astype(f32), P.astype(f32)


def full_loglik(gmm: FullGMM, x, precomp=None) -> jax.Array:
    """x: [F, D] -> [F, C] via the dense vec-trick matmul (routed through
    the kernel wrapper: Pallas on TPU, jnp reference elsewhere)."""
    from repro.kernels import ops
    const, lin, P = precomp if precomp is not None else full_precisions(gmm)
    D = x.shape[1]
    return ops.gmm_loglik(x, const, lin.T, P.reshape(-1, D * D))


def rescore_pack(precomp) -> jax.Array:
    """``full_precisions`` output -> [C, 1 + D + D²] packed rows
    A[c] = [const_c | lin_c | vec(P_c)] — the gather unit of the sparse
    rescoring kernel (DESIGN.md §8): per frame-tile the selected rows are
    copied HBM→VMEM as one batch of coalesced row DMAs (sorted by id so
    duplicate/adjacent components become near-sequential traffic; the
    fused kernel pipelines them through a depth-``dma_depth`` semaphore
    ring). Built once per UBM alongside the precompute and cached in
    ``engine.UBMPack`` / the serving session."""
    from repro.kernels import ref
    const, lin, P = precomp
    C, D = lin.shape
    return ref.rescore_pack(const, lin.T, P.reshape(C, D * D))


def align_pack(precomp) -> jax.Array:
    """``full_precisions`` output -> [C, 1 + D + D(D+1)/2] packed-SYMMETRIC
    rows A2[c] = [const_c | lin_c | -0.5·triu(P_c)] — the GEMM operand of
    the fused alignment path (``rescore='fused'``, DESIGN.md §12): the
    precision matrix is symmetric, so only the upper triangle rides along
    (≈2× smaller rows than ``rescore_pack``) and the −0.5 quadratic weight
    is folded in at pack time. Built once per UBM and cached in
    ``engine.UBMPack.align_A`` / the serving session."""
    from repro.kernels import ref
    const, lin, P = precomp
    C, D = lin.shape
    return ref.align_pack(const, lin.T, P.reshape(C, D * D))


def full_rescore(gmm, x, sel, precomp=None, pack=None) -> jax.Array:
    """x: [F, D], sel: [F, K] component ids -> [F, K] loglik of ONLY the
    selected components (sparse gather-and-rescore; never materialises
    [F, C]). ``gmm`` may be None when ``precomp`` is given."""
    from repro.kernels import ops
    const, lin, P = precomp if precomp is not None else full_precisions(gmm)
    D = x.shape[1]
    return ops.gmm_rescore(x, sel, const, lin.T, P.reshape(-1, D * D),
                           pack=pack)


def full_rescore_fused(gmm, x, sel, precomp=None, pack=None) -> jax.Array:
    """x: [F, D], sel: [F, K] -> [F, K] selected logliks via the fused
    packed-GEMM path (DESIGN.md §12): one GEMM against the
    packed-symmetric ``align_pack`` rows instead of per-slot gathers.
    Identical to ``full_rescore``/dense-then-gather to f32 rounding;
    ``gmm`` may be None when ``precomp``/``pack`` is given."""
    from repro.kernels import ops
    if pack is None:
        pack = align_pack(
            precomp if precomp is not None else full_precisions(gmm))
    return ops.gmm_rescore_fused(x, sel, pack)


# ---------------------------------------------------------------------------
# EM training (E-side streamed through core/engine.py; M-steps here)
# ---------------------------------------------------------------------------

VAR_FLOOR = 1e-3
WEIGHT_FLOOR = 1e-8


def init_diag_from_data(x, C: int, key, mask=None) -> DiagGMM:
    """Random-frame means, global variance init.

    ``x`` may be flat [F, D] or batched [U, F, D]; with ``mask`` the means
    are drawn from (and the variance computed over) valid frames only.
    """
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    if mask is None:
        idx = jax.random.choice(key, xf.shape[0], (C,), replace=False)
        gvar = jnp.var(xf, axis=0) + VAR_FLOOR
    else:
        m = mask.reshape(-1).astype(f32)
        tot = jnp.maximum(jnp.sum(m), 1.0)
        xm = jnp.where(m[:, None] > 0, xf, 0.0)
        mean = jnp.sum(xm, axis=0) / tot
        gvar = jnp.sum(xm * xm, axis=0) / tot - mean ** 2 + VAR_FLOOR
        idx = jax.random.choice(key, xf.shape[0], (C,), replace=False,
                                p=m / jnp.sum(m))
    return DiagGMM(jnp.full((C,), 1.0 / C, f32), xf[idx].astype(f32),
                   jnp.broadcast_to(gvar, (C, D)).astype(f32))


def renormalised_weights(n) -> jax.Array:
    """Occupancies -> mixture weights: normalise, floor, renormalise.
    Flooring alone leaves the weights summing to > 1 (every floored
    component adds mass); the second normalisation restores sum == 1."""
    w = jnp.maximum(n / jnp.maximum(jnp.sum(n), 1e-10), WEIGHT_FLOOR)
    return w / jnp.sum(w)


def diag_m_step(n, f, ss) -> DiagGMM:
    """M-step from streamed sufficient stats (n [C], f [C, D], ss [C, D])."""
    n_safe = jnp.maximum(n, 1e-6)
    means = f / n_safe[:, None]
    vars_ = jnp.maximum(ss / n_safe[:, None] - means ** 2, VAR_FLOOR)
    return DiagGMM(renormalised_weights(n), means, vars_)


def full_m_step(n, f, ss) -> FullGMM:
    """M-step from streamed sufficient stats (ss [C, D, D])."""
    n_safe = jnp.maximum(n, 1e-6)
    means = f / n_safe[:, None]
    covs = (ss / n_safe[:, None, None]
            - means[:, :, None] * means[:, None, :])
    D = covs.shape[1]
    covs = 0.5 * (covs + covs.transpose(0, 2, 1)) + VAR_FLOOR * jnp.eye(D)[None]
    return FullGMM(renormalised_weights(n), means, covs)


def psd_floor(covs, floor: float = VAR_FLOOR) -> jax.Array:
    """Eigenvalue-clipped covariance floor ([..., D, D]): the strongest
    floor — guarantees every covariance is PSD with spectrum >= floor."""
    covs = 0.5 * (covs + jnp.swapaxes(covs, -1, -2))
    lam, Q = jnp.linalg.eigh(covs)
    lam = jnp.maximum(lam, floor)
    return jnp.einsum("...ir,...r,...jr->...ij", Q, lam, Q, precision=HI)


def full_from_diag(gmm: DiagGMM) -> FullGMM:
    covs = jax.vmap(jnp.diag)(gmm.vars)
    return FullGMM(gmm.weights, gmm.means, covs)


def _as_utterances(x, mask, frame_chunk: int):
    """Flat [F, D] frames (+ optional [F] mask) -> pseudo-utterances
    [U, frame_chunk, D] with the mask carried through (padded tail marked
    invalid); batched [U, F, D] input passes through."""
    if x.ndim == 3:
        return x, mask
    F, D = x.shape
    fc = min(int(frame_chunk), F)
    n_utts = -(-F // fc)
    pad = n_utts * fc - F
    feats = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_utts, fc, D)
    if pad == 0 and mask is None:
        return feats, None
    m = jnp.ones((F,), f32) if mask is None else mask.reshape(F).astype(f32)
    return feats, jnp.pad(m, (0, pad)).reshape(n_utts, fc)


def train_ubm(x, C: int, key, diag_iters: int = 8, full_iters: int = 4,
              top_k: int = 0, chunk: int = 8, frame_chunk: int = 4096,
              mask=None, rescore: str = "dense", mesh=None) -> FullGMM:
    """The Kaldi-style recipe (diag EM, then full-covariance EM), with the
    E-side streamed through the StatsEngine: utterance chunks are scanned
    so nothing frame-resident ([F, C] posteriors, [F, D^2] expansions)
    outlives one chunk — the retired whole-dataset dense path materialized
    a [F_total, D^2] expansion (21 GB at the paper's §4.1 scale).

    ``x``: flat frames [F, D] (re-chunked into ``frame_chunk``-frame
    pseudo-utterances) or ragged-padded utterances [U, F, D] with ``mask``
    [U, F]. ``top_k`` prunes EM responsibilities (Kaldi's gselect); 0
    keeps all C components — exact dense EM. ``rescore`` ('dense' |
    'sparse' | 'fused') picks how the full-covariance phase scores the
    selected set (DESIGN.md §8, §12); it only pays off with a pruned
    ``top_k``, and the diag phase (no full-cov rescoring) ignores it.

    ``mesh`` runs both EM phases through the engine's sharded mode
    (pseudo-utterances over the data axes, components over 'model') —
    the same macro-step substrate the trainer uses (DESIGN.md §11). It
    is dropped (local streaming) when the pseudo-utterance count does
    not divide the mesh's data extent.
    """
    from repro.core import engine as EN   # deferred: engine imports ubm
    feats, mask = _as_utterances(x, mask, frame_chunk)
    if mesh is not None:
        d = 1
        for a, s in zip(mesh.axis_names, mesh.devices.shape):
            if a != "model":
                d *= int(s)
        if feats.shape[0] % d or C % mesh.shape.get("model", 1):
            mesh = None
    gmm = init_diag_from_data(feats, C, key, mask=mask)
    K = int(top_k) if top_k else C
    spec_d = EN.EngineSpec(n_components=C, top_k=K, floor=0.0,
                           second_order="diag", chunk=chunk)
    step_d = jax.jit(lambda g, xs, m: EN.stream_ubm(
        spec_d, EN.pack_diag(g), xs, m, mesh=mesh))
    for _ in range(diag_iters):
        st = step_d(gmm, feats, mask)
        gmm = diag_m_step(st.n, st.f, st.ss)
    full = full_from_diag(gmm)
    spec_f = EN.EngineSpec(n_components=C, top_k=K, floor=0.0,
                           second_order="full", chunk=chunk,
                           rescore=rescore)
    step_f = jax.jit(lambda g, xs, m: EN.stream_ubm(
        spec_f, EN.pack_ubm(g), xs, m, mesh=mesh))
    for _ in range(full_iters):
        st = step_f(full, feats, mask)
        full = full_m_step(st.n, st.f, st.ss)
    return full


jax.tree_util.register_pytree_node(
    DiagGMM, lambda g: ((g.weights, g.means, g.vars), None),
    lambda _, c: DiagGMM(*c))
jax.tree_util.register_pytree_node(
    FullGMM, lambda g: ((g.weights, g.means, g.covs), None),
    lambda _, c: FullGMM(*c))
