"""Total-variability model: standard and augmented (Kaldi) formulations.

Implements the paper's §2-§3 exactly:
  * E-step posteriors, eqs. (3)-(4), with prior offset p (augmented only)
  * M-step: T update, residual-covariance (Σ_c) update
  * minimum-divergence re-estimation: whitening P1; for the augmented
    formulation also the Householder reflection P2 (eqs. 8-11) and the
    prior-offset update (eq. 12)
  * UBM-mean write-back for realignment (§3.2 step 5)
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.stats import BWStats
from repro.kernels import ops

f32 = jnp.float32
# f32 contractions run at HIGHEST precision: a TPU's default f32 matmul
# rounds its inputs to bf16, and the E-step and Σ update cancel large terms
HI = jax.lax.Precision.HIGHEST
COV_FLOOR = 1e-4
VAR_FLOOR_FACTOR = 0.1   # Kaldi's --variance-floor-factor


@dataclass
class TVModel:
    T: jax.Array            # [C, D, R]; augmented: column 0 holds m_c / p
    Sigma: jax.Array        # [C, D, D] residual covariances
    prior: jax.Array        # [R]; zeros (standard) or [p,0,...,0]-ish (augm.)
    means: jax.Array        # [C, D] bias terms m_c (standard formulation)
    formulation: str        # 'standard' | 'augmented'

    @property
    def rank(self):
        return self.T.shape[2]


jax.tree_util.register_pytree_node(
    TVModel,
    lambda m: ((m.T, m.Sigma, m.prior, m.means), m.formulation),
    lambda form, c: TVModel(*c, formulation=form))


def init_model(key, ubm_means, ubm_covs, R: int, formulation: str,
               prior_offset: float = 100.0) -> TVModel:
    """Paper §2.1/§2.2 initialisation."""
    C, D = ubm_means.shape
    T = jax.random.normal(key, (C, D, R), f32)
    if formulation == "augmented":
        T = T.at[:, :, 0].set(ubm_means / prior_offset)
        prior = jnp.zeros((R,), f32).at[0].set(prior_offset)
    else:
        prior = jnp.zeros((R,), f32)
    return TVModel(T=T, Sigma=ubm_covs.astype(f32), prior=prior,
                   means=ubm_means.astype(f32), formulation=formulation)


# ---------------------------------------------------------------------------
# Precomputation + E-step (eqs. 3-4)
# ---------------------------------------------------------------------------


class Precomp(NamedTuple):
    U: jax.Array    # [C, R, R] T^T Σ^{-1} T; packed mode: [C, P] triu
    Pj: jax.Array   # [C, D, R]  Σ^{-1} T

    @property
    def packed(self) -> bool:
        """Packed-symmetric E-step layout (DESIGN.md §9): U holds only
        the upper triangle, P = R(R+1)/2."""
        return self.U.ndim == 2


def precompute(model: TVModel, estep: str = "dense") -> Precomp:
    """T^T Σ^{-1} T and Σ^{-1} T via a Cholesky solve against T (never
    an explicit inverse — near-singular residual covariances would
    poison Pj/U through `inv`; `cho_solve` stays backward-stable).

    ``estep='packed'`` stores U as its packed upper triangle [C, P]
    (DESIGN.md §9); ``'dense'`` keeps the full [C, R, R] reference
    layout.
    """
    if estep not in ("dense", "packed"):
        raise ValueError(f"estep must be 'dense'|'packed', got {estep!r}")
    with jax.named_scope("ivec_estep"):
        chol = jnp.linalg.cholesky(model.Sigma)
        Pj = jax.scipy.linalg.cho_solve((chol, True), model.T)
        Uc = jnp.einsum("cdr,cds->crs", model.T, Pj, precision=HI)
        # exact symmetry before packing (fp round-off from the solve)
        Uc = 0.5 * (Uc + Uc.transpose(0, 2, 1))
        if estep == "packed":
            return Precomp(ops.pack_symmetric(Uc).astype(f32),
                           Pj.astype(f32))
        return Precomp(Uc.astype(f32), Pj.astype(f32))


def posterior(model: TVModel, pre: Precomp, n, f, mean_only: bool = False,
              estep_dtype: str = "float32", axis: Optional[str] = None
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """n: [U, C], f: [U, C, D] -> (phi [U, R], Phi [U, R, R] | None).

    Stats must be centred for the standard formulation and raw for the
    augmented one (paper §2 convention).

    With a packed ``pre`` the precision assembly runs on the upper
    triangle (``ops.tvm_estep_l``, optionally bf16 inputs with f32
    accumulation per ``estep_dtype``) and unpacks ONLY at this batched
    Cholesky boundary. ``mean_only=True`` solves just the rhs (R× fewer
    triangular solves than the identity-RHS covariance solve) and
    returns ``Phi=None`` — the extraction/serving scoring path.

    ``axis`` (inside the engine's shard_map mode): n/f and the precompute
    rows cover only the rank-local C-block, so the component contractions
    are partial sums — they psum over ``axis`` BEFORE the eye/prior terms
    are added, and everything downstream (solves, phi, Phi) is replicated
    over the model axis. This is the only model-axis collective of the
    E-step (DESIGN.md §11).
    """
    R = model.rank
    if pre.packed:
        Lp = ops.tvm_estep_l(n, pre.U, dtype=estep_dtype)      # [U, P]
        if axis is not None:
            Lp = jax.lax.psum(Lp, axis)
        L = jnp.eye(R, dtype=f32) + ops.unpack_symmetric(Lp, R)
    else:
        # f32 accumulation pinned explicitly (rule NUM001): n may arrive
        # bf16 under the mixed-precision E-step
        Ld = jnp.einsum("uc,crs->urs", n, pre.U, precision=HI,
                        preferred_element_type=f32)
        if axis is not None:
            Ld = jax.lax.psum(Ld, axis)
        L = jnp.eye(R, dtype=f32) + Ld
    rhs = jnp.einsum("cdr,ucd->ur", pre.Pj, f, precision=HI,
                     preferred_element_type=f32)
    if axis is not None:
        rhs = jax.lax.psum(rhs, axis)
    rhs = model.prior[None] + rhs
    chol = jnp.linalg.cholesky(L)
    if pre.packed:
        # posterior-assembly fast path (DESIGN.md §12): invert the
        # Cholesky factor with the blocked matmul-only ``tri_inverse``
        # and assemble Phi = G^{-T} G^{-1} as a batched syrk — batched
        # ``cho_solve``/``triangular_solve`` lowers to a per-item LAPACK
        # loop on CPU and to sequential substitutions on the MXU, while
        # this path is pure GEMM work (measured 2.3× on the whole E-step
        # tail, BENCH_tvm_estep.json). Dense mode keeps the cho_solve
        # reference — the ladder's exactness oracle.
        Gi = ops.tri_inverse(chol)
        if mean_only:
            # two triangular mat-vecs: phi = G^{-T} (G^{-1} rhs); Phi is
            # never materialised at all
            y = jnp.einsum("urs,us->ur", Gi, rhs, precision=HI,
                           preferred_element_type=f32)
            phi = jnp.einsum("usr,us->ur", Gi, y, precision=HI,
                             preferred_element_type=f32)
            return phi.astype(f32), None
        Phi = jnp.einsum("uir,uis->urs", Gi, Gi, precision=HI,
                         preferred_element_type=f32)
        phi = jnp.einsum("urs,us->ur", Phi, rhs, precision=HI,
                         preferred_element_type=f32)
        return phi.astype(f32), Phi.astype(f32)
    phi = jax.scipy.linalg.cho_solve((chol, True), rhs[..., None])[..., 0]
    if mean_only:
        return phi.astype(f32), None
    Phi = jax.scipy.linalg.cho_solve(
        (chol, True), jnp.broadcast_to(jnp.eye(R, dtype=f32),
                                       (n.shape[0], R, R)))
    return phi.astype(f32), Phi.astype(f32)


class EMAccum(NamedTuple):
    A: jax.Array        # [C, R, R]  Σ_u n_uc (Phi_u + phi phi^T);
    #                     packed mode: [C, P] upper triangle
    B: jax.Array        # [C, D, R]  Σ_u f_uc ⊗ phi_u
    # h and H are taken about the model's prior (phi_u - prior): in the
    # augmented formulation phi_u[0] ~ p = 100, and the min-divergence
    # covariance H/N - h h^T would otherwise cancel 1e4-sized terms
    h: jax.Array        # [R]        Σ_u (phi_u - prior)
    H: jax.Array        # [R, R]     Σ_u (Phi_u + (phi-prior)(phi-prior)^T)
    n_tot: jax.Array    # [C]
    n_utts: jax.Array   # []

    @staticmethod
    def zeros(C: int, D: int, R: int, estep: str = "dense") -> "EMAccum":
        """Identity element of ``merge_accums`` (scan/stream carries).
        ``estep='packed'`` sizes A as the packed triangle [C, P]."""
        A0 = (jnp.zeros((C, R * (R + 1) // 2), f32) if estep == "packed"
              else jnp.zeros((C, R, R), f32))
        return EMAccum(
            A=A0, B=jnp.zeros((C, D, R), f32),
            h=jnp.zeros((R,), f32), H=jnp.zeros((R, R), f32),
            n_tot=jnp.zeros((C,), f32), n_utts=jnp.zeros((), f32))


def em_accumulate(model: TVModel, pre: Precomp, n, f,
                  estep_dtype: str = "float32",
                  axis: Optional[str] = None) -> EMAccum:
    """One minibatch of utterance stats -> E-step accumulators.

    Packed ``pre`` keeps the symmetric operands packed END TO END: the
    per-utterance second moment Phi + φφᵀ is packed once [U, P] and both
    the A-accumulation (``ops.tvm_estep_a``) and the tiny H reduction
    consume the packed form — A is stored packed until the M-step solve.

    With ``axis`` (model-sharded n/f/pre) the posterior solve psums its
    partial precision/rhs over the axis; phi/Phi come back replicated, so
    A/B/n_tot below stay rank-local rows of the global accumulators and
    h/H/n_utts are replicated — exactly the packing the engine's exit
    psum expects (DESIGN.md §11).
    """
    with jax.named_scope("ivec_estep"):
        phi, Phi = posterior(model, pre, n, f, estep_dtype=estep_dtype,
                             axis=axis)
        if pre.packed:
            # assemble Phi + φφᵀ DIRECTLY in packed form: pack Phi once and
            # add the packed outer product φ_{i0} φ_{i1} — the dense [U, R, R]
            # second moment never exists (DESIGN.md §12)
            iu = jnp.triu_indices(model.rank)
            i0, i1 = iu[0].astype(jnp.int32), iu[1].astype(jnp.int32)
            PPp = (ops.pack_symmetric(Phi)
                   + jnp.take(phi, i0, axis=1) * jnp.take(phi, i1, axis=1))
            A = ops.tvm_estep_a(n, PPp, dtype=estep_dtype)         # [C, P]
        else:
            PP = Phi + phi[:, :, None] * phi[:, None, :]
            # f32 accumulation pinned (rule NUM001): n/f may arrive bf16
            # under the mixed-precision E-step
            A = jnp.einsum("uc,urs->crs", n, PP, precision=HI,
                           preferred_element_type=f32)
        B = jnp.einsum("ucd,ur->cdr", f, phi, precision=HI,
                       preferred_element_type=f32)
        dphi = phi - model.prior[None]
        H = jnp.sum(Phi, axis=0) + jnp.einsum("ur,us->rs", dphi, dphi,
                                              precision=HI,
                                              preferred_element_type=f32)
        return EMAccum(A=A, B=B, h=jnp.sum(dphi, axis=0), H=H,
                       n_tot=jnp.sum(n, axis=0),
                       n_utts=jnp.asarray(n.shape[0], f32))


def mean_phi(model: TVModel, acc: EMAccum) -> jax.Array:
    """Mean posterior phi over the accumulated utterances."""
    return acc.h / jnp.maximum(acc.n_utts, 1.0) + model.prior


def merge_accums(a: EMAccum, b: EMAccum) -> EMAccum:
    return EMAccum(*(x + y for x, y in zip(a, b)))


def em_accumulate_scan(model: TVModel, pre: Precomp, n, f,
                       chunk: int = 512,
                       estep_dtype: str = "float32") -> EMAccum:
    """Chunked E-step: scans utterance sub-batches so the per-utterance
    posterior covariances ([chunk, R, R], not [U, R, R]) never exist all at
    once — at pod-scale batches the unchunked form is terabytes.

    A ragged tail (U % chunk != 0) is processed as one remainder chunk, so
    arbitrary batch sizes keep the bounded [chunk, R, R] footprint (falling
    back to the unchunked path would be exactly the memory blow-up the
    chunking exists to avoid)."""
    with jax.named_scope("ivec_estep"):
        U_, C = n.shape
        chunk = min(chunk, U_)
        g = U_ // chunk
        rem = U_ % chunk
        R, D = model.rank, model.T.shape[1]

        def body(carry, inp):
            nc, fc = inp
            acc = em_accumulate(model, pre, nc, fc, estep_dtype=estep_dtype)
            return merge_accums(carry, acc), None

        zero = EMAccum.zeros(C, D, R,
                             estep="packed" if pre.packed else "dense")
        nr = n[:g * chunk].reshape(g, chunk, C)
        fr = f[:g * chunk].reshape(g, chunk, C, D)
        acc, _ = jax.lax.scan(body, zero, (nr, fr))
        if rem:
            acc = merge_accums(
                acc, em_accumulate(model, pre, n[g * chunk:], f[g * chunk:],
                                   estep_dtype=estep_dtype))
        return acc


# ---------------------------------------------------------------------------
# M-step
# ---------------------------------------------------------------------------


def m_step(model: TVModel, acc: EMAccum, S_tot: Optional[jax.Array],
           update_sigma: bool) -> TVModel:
    """T update (and Σ update) from accumulated statistics [Kenny 2005].

    A packed accumulator ([C, P]) is unpacked here — the Cholesky
    boundary — exactly as L unpacks at the posterior's. Each A_c + εI is
    SPD (occupancy-weighted Phi_u + φ_u φ_uᵀ plus a ridge), so T_c =
    B_c A_c⁻¹ takes the posterior's matmul-only path (DESIGN.md §12):
    A = G Gᵀ, Gi = G⁻¹ by ``ops.tri_inverse``, T = (B Giᵀ) Gi. The two
    factors are applied to the D-row B; A⁻¹ is never formed."""
    with jax.named_scope("ivec_mstep"):
        R = model.rank
        A = ops.unpack_symmetric(acc.A, R) if acc.A.ndim == 2 else acc.A
        A_reg = A + 1e-6 * jnp.eye(R, dtype=f32)[None]
        Gi = ops.tri_inverse(jnp.linalg.cholesky(A_reg))
        T_new = jnp.einsum("cdr,csr->cds", acc.B, Gi, precision=HI)
        T_new = jnp.einsum("cds,cst->cdt", T_new, Gi, precision=HI)
        Sigma = model.Sigma
        if update_sigma and S_tot is not None:
            n_safe = jnp.maximum(acc.n_tot, 1e-6)[:, None, None]
            TB = jnp.einsum("cdr,cer->cde", T_new, acc.B, precision=HI)
            resid = S_tot - 0.5 * (TB + TB.transpose(0, 2, 1))
            D = resid.shape[1]
            # Kaldi's variance floor: a fraction of the occupancy-weighted
            # average residual covariance. A component seen in fewer frames
            # than D has a rank-deficient estimate; unfloored, its precision
            # explodes in the next E-step and the iterations diverge to NaN.
            floor = (VAR_FLOOR_FACTOR * jnp.sum(resid, axis=0)
                     / jnp.maximum(jnp.sum(acc.n_tot), 1e-6))
            floor = 0.5 * (floor + floor.T) + COV_FLOOR * jnp.eye(D)
            Sigma = floor_covariances(resid / n_safe, floor)
        return replace(model, T=T_new.astype(f32), Sigma=Sigma.astype(f32))


def floor_covariances(covs, floor):
    """[C, D, D] symmetric -> each covariance raised to at least ``floor``
    [D, D] (SPD) in the PSD order (Kaldi's ``SpMatrix::ApplyFloor``):
    whiten by the floor's Cholesky factor L, clamp the eigenvalues of
    L⁻¹ Σ L⁻ᵀ at 1, and map back."""
    with jax.named_scope("ivec_sigma_floor"):
        D = floor.shape[0]
        L = jnp.linalg.cholesky(floor)
        Li = jax.scipy.linalg.solve_triangular(L, jnp.eye(D, dtype=f32),
                                               lower=True)
        M = jnp.einsum("ij,cjk,lk->cil", Li, covs, Li, precision=HI)
        lam, Q = jnp.linalg.eigh(0.5 * (M + M.transpose(0, 2, 1)))
        M = jnp.einsum("cir,cr,cjr->cij", Q, jnp.maximum(lam, 1.0), Q,
                       precision=HI)
        return jnp.einsum("ij,cjk,lk->cil", L, M, L, precision=HI)


# ---------------------------------------------------------------------------
# Minimum-divergence re-estimation (§3.1)
# ---------------------------------------------------------------------------


def min_divergence(model: TVModel, acc: EMAccum,
                   update_means: bool = False) -> TVModel:
    with jax.named_scope("ivec_mstep"), \
            jax.named_scope("ivec_min_divergence"):
        nu = jnp.maximum(acc.n_utts, 1.0)
        dh = acc.h / nu                    # mean of phi - prior
        G = acc.H / nu - dh[:, None] * dh[None, :]
        h = dh + model.prior
        R = model.rank
        G = G + 1e-8 * jnp.eye(R, dtype=f32)
        lam, Q = jnp.linalg.eigh(G)
        lam = jnp.maximum(lam, 1e-10)
        P1 = (Q * (lam ** -0.5)[None, :]).T            # Λ^{-1/2} Q^T
        P1_inv = Q * (lam ** 0.5)[None, :]             # Q Λ^{1/2}

        if model.formulation == "standard":
            T_new = jnp.einsum("cdr,rs->cds", model.T, P1_inv, precision=HI)
            means = model.means
            if update_means:
                # paper §5: m_c^upd = m_c + T_c h  (old T)
                means = means + jnp.einsum("cdr,r->cd", model.T, h,
                                           precision=HI)
            return replace(model, T=T_new.astype(f32), means=means)

        # augmented: additionally require P2 P1 h = b e1 (Householder,
        # eqs 8-11)
        p1h = jnp.dot(P1, h, precision=HI)
        norm = jnp.linalg.norm(p1h)
        h_t = p1h / jnp.maximum(norm, 1e-10)
        e1 = jnp.zeros((R,), f32).at[0].set(1.0)
        denom = jnp.maximum(2.0 * (1.0 - h_t[0]), 1e-10)
        alpha = denom ** -0.5
        a = alpha * h_t - alpha * e1
        # degenerate case: h already along e1 -> P2 = I
        degenerate = (1.0 - h_t[0]) < 1e-8
        P2 = jnp.where(degenerate, jnp.eye(R, dtype=f32),
                       jnp.eye(R, dtype=f32) - 2.0 * a[:, None] * a[None, :])
        # T <- T P1^{-1} P2^{-1}; P2 is a reflection: P2^{-1} = P2
        T_new = jnp.einsum("cdr,rs,st->cdt", model.T, P1_inv, P2, precision=HI)
        prior = jnp.where(degenerate, p1h, jnp.dot(P2, p1h, precision=HI))
        return replace(model, T=T_new.astype(f32), prior=prior.astype(f32))


# ---------------------------------------------------------------------------
# Realignment support (§3.2 step 5) and i-vector extraction
# ---------------------------------------------------------------------------


def updated_ubm_means(model: TVModel) -> jax.Array:
    """New UBM means: augmented = first column of T times p; standard = m_c."""
    if model.formulation == "augmented":
        return model.T[:, :, 0] * model.prior[0]
    return model.means


def extract_ivectors(model: TVModel, pre: Precomp, n, f,
                     estep_dtype: str = "float32") -> jax.Array:
    """Posterior means, centred at the prior offset (Kaldi convention).

    Extraction only needs the mean, so this takes the ``mean_only``
    posterior path: the [U, R, R] covariance (an identity-RHS solve that
    serving used to compute and discard) is never formed — R× fewer
    triangular solves per extraction."""
    with jax.named_scope("ivec_estep"):
        phi, _ = posterior(model, pre, n, f, mean_only=True,
                           estep_dtype=estep_dtype)
        return phi - model.prior[None]
