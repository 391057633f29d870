"""Pure-jnp oracles for every Pallas kernel (the correctness reference and
the CPU execution path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32
# f32 contractions run at HIGHEST precision: a TPU's default f32 matmul
# rounds its inputs to bf16
HI = jax.lax.Precision.HIGHEST


def gmm_loglik(x, const, lin, P_flat):
    """Full-covariance GMM log-likelihood via the vec-trick.

    x: [F, D]; const: [C]; lin: [D, C]; P_flat: [C, D*D] (row-major
    precision matrices). Returns [F, C]:
        out[f,c] = const[c] + x_f . lin[:,c] - 0.5 vec(x x^T) . P_flat[c]
    """
    F, D = x.shape
    x2 = (x[:, :, None] * x[:, None, :]).reshape(F, D * D)
    return (const[None]
            + jnp.dot(x, lin, precision=HI, preferred_element_type=f32)
            - 0.5 * jnp.dot(x2, P_flat.T, precision=HI,
                            preferred_element_type=f32)).astype(f32)


def gmm_rescore(x, sel, const, lin, P_flat):
    """Sparse top-K full-covariance rescoring: loglik of the SELECTED
    components only (Kaldi's gselect regime; DESIGN.md §8).

    x: [F, D]; sel: [F, K] int32 component ids; const: [C]; lin: [D, C];
    P_flat: [C, D*D] (row-major precision matrices). Returns [F, K]:

        out[f, k] = const[sel[f,k]] + x_f . lin[:, sel[f,k]]
                    - 0.5 vec(x_f x_f^T) . P_flat[sel[f,k]]

    — the same three-term decomposition as ``gmm_loglik`` followed by
    ``take_along_axis``, but only K of the C components are ever touched:
    a C/K FLOP cut on the quadratic term. Duplicate / clipped indices are
    allowed (each slot scores independently).
    """
    F, D = x.shape
    x2 = (x[:, :, None] * x[:, None, :]).reshape(F, D * D)
    lin_g = jnp.take(lin.T, sel, axis=0)                    # [F, K, D]
    P_g = jnp.take(P_flat, sel, axis=0)                     # [F, K, D*D]
    return (jnp.take(const, sel)
            + jnp.einsum("fd,fkd->fk", x, lin_g, precision=HI,
                         preferred_element_type=f32)
            - 0.5 * jnp.einsum("fe,fke->fk", x2, P_g, precision=HI,
                               preferred_element_type=f32)).astype(f32)


def rescore_pack(const, lin, P_flat):
    """Pack the full-cov precompute into ONE gatherable row per component:
    A[c] = [const_c | lin[:, c] | P_flat[c]], shape [C, 1 + D + D*D].
    The Pallas rescore kernel DMAs exactly one packed row per selected
    (frame, slot) pair instead of three strided gathers."""
    return jnp.concatenate(
        [const[:, None], lin.T, P_flat], axis=1).astype(f32)


def _quad_pairs(D):
    """Upper-triangle pair indices + off-diagonal doubling weights for the
    packed quadratic form: (i0, i1, w) with w = 2 off-diagonal, 1 on it,
    so that vec(x x^T) . vec(P) == sum_p w_p x_{i0_p} x_{i1_p} P_{i0 i1}."""
    iu = jnp.triu_indices(D)
    i0 = iu[0].astype(jnp.int32)
    i1 = iu[1].astype(jnp.int32)
    w = jnp.where(i0 == i1, 1.0, 2.0).astype(f32)
    return i0, i1, w


def align_pack(const, lin, P_flat):
    """Pack the full-cov precompute into PACKED-SYMMETRIC rows for the
    fused alignment path: A2[c] = [const_c | lin[:, c] | -0.5 * triu(P_c)],
    shape [C, E2] with E2 = 1 + D + D(D+1)/2.

    Unlike ``rescore_pack`` (full [C, 1+D+D*D] rows, one per-row DMA per
    selected slot), this is the operand of a single packed GEMM against
    the ``expand_quadratic`` frame expansion — the precision matrix is
    symmetric, so only the upper triangle is stored (≈2x fewer bytes per
    row DMA) and the -0.5 quadratic weight is folded in at pack time.
    """
    C, DD = P_flat.shape
    D = lin.shape[0]
    i0, i1, _ = _quad_pairs(D)
    Pp = jnp.take(P_flat, i0 * D + i1, axis=1)              # [C, D(D+1)/2]
    return jnp.concatenate(
        [const[:, None], lin.T, -0.5 * Pp], axis=1).astype(f32)


def expand_quadratic(x):
    """Packed-symmetric frame expansion: [F, D] -> [F, 1 + D + D(D+1)/2]
    with xe[f] = [1 | x_f | w ⊙ (x_{i0} x_{i1})] (w doubles off-diagonal
    pairs), so that ``xe @ align_pack(...)^T`` reproduces ``gmm_loglik``
    exactly — the quadratic term touches D(D+1)/2 entries instead of D²."""
    F, D = x.shape
    i0, i1, w = _quad_pairs(D)
    x2p = jnp.take(x, i0, axis=1) * jnp.take(x, i1, axis=1) * w[None]
    return jnp.concatenate(
        [jnp.ones((F, 1), f32), x.astype(f32), x2p.astype(f32)], axis=1)


def gmm_rescore_fused(x, sel, A2, *, strategy="full", block_f=8):
    """Fused packed-GEMM rescoring of the selected components
    (the jnp oracle for ``kernels/gmm_align.py``; DESIGN.md §12).

    x: [F, D]; sel: [F, K] int32 in [0, C); A2: [C, E2] from
    ``align_pack``. Returns [F, K] — identical (to f32 rounding) to
    ``gmm_rescore`` / dense-then-gather, but evaluated as GEMMs against
    the packed-symmetric expansion instead of per-slot row gathers:

    * ``strategy='full'``: one [F, E2] @ [E2, C] GEMM + take_along_axis.
      Wins when the frame-tile union of selected ids saturates C
      (BF·K >= C — always true at CPU bench scale) or when C is small:
      no gather at all, the whole pack streams once.
    * ``strategy='union'``: per frame-tile of BF frames, gather the
      sorted union-multiset of BF·K selected rows once and GEMM the
      tile against it ([BF, E2] @ [E2, BF·K]), then extract each slot's
      score through the inverse sort permutation. This is the Pallas
      kernel's schedule (sort-by-id coalesces the row DMAs); FLOPs drop
      C/(BF·K)-fold at paper scale where BF·K << C. F must divide by
      block_f (the ops wrapper pads).
    """
    Fn, K = sel.shape
    xe = expand_quadratic(x)                                 # [F, E2]
    if strategy == "full":
        ll = jnp.dot(xe, A2.T, precision=HI,
                     preferred_element_type=f32)             # [F, C]
        return jnp.take_along_axis(ll, sel, axis=1).astype(f32)
    if strategy != "union":
        raise ValueError(f"strategy must be 'full' or 'union': {strategy!r}")
    if Fn % block_f:
        raise ValueError(f"F={Fn} not a multiple of block_f={block_f}")
    T = Fn // block_f
    E2 = xe.shape[1]
    ids = sel.reshape(T, block_f * K)
    order = jnp.argsort(ids, axis=1)                  # coalescing sort-by-id
    ids_sorted = jnp.take_along_axis(ids, order, axis=1)
    inv = jnp.argsort(order, axis=1)                  # slot -> sorted pos
    rows = jnp.take(A2, ids_sorted, axis=0)           # [T, BF*K, E2]
    scores = jax.lax.dot_general(
        xe.reshape(T, block_f, E2), rows,
        (((2,), (2,)), ((0,), (0,))), precision=HI,
        preferred_element_type=f32)                   # [T, BF, BF*K]
    out = jnp.take_along_axis(scores, inv.reshape(T, block_f, K), axis=2)
    return out.reshape(Fn, K).astype(f32)


def tri_inverse(G, block: int = 16):
    """Inverse of a batched lower-triangular matrix via blocked MATMULS
    (no triangular_solve): G [..., R, R] lower-triangular -> G^{-1}.

    Recursion on [[A, 0], [B, C]]^{-1} = [[A^{-1}, 0],
    [-C^{-1} B A^{-1}, C^{-1}]] with halving splits; sub-blocks of size
    <= ``block`` factor G = D(I + N) (N strictly lower, nilpotent) and
    invert I + N by log-depth squaring: (I+N)^{-1} = (I-N)(I+N²)(I+N⁴)…

    Every step is a batched matmul, which is why this exists: batched
    ``triangular_solve`` lowers to a per-item LAPACK loop on the CPU
    backend (~100x slower than the equivalent GEMM FLOPs) and to
    sequential row substitutions on the MXU, while this path is pure
    dense-matmul work (~R³/3 useful FLOPs) on either. Used by the
    posterior fast path (core/tvm.py, DESIGN.md §12).
    """
    R = G.shape[-1]
    if R <= block:
        d = jnp.diagonal(G, axis1=-2, axis2=-1)
        Dinv = 1.0 / d
        N = G * Dinv[..., None] - jnp.eye(R, dtype=G.dtype)
        X = jnp.eye(R, dtype=G.dtype) - N
        M = -N
        p = 1
        while p < R:
            M = jnp.matmul(M, M, precision=HI, preferred_element_type=f32)
            X = X + jnp.matmul(M, X, precision=HI,
                               preferred_element_type=f32)
            p *= 2
        return X * Dinv[..., None, :]
    h = (R + 1) // 2
    A = G[..., :h, :h]
    B = G[..., h:, :h]
    C_ = G[..., h:, h:]
    Ai = tri_inverse(A, block)
    Ci = tri_inverse(C_, block)
    BAi = jnp.matmul(B, Ai, precision=HI, preferred_element_type=f32)
    low = -jnp.matmul(Ci, BAi, precision=HI, preferred_element_type=f32)
    top = jnp.concatenate([Ai, jnp.zeros(A.shape[:-2] + (h, R - h),
                                         dtype=G.dtype)], axis=-1)
    bot = jnp.concatenate([low, Ci], axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


def bw_stats(gamma, x):
    """Dense Baum-Welch moments.

    gamma: [F, C] posteriors; x: [F, D]. Returns (n [C], f [C, D],
    S [C, D*D]) with S_c = sum_f gamma_fc vec(x_f x_f^T).
    """
    F, D = x.shape
    x2 = (x[:, :, None] * x[:, None, :]).reshape(F, D * D)
    n = jnp.sum(gamma, axis=0)
    f = jnp.dot(gamma.T, x, precision=HI, preferred_element_type=f32)
    S = jnp.dot(gamma.T, x2, precision=HI, preferred_element_type=f32)
    return n.astype(f32), f.astype(f32), S.astype(f32)


def second_moments(x, values, indices, C: int):
    """Second-order Baum-Welch moments of sparse posteriors by scatter-add.

    x: [N, D] frames; values/indices: [N, K] posteriors of the selected
    components. Returns [C, D*D] with row c = sum over (frame, slot)
    pairs selecting c of value * vec(x xᵀ) (row-major).
    """
    N, D = x.shape
    K = values.shape[1]
    x2 = (x[:, :, None] * x[:, None, :]).reshape(N, D * D)
    x2w = (values[:, :, None] * x2[:, None, :]).reshape(N * K, D * D)
    return jnp.zeros((C, D * D), f32).at[indices.reshape(-1)].add(x2w)


def tvm_estep_l(n, U_packed):
    """TVM E-step L-assembly with symmetric packing (DESIGN.md §9).

    n: [U, C] occupancies; U_packed: [C, P] where P = R(R+1)/2 holds the
    upper triangle of T_c^T Sigma_c^{-1} T_c. Returns [U, P] f32 — the
    packed L_u (before adding I). Packing halves both HBM bytes and
    matmul FLOPs versus the dense [C, R, R] form. bf16 inputs accumulate
    in f32 (``preferred_element_type``), same contract as the kernel.
    """
    return jnp.dot(n, U_packed, precision=HI,
                   preferred_element_type=f32).astype(f32)


def tvm_estep_a(n, PP_packed):
    """TVM E-step A-accumulation with symmetric packing.

    n: [U, C] occupancies; PP_packed: [U, P] packed per-utterance second
    moments Phi_u + φ_u φ_uᵀ. Returns [C, P] f32 — the packed M-step
    operand A_c = Σ_u n_uc (Phi_u + φ_u φ_uᵀ).
    """
    return jnp.dot(n.T, PP_packed, precision=HI,
                   preferred_element_type=f32).astype(f32)


def _packed_index_map(R):
    """[R, R] int32 map (r, s) -> row-major upper-triangle packed index,
    computed arithmetically (no scatter): for r <= s,
    idx = r*R - r(r-1)/2 + (s-r), mirrored for the lower triangle."""
    i = jnp.arange(R, dtype=jnp.int32)
    r = jnp.minimum(i[:, None], i[None, :])
    s = jnp.maximum(i[:, None], i[None, :])
    return r * R - (r * (r - 1)) // 2 + (s - r)


def pack_symmetric(M):
    """[..., R, R] -> [..., R(R+1)/2] upper triangle (row-major).

    Vectorised flat gather — lowers to one take, no boolean masking.
    """
    R = M.shape[-1]
    iu = jnp.triu_indices(R)
    flat = (iu[0] * R + iu[1]).astype(jnp.int32)
    return jnp.take(M.reshape(M.shape[:-2] + (R * R,)), flat, axis=-1)


def unpack_symmetric(Mp, R):
    """[..., R(R+1)/2] -> [..., R, R] symmetric.

    A pure gather through the arithmetic (r, s) -> packed-index map:
    both triangles read the same packed entry, so the result is exactly
    symmetric (no scatter + transpose + diagonal fix-up).
    """
    idx = _packed_index_map(R).reshape(-1)
    out = jnp.take(Mp, idx, axis=-1)
    return out.reshape(Mp.shape[:-1] + (R, R))


def flash_attention(q, k, v, causal: bool = True):
    """Reference attention. q: [B, S, H, hd]; k, v: [B, S, KVH, hd]."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qr = q.reshape(B, S, KVH, G, hd)
    s = jnp.einsum("bqkgh,bskh->bqkgs", qr.astype(f32), k.astype(f32)) \
        * hd ** -0.5
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgs,bskh->bqkgh", p, v.astype(f32))
    return o.reshape(B, S, H, hd).astype(q.dtype)
