"""Frame alignment: posterior computation with Kaldi's pruning recipe
(paper §4.2), adapted to TPU (DESIGN.md §2-§3, §8) as an explicit
two-phase preselect → rescore pipeline:

1. **preselect** — diagonal-covariance scores for all C (cheap matmul),
   top-K component ids per frame,
2. **rescore_selected** — full-covariance log-likelihood of the selected
   set, in one of three modes:
     'dense'  — evaluate all C densely (vec-trick MXU matmul, §2) and
                gather K; the CPU/reference fallback, and the winner at
                small C where the MXU is cheap and gathers are not,
     'sparse' — gather-and-rescore ONLY the K selected components
                (`kernels.ops.gmm_rescore`, §8): the [F, C] score matrix
                is never materialised — a C/K FLOP cut on the hot path,
     'fused'  — packed-GEMM rescoring against the symmetric-packed
                `align_pack` rows (`kernels.ops.gmm_rescore_fused`, §12):
                the same C/K cut as 'sparse' with the gather coalesced
                into tile-level GEMMs. It is jnp on every backend: the
                single-kernel `kernels/gmm_align.py` is not on this path
                (and does not fit VMEM at C=2048),
3. intersect is free (softmax/floor already operate on the gathered
   [F, K] set, so both modes feed bit-identical downstream math), drop
   posteriors < floor, renormalise to sum 1.

Output is sparse: (values [F, K], indices [F, K]) — the compact form the
paper stores to disk; here it flows straight into Baum-Welch accumulation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ubm as U

f32 = jnp.float32


class SparsePosteriors(NamedTuple):
    values: jax.Array   # [F, K] renormalised posteriors (zeros where pruned)
    indices: jax.Array  # [F, K] component ids


def floor_renormalise(post, floor: float) -> jax.Array:
    """Floor + renormalise posteriors (paper: drop < 0.025, rescale to
    sum 1). Kaldi never lets a frame vanish: if flooring would zero every
    posterior, the arg-max component is kept (otherwise the frame silently
    drops out of the statistics and the renormalisation divides by the
    guard). Shared by the in-memory path and the sharded owner-local path
    in ``launch/ivector_cell.py``.
    """
    keep = post >= floor
    K = post.shape[1]
    best = jax.nn.one_hot(jnp.argmax(post, axis=1), K, dtype=bool)
    keep = keep | (~jnp.any(keep, axis=1, keepdims=True) & best)
    post = jnp.where(keep, post, 0.0)
    return post / jnp.maximum(jnp.sum(post, axis=1, keepdims=True), 1e-10)


def preselect(diag: U.DiagGMM, x, top_k: int):
    """Phase 1: diag-UBM scores [F, C] + top-K component ids [F, K]."""
    diag_ll = U.diag_loglik(diag, x)
    _, sel = jax.lax.top_k(diag_ll, top_k)
    return diag_ll, sel


def rescore_selected(x, sel, full, diag_ll, *, precomp=None,
                     rescore: str = "dense", rescore_pack=None,
                     align_pack=None):
    """Phase 2: loglik of the selected components -> [F, K].

    ``full`` None with no ``precomp`` scores the selected set with the
    (already-computed) diag scores — the diag phase of UBM EM, where
    there is nothing to rescore and ``rescore`` is moot. ``precomp``
    alone is a full parameterisation (const/lin/precisions), so full-cov
    rescoring needs no GMM object. 'dense' evaluates all C and gathers
    (exact current-TPU adaptation); 'sparse' gathers first and scores
    only K (``kernels.ops.gmm_rescore``), never materialising [F, C];
    'fused' scores the selected set through the packed-symmetric GEMM
    path (``kernels.ops.gmm_rescore_fused``; ``align_pack`` optionally
    supplies the cached ``ubm.align_pack`` rows). All three agree to f32
    rounding — 'dense' stays the reference fallback of the
    fused→sparse→dense ladder (DESIGN.md §12).
    """
    if full is None and precomp is None:
        return jnp.take_along_axis(diag_ll, sel, axis=1)
    if rescore == "sparse":
        return U.full_rescore(full, x, sel, precomp=precomp,
                              pack=rescore_pack)
    if rescore == "fused":
        return U.full_rescore_fused(full, x, sel, precomp=precomp,
                                    pack=align_pack)
    if rescore != "dense":
        raise ValueError(
            f"rescore must be 'dense', 'sparse' or 'fused': {rescore}")
    ll = U.full_loglik(full, x, precomp=precomp)            # [F, C]
    return jnp.take_along_axis(ll, sel, axis=1)


def finalise_posteriors(sel_ll, floor: float, mask=None):
    """Selected-set logliks [F, K] -> (posteriors [F, K], lse [F]).

    The shared tail of every alignment path — softmax over the selected
    set, floor + renormalise, padding-frame zeroing — used by both the
    in-memory `align_frames` and the owner-local sharded path in
    `engine._align_sharded` (where ``sel_ll`` arrives replicated after the
    masked pmax), so the two paths are the same code, not two copies.
    """
    lse = jax.scipy.special.logsumexp(sel_ll, axis=1)      # [F]
    post = floor_renormalise(jnp.exp(sel_ll - lse[:, None]), floor)
    if mask is not None:
        # where, not multiply: garbage padding frames can produce NaN/inf
        # posteriors (overflowing logliks), and NaN * 0 == NaN
        valid = mask.astype(bool)
        post = jnp.where(valid[:, None], post, 0.0)
        lse = jnp.where(valid, lse, 0.0)
    return post.astype(f32), lse.astype(f32)


def align_frames(x, full, diag: U.DiagGMM, *, top_k: int = 20,
                 floor: float = 0.025, precomp=None, mask=None,
                 with_loglik: bool = False, rescore: str = "dense",
                 rescore_pack=None, align_pack=None):
    """x: [F, D] -> sparse pruned-renormalised posteriors.

    Follows Kaldi/the paper: preselect with the diag UBM, score the
    selected components with the full UBM (``rescore`` mode: 'dense'
    matmul-and-gather, 'sparse' gather-and-rescore, or 'fused'
    packed-GEMM — same selected set, same downstream softmax/floor),
    floor + renormalise.

    ``full`` may be None: the selected components are then scored with the
    diag UBM itself (the diag phase of UBM EM; with top_k == C and
    floor == 0 this is exactly dense diag EM responsibilities).

    ``mask`` ([F], bool/0-1) marks valid frames; masked-out (padding)
    frames get all-zero posteriors so they contribute nothing downstream.

    With ``with_loglik`` also returns the per-frame logsumexp over the
    selected set ([F], zeroed on masked frames) — the EM diagnostic
    loglik, exact when top_k == C.
    """
    diag_ll, sel = preselect(diag, x, top_k)               # [F, C], [F, K]
    sel_ll = rescore_selected(x, sel, full, diag_ll, precomp=precomp,
                              rescore=rescore,
                              rescore_pack=rescore_pack,
                              align_pack=align_pack)       # [F, K]
    post, lse = finalise_posteriors(sel_ll, floor, mask)
    out = SparsePosteriors(post, sel)
    return (out, lse) if with_loglik else out


def densify(post: SparsePosteriors, C: int) -> jax.Array:
    """[F, K] sparse -> [F, C] dense (tests / small-scale CPU paths)."""
    F, K = post.values.shape
    dense = jnp.zeros((F, C), f32)
    rows = jnp.broadcast_to(jnp.arange(F)[:, None], (F, K))
    return dense.at[rows.reshape(-1), post.indices.reshape(-1)].add(
        post.values.reshape(-1))
