"""Baum-Welch statistics (paper §2, Kenny 2012 definitions).

For utterance u with frames x_t and posteriors gamma_tc:
    n_c  = sum_t gamma_tc                  (occupancy, zeroth order)
    f_c  = sum_t gamma_tc x_t              (first order)
    S_c  = sum_t gamma_tc x_t x_t^T        (second order)

Convention (paper §2): the STANDARD formulation centres f and S around the
UBM means; the AUGMENTED (Kaldi) formulation uses raw statistics.
``repro.kernels.bw_stats`` provides the fused Pallas second-order kernel.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.alignment import SparsePosteriors
from repro.kernels import ops

f32 = jnp.float32


class BWStats(NamedTuple):
    n: jax.Array   # [U, C]
    f: jax.Array   # [U, C, D]
    S: Optional[jax.Array] = None  # [C, D, D] (summed over utts; Σ update)


def scatter_accumulate(x, values, indices, utt_ids, n_utts: int, C: int,
                       second_order: Optional[str] = None, mask=None):
    """THE Baum-Welch scatter-add: flat frames -> (n, f, S).

    Every accumulation path in the repo (in-memory batches via
    ``accumulate_batch``, the streaming engine chunk body, the owner-local
    shards in ``launch/ivector_cell.py``) bottoms out here.

    x: [N, D] frames (any utterance structure, flattened);
    values/indices: [N, K] sparse posteriors; utt_ids: [N] utterance id per
    frame; mask: [N] optional validity. ``second_order``: None | 'diag' |
    'full' selects S as absent, [C, D] (sum gamma x^2) or [C, D*D]
    (sum gamma vec(x x^T), row-major; ``kernels.ops.second_moments``).
    """
    N, D = x.shape
    K = values.shape[1]
    if mask is not None:
        # where, not multiply: NaN/inf in garbage padding frames must not
        # survive masking (NaN * 0 == NaN)
        valid = mask.astype(bool)[:, None]
        values = jnp.where(valid, values, 0.0)
        x = jnp.where(valid, x, 0.0)
    rows_u = jnp.repeat(utt_ids, K)            # [N*K]
    rows_c = indices.reshape(-1)               # [N*K]
    n = jnp.zeros((n_utts, C), f32).at[rows_u, rows_c].add(
        values.reshape(-1))
    xw = (values[:, :, None] * x[:, None, :]).reshape(N * K, D)
    f = jnp.zeros((n_utts, C, D), f32).at[rows_u, rows_c].add(xw)
    S = None
    if second_order == "diag":
        sw = (values[:, :, None] * (x * x)[:, None, :]).reshape(N * K, D)
        S = jnp.zeros((C, D), f32).at[rows_c].add(sw)
    elif second_order == "full":
        # a grouped contraction where kernels run (kernels/ops.py)
        S = ops.second_moments(x, values, indices, C)
    return n, f, S


def accumulate(x, post: SparsePosteriors, C: int,
               second_order: bool = False, mask=None) -> BWStats:
    """x: [F, D] single utterance -> per-utterance stats (U dim absent).

    ``mask`` ([F], bool/0-1) marks valid frames; masked-out frames are
    excluded from n/f/S entirely (the frame features are zeroed too, so
    arbitrary garbage in padding frames cannot pollute the statistics).
    """
    F, D = x.shape
    n, f, S = scatter_accumulate(
        x, post.values, post.indices, jnp.zeros((F,), jnp.int32), 1, C,
        second_order="full" if second_order else None, mask=mask)
    return BWStats(n[0], f[0], S.reshape(C, D, D) if second_order else None)


def accumulate_batch(xs, posts: SparsePosteriors, C: int,
                     second_order: bool = False, mask=None) -> BWStats:
    """xs: [U, F, D]; posts values/indices: [U, F, K] -> batched stats.

    n, f keep the utterance dim (the TVM E-step needs per-utterance stats);
    S is summed over utterances (only its total enters the Σ update).
    ``mask`` ([U, F]) marks valid frames per utterance.
    """
    U, F, D = xs.shape
    K = posts.values.shape[-1]
    n, f, S = scatter_accumulate(
        xs.reshape(U * F, D), posts.values.reshape(U * F, K),
        posts.indices.reshape(U * F, K), jnp.repeat(jnp.arange(U), F), U, C,
        second_order="full" if second_order else None,
        mask=None if mask is None else mask.reshape(U * F))
    return BWStats(n, f, S.reshape(C, D, D) if second_order else None)


def center(stats: BWStats, means) -> BWStats:
    """Centre first/second-order stats around UBM means (standard form)."""
    f = stats.f - stats.n[..., None] * means[None]
    S = stats.S
    if S is not None:
        n_tot = jnp.sum(stats.n, axis=0)
        f_tot = jnp.sum(stats.f, axis=0)
        S = (S - f_tot[:, :, None] * means[:, None, :]
             - means[:, :, None] * f_tot[:, None, :]
             + n_tot[:, None, None] * means[:, :, None] * means[:, None, :])
    return BWStats(stats.n, f, S)
