"""Pallas TPU kernel: the FUSED alignment pipeline — diag preselect
scoring, per-frame top-K, coalesced packed-row gather, and full-covariance
rescoring in ONE kernel (DESIGN.md §12).

The two-phase path (`gmm_loglik`/diag preselect + `gmm_rescore`) crosses
HBM twice per frame-tile: the `[F, C]` diag scores round-trip to pick the
top-K, and the rescore kernel then issues one row DMA per selected
(frame, slot) pair. This kernel keeps the whole per-tile state resident:

* the diag scores `[BF, C]` live in VMEM for the life of the frame-tile
  and never reach HBM — top-K runs as K masked-argmax steps in registers;
* the selected ids stay on-chip and drive the gather directly: the BF·K
  ids are sorted (iterative min-extraction) so the packed-row copies walk
  `A2` in ascending address order — adjacent/duplicate ids become
  near-sequential HBM traffic instead of BF·K random row touches — and
  are pipelined through a ``dma_depth``-slot semaphore ring;
* rescoring is a single packed GEMM `[BF, E2] @ [E2, BF·K]` against the
  gathered tile-union (E2 = 1 + D + D(D+1)/2, the packed-symmetric rows
  of `ref.align_pack` with −0.5 folded in), and each slot's score is
  extracted through the inverse sort permutation with a one-hot dot.

The quadratic x-expansion is itself a matmul (`x2 @ sel_mat`, the
[D², E2] 0/1/2-weight selection operand from `align_expand_operand`), so
the kernel contains no data-dependent gathers at all outside the row DMAs.

Grid: (F/BF,). The diag coefficient blocks map to the same (0, 0) block
every grid step, so they stay VMEM-resident across the whole call; `A2`
stays in HBM/ANY and only the gathered BF·K rows ever move. FLOPs per
frame are 2·C·(2D+1) (preselect) + 2·u·E2 (rescore, u = BF·K tile-union)
— the C/K cut of the sparse path with none of its per-slot DMA latency.

Like `gmm_rescore`, duplicate and clipped ids are legal (slots score
independently; the min-extraction consumes multiset duplicates one at a
time), and NaN/inf garbage rows select arbitrary clipped ids — masked
frames are finalised away downstream, same contract as `lax.top_k`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32

# default frame-tile / DMA pipeline depth; the autotuner
# (analysis/roofline.py) picks per-shape values and ops.py pads against BF
BLOCK_F = 8
DMA_DEPTH = 4


def _kernel(x_ref, dconst_ref, dlin_ref, dquad_ref, sexp_ref, a_ref,
            ll_ref, sel_ref, scores_ref, ids_ref, work_ref, inv_ref,
            gath_ref, sem_ref, *, top_k: int, dma_depth: int):
    bf = x_ref.shape[0]
    C = dconst_ref.shape[1]
    n = bf * top_k

    x = x_ref[...].astype(f32)                           # [BF, D]
    d = x.shape[1]

    # --- phase 1: diag preselect scores, VMEM-resident for the tile ----
    scores_ref[...] = (dconst_ref[...]                   # [BF, C]
                       + jax.lax.dot_general(
                           x, dlin_ref[...], (((1,), (0,)), ((), ())),
                           preferred_element_type=f32)
                       + jax.lax.dot_general(
                           x * x, dquad_ref[...], (((1,), (0,)), ((), ())),
                           preferred_element_type=f32))

    # --- phase 2: top-K as K masked-argmax steps (scores never leave
    # VMEM; ids land in ids_ref) ----------------------------------------
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (bf, C), 1)
    for k in range(top_k):
        s = scores_ref[...]
        v = jnp.max(s, axis=1, keepdims=True)
        # first index attaining the max; NaN rows (masked-frame garbage)
        # compare false everywhere -> clipped to C-1, same "arbitrary but
        # in-range" contract as lax.top_k on garbage
        idx = jnp.min(jnp.where(s >= v, iota_c, C), axis=1)
        idx = jnp.minimum(idx, C - 1)
        ids_ref[:, k] = idx
        scores_ref[...] = jnp.where(iota_c == idx[:, None], -jnp.inf, s)

    # --- phase 3: sort-by-id (iterative min-extraction) + pipelined row
    # DMAs through a dma_depth-slot semaphore ring ----------------------
    work_ref[...] = ids_ref[...]
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (bf, top_k), 0)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (bf, top_k), 1)
    flat = iota_f * top_k + iota_k                       # [BF, K] flat slots

    def extract(j, _):
        w = work_ref[...]
        m = jnp.min(w)                                   # smallest id left
        pos = jnp.min(jnp.where(w == m, flat, n))        # its slot
        # j-th gathered row <- A2[m]; remember slot -> gather position
        inv_ref[...] = jnp.where(flat == pos, j, inv_ref[...])
        work_ref[...] = jnp.where(flat == pos, jnp.int32(2 ** 30), w)

        # ring: slot j % dma_depth must be free before reuse
        @pl.when(j >= dma_depth)
        def _():
            pltpu.make_async_copy(
                a_ref.at[m], gath_ref.at[j - dma_depth],
                sem_ref.at[j % dma_depth]).wait()
        pltpu.make_async_copy(
            a_ref.at[m], gath_ref.at[j], sem_ref.at[j % dma_depth]).start()
        return 0

    jax.lax.fori_loop(0, n, extract, 0)

    def drain(j, _):
        pltpu.make_async_copy(
            a_ref.at[0], gath_ref.at[j], sem_ref.at[j % dma_depth]).wait()
        return 0

    jax.lax.fori_loop(max(n - dma_depth, 0), n, drain, 0)

    # --- phase 4: packed expansion (a matmul, no gathers) + one GEMM
    # against the sorted tile-union, then inverse-perm extraction -------
    e2 = gath_ref.shape[1]
    x2 = (x[:, :, None] * x[:, None, :]).reshape(bf, d * d)
    xe = jax.lax.dot_general(
        x2, sexp_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=f32)                      # [BF, E2]
    xe = xe + jnp.concatenate(
        [jnp.ones((bf, 1), f32), x,
         jnp.zeros((bf, e2 - 1 - d), f32)], axis=1)
    g = gath_ref[...].astype(f32)                        # [n, E2]
    tile = jax.lax.dot_general(
        xe, g, (((1,), (1,)), ((), ())),
        preferred_element_type=f32)                      # [BF, n]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (bf, top_k, n), 2)
    onehot = (iota_n == inv_ref[...][:, :, None]).astype(f32)
    ll_ref[...] = jax.lax.dot_general(
        tile[:, None, :], onehot, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=f32)[:, 0, :]             # [BF, K]
    sel_ref[...] = ids_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "top_k", "block_f", "dma_depth", "interpret"))
def gmm_align(x, dconst, dlin, dquad, sexp, A2, *, top_k: int,
              block_f: int = BLOCK_F, dma_depth: int = DMA_DEPTH,
              interpret: bool = False):
    """x: [F, D]; dconst: [1, C], dlin: [D, C], dquad: [D, C] diag
    preselect coefficients (score = const + x·lin + x²·quad); sexp:
    [D*D, E2] quadratic-expansion operand (``ops.align_expand_operand``);
    A2: [C, E2] packed-symmetric rows (``ref.align_pack``) ->
    (sel_ll [F, K] f32, sel [F, K] int32)."""
    F, D = x.shape
    C = A2.shape[0]
    E2 = A2.shape[1]
    bf = min(block_f, F)
    assert F % bf == 0, (F, bf)
    assert E2 >= 1 + D + D * (D + 1) // 2, (E2, D)
    depth = max(1, min(dma_depth, bf * top_k))
    grid = (F // bf,)
    kernel = functools.partial(_kernel, top_k=top_k, dma_depth=depth)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, D), lambda i: (i, 0)),
            # diag coefficients map to block (0, 0) on every grid step:
            # they stay VMEM-resident for the whole call
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((D, C), lambda i: (0, 0)),
            pl.BlockSpec((D, C), lambda i: (0, 0)),
            pl.BlockSpec((D * D, E2), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # A2 stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((bf, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bf, top_k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F, top_k), f32),
            jax.ShapeDtypeStruct((F, top_k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bf, C), f32),                    # diag scores
            pltpu.VMEM((bf, top_k), jnp.int32),          # selected ids
            pltpu.VMEM((bf, top_k), jnp.int32),          # sort workspace
            pltpu.VMEM((bf, top_k), jnp.int32),          # inverse perm
            pltpu.VMEM((bf * top_k, E2), f32),           # gathered rows
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
        name="gmm_align",
    )(x, dconst, dlin, dquad, sexp, A2)
