"""Pallas TPU kernel: fused gather-and-rescore for sparse top-K GMM
log-likelihood (DESIGN.md §8).

The dense kernel (`gmm_loglik.py`) scores every frame against every
component — O(F·C·D²) — and the alignment recipe then keeps only the K
diag-preselected components per frame, discarding ~99% of the work at the
paper's scale (K=20 of C=2048). This kernel computes the `[F, K]` selected
logliks directly: per frame-tile it DMA-gathers the K packed precompute
rows (const | lin | P, see `ref.rescore_pack`) from HBM into VMEM — the
`[F, C]` score matrix and the untouched C−K precision blocks never move —
and scores them against the tile's frame expansion
``xe[f] = [1 | x_f | -0.5·vec(x_f x_fᵀ)]`` (built by the `ops.py`
wrapper), so one row dot product gives const + lin + quadratic terms.

Grid: (F/BF,). VMEM per step ~ BF·K·E floats (E = 1 + D + D², padded to a
lane multiple), so BF is small (default 8): the kernel is gather-bound by
construction, trading MXU-friendly dense FLOPs for a C/K cut in both
FLOPs and HBM precision-block traffic. Dense wins when C is small or K
approaches C (see DESIGN.md §8 for the crossover); the alignment layer
keeps both paths selectable.

Row DMAs are COALESCED, not issued in slot order: the wrapper sorts each
tile's BF·K ids, and the kernel reads the sorted ids and their
destination slots from SMEM, so consecutive copies walk `A` in ascending
address order — adjacent and duplicate ids become near-sequential HBM
traffic instead of BF·K random row touches — with up to ``dma_depth``
copies in flight through a semaphore ring. Destination slots keep their
original (frame, slot) positions (only the issue order is sorted), so
each destination row is distinct and the scoring below reads the gather
in natural order with no inverse permutation.

TPU layout: a one-row slice of a 2-D array is not aligned to the (8, 128)
tiling, so `A` arrives as [C, 1, E] and the gather buffer is
[BF·K, 1, E]: every copy moves one whole leading-dim entry. The scores
are two small MXU products at HIGHEST precision — the tile against every
gathered row, then a 0/1 selection of each frame's own K slots — which
keeps the result in f32 and in the [BF, K] output layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# default frame-tile / DMA ring depth; the ops.py wrapper pads ragged F
# against BF and the autotuner (analysis/roofline.py) picks per-shape
BLOCK_F = 8
DMA_DEPTH = 4


def _kernel(ids_ref, dst_ref, xe_ref, a_ref, out_ref, gath_ref, sem_ref,
            *, dma_depth: int):
    bf, K = out_ref.shape
    n = bf * K

    # the j-th copy moves the j-th smallest selected id of the tile into
    # its (frame, slot) row, pipelined dma_depth deep
    def copy(j):
        return pltpu.make_async_copy(
            a_ref.at[ids_ref[0, j]], gath_ref.at[dst_ref[0, j]],
            sem_ref.at[j % dma_depth])

    def issue(j, _):
        @pl.when(j >= dma_depth)
        def _():
            copy(j - dma_depth).wait()
        copy(j).start()
        return 0

    jax.lax.fori_loop(0, n, issue, 0)

    def drain(j, _):
        copy(j).wait()
        return 0

    jax.lax.fori_loop(max(n - dma_depth, 0), n, drain, 0)

    g = gath_ref[:, 0, :]                            # [BF*K, E]
    s = jax.lax.dot_general(
        xe_ref[...], g, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=f32)                  # [BF, BF*K]
    row = jax.lax.broadcasted_iota(jnp.int32, (bf, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bf, n), 1)
    own = jnp.where(col // K == row, s, 0.0)         # frame f's own slots
    slot = jax.lax.broadcasted_iota(jnp.int32, (n, K), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (n, K), 1)
    pick = jnp.where(slot % K == k, 1.0, 0.0).astype(f32)
    out_ref[...] = jax.lax.dot(own, pick, precision=_HI,
                               preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("block_f", "dma_depth",
                                              "interpret"))
def gmm_rescore(xe, sel, A, *, block_f: int = BLOCK_F,
                dma_depth: int = DMA_DEPTH, interpret: bool = False):
    """xe: [F, E] frame expansions; sel: [F, K] int32 in [0, C);
    A: [C, 1, E] packed rows (``ref.rescore_pack``, E >= 1 + D + D*D,
    zero-padded) -> [F, K] selected log-likelihoods."""
    F, E = xe.shape
    K = sel.shape[1]
    C = A.shape[0]
    assert A.shape == (C, 1, E), (A.shape, E)
    bf = min(block_f, F)
    assert F % bf == 0, (F, bf)
    n = bf * K
    depth = max(1, min(dma_depth, n))
    T = F // bf
    ids = sel.astype(jnp.int32).reshape(T, n)
    order = jnp.argsort(ids, axis=1).astype(jnp.int32)   # issue order
    ids_sorted = jnp.take_along_axis(ids, order, axis=1)
    smem = functools.partial(pl.BlockSpec, (None, 1, n),
                             lambda i: (i, 0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, dma_depth=depth),
        grid=(T,),
        in_specs=[
            smem(),                                  # sorted ids
            smem(),                                  # their dest slots
            pl.BlockSpec((bf, E), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),    # A stays in HBM
        ],
        out_specs=pl.BlockSpec((bf, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((F, K), f32),
        scratch_shapes=[
            pltpu.VMEM((n, 1, E), f32),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
        name="gmm_rescore",
    )(ids_sorted.reshape(T, 1, n), order.reshape(T, 1, n), xe, A)
