"""Pallas TPU kernel: fused full-covariance GMM log-likelihood.

The paper's frame-posterior hot spot (3000x real time on GPU). TPU
adaptation (DESIGN.md §2): the quadratic form is a dense MXU matmul
``[F, D^2] @ [D^2, C]`` where the [BF, D^2] expansion x (x) x is built
on-the-fly in VMEM — the expansion never exists in HBM, saving
F x D^2 x 4 bytes of traffic per batch (the memory-term win).

Grid: (F/BF, C/BC). VMEM per step ~ BF*D^2 + D^2*BC + BF*BC floats.
The f32 contractions run at HIGHEST precision (the TPU's default f32
matmul rounds its inputs to bf16), and ``const`` arrives as a [1, C] row:
a 1-D operand's HBM tiling does not match the kernel's block layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# default block sizes; the ops.py wrapper pads ragged shapes against these
BLOCK_F = 256
BLOCK_C = 128


def _kernel(x_ref, const_ref, lin_ref, p_ref, out_ref):
    x = x_ref[...].astype(f32)                       # [BF, D]
    bf, d = x.shape
    x2 = (x[:, :, None] * x[:, None, :]).reshape(bf, d * d)
    quad = jax.lax.dot_general(
        x2, p_ref[...].astype(f32), (((1,), (1,)), ((), ())),
        precision=_HI, preferred_element_type=f32)   # [BF, BC]
    lin = jax.lax.dot(x, lin_ref[...].astype(f32), precision=_HI,
                      preferred_element_type=f32)    # [BF, BC]
    out_ref[...] = const_ref[...] + lin - 0.5 * quad


@functools.partial(jax.jit, static_argnames=("block_f", "block_c",
                                             "interpret"))
def gmm_loglik(x, const, lin, P_flat, *, block_f: int = BLOCK_F,
               block_c: int = BLOCK_C, interpret: bool = False):
    """x: [F, D]; const: [1, C]; lin: [D, C]; P_flat: [C, D*D] -> [F, C]."""
    F, D = x.shape
    C = const.shape[1]
    bf = min(block_f, F)
    bc = min(block_c, C)
    assert F % bf == 0 and C % bc == 0, (F, C, bf, bc)
    grid = (F // bf, C // bc)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, D), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((D, bc), lambda i, j: (0, j)),
            pl.BlockSpec((bc, D * D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bf, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((F, C), f32),
        interpret=interpret,
    )(x, const, lin, P_flat)
