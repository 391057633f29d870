"""Pallas TPU kernel: fused Baum-Welch statistic accumulation.

Computes n = Γᵀ1, f = ΓᵀX and S = ΓᵀX₂ where X₂ is the per-frame outer
product expansion, built on-the-fly in VMEM (never in HBM). The frame
dimension is the reduction: grid = (C blocks, F blocks) with F declared
'arbitrary' so output blocks accumulate across F steps in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32


def _kernel(g_ref, x_ref, n_ref, f_ref, s_ref):
    fi = pl.program_id(1)
    g = g_ref[...].astype(f32)                       # [BF, BC]
    x = x_ref[...].astype(f32)                       # [BF, D]
    bf, d = x.shape
    x2 = (x[:, :, None] * x[:, None, :]).reshape(bf, d * d)
    gt = g.T
    n_part = jnp.sum(g, axis=0)
    f_part = jax.lax.dot(gt, x, preferred_element_type=f32)
    s_part = jax.lax.dot(gt, x2, preferred_element_type=f32)

    @pl.when(fi == 0)
    def _init():
        n_ref[...] = n_part
        f_ref[...] = f_part
        s_ref[...] = s_part

    @pl.when(fi != 0)
    def _acc():
        n_ref[...] += n_part
        f_ref[...] += f_part
        s_ref[...] += s_part


@functools.partial(jax.jit, static_argnames=("block_f", "block_c",
                                             "interpret"))
def bw_stats(gamma, x, *, block_f: int = 256, block_c: int = 128,
             interpret: bool = False):
    """gamma: [F, C]; x: [F, D] -> (n [C], f [C, D], S [C, D*D])."""
    F, C = gamma.shape
    D = x.shape[1]
    bf = min(block_f, F)
    bc = min(block_c, C)
    assert F % bf == 0 and C % bc == 0
    grid = (C // bc, F // bf)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, bc), lambda j, i: (i, j)),
            pl.BlockSpec((bf, D), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bc,), lambda j, i: (j,)),
            pl.BlockSpec((bc, D), lambda j, i: (j, 0)),
            pl.BlockSpec((bc, D * D), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C,), f32),
            jax.ShapeDtypeStruct((C, D), f32),
            jax.ShapeDtypeStruct((C, D * D), f32),
        ],
        interpret=interpret,
        name="bw_stats",
    )(gamma, x)
