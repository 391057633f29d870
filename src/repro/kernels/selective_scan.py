"""Pallas TPU kernel: fused Mamba selective scan.

The jamba hillclimb (EXPERIMENTS.md §Perf) showed the XLA selective scan is
memory-bound: the associative scan streams [B,T,di,ds]-sized transition
tensors through HBM ~log(T) times per pass. This kernel is the production
fix: the recurrence runs sequentially INSIDE VMEM — HBM traffic is exactly
the inputs (dt, dx, B, C read once) and y written once; h lives in a VMEM
scratch register the whole time (~9x fewer bytes than the XLA path).

Grid: (B, di/bd, T/bt) with T 'arbitrary' (sequential); the [bd, ds] state
carries across T blocks in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


f32 = jnp.float32


def _kernel(dt_ref, dx_ref, A_ref, B_ref, C_ref, y_ref, h, *, block_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h[...] = jnp.zeros_like(h)

    dt = dt_ref[0].astype(f32)        # [bt, bd]
    dx = dx_ref[0].astype(f32)        # [bt, bd]
    A = A_ref[...].astype(f32)        # [bd, ds]
    Bc = B_ref[0].astype(f32)         # [bt, ds]
    Cc = C_ref[0].astype(f32)         # [bt, ds]
    bt = dt.shape[0]

    def step(t, carry):
        hh, y = carry
        a = jnp.exp(dt[t][:, None] * A)            # [bd, ds]
        hh = a * hh + dx[t][:, None] * Bc[t][None]  # [bd, ds]
        y = y.at[t].set(jnp.sum(hh * Cc[t][None], axis=1))
        return hh, y

    y0 = jnp.zeros((bt, dt.shape[1]), f32)
    hh, y = jax.lax.fori_loop(0, bt, step, (h[...], y0))
    h[...] = hh
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def selective_scan(dt, dx, A, Bc, Cc, *, block_t: int = 128,
                   block_d: int = 512, interpret: bool = False):
    """dt, dx: [B, T, di]; A: [di, ds]; Bc, Cc: [B, T, ds] -> y [B, T, di].

    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t;  y_t = C_t . h_t
    """
    B, T, di = dt.shape
    ds = A.shape[1]
    bt = min(block_t, T)
    bd = min(block_d, di)
    assert T % bt == 0 and di % bd == 0
    grid = (B, di // bd, T // bt)
    kernel = functools.partial(_kernel, block_t=bt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((bd, ds), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, bt, ds), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, bt, ds), lambda b, d, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, T, di), dt.dtype),
        scratch_shapes=[pltpu.VMEM((bd, ds), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, dx, A, Bc, Cc)
