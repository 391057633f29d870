"""Pallas TPU kernel: causal GQA flash attention (forward).

The LM-side memory-term fix: pure-XLA blockwise attention streams f32
score/prob blocks through HBM (see EXPERIMENTS.md §Perf); this kernel keeps
the entire online-softmax pipeline in VMEM — HBM traffic is exactly
q/k/v in + out, giving arithmetic intensity ~ block_q instead of ~4.

Grid: (B, H, nq, nk) with nk 'arbitrary' (sequential): VMEM scratch carries
(acc, m, l) across kv blocks of one q block. Upper-triangular kv blocks are
skipped with pl.when (no FLOPs, no traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


f32 = jnp.float32
_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m, l, *, block_q, block_k,
            scale):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, _NEG)
        l[...] = jnp.zeros_like(l)

    @pl.when(j * block_k <= i * block_q + block_q - 1)  # causal: skip j>i
    def _compute():
        q = q_ref[0, :, 0, :].astype(f32)            # [BQ, hd]
        k = k_ref[0, :, 0, :].astype(f32)            # [BK, hd]
        v = v_ref[0, :, 0, :].astype(f32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l[...] = l[...] * alpha + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=f32)
        m[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, :, 0, :] = (acc[...] /
                             jnp.maximum(l[...], 1e-30)[:, None]
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """Causal GQA attention. q: [B, S, H, hd]; k, v: [B, S, KVH, hd]."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    grid = (B, H, S // bq, S // bk)
    kernel = functools.partial(_kernel, block_q=bq, block_k=bk,
                               scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), f32),
            pltpu.VMEM((bq,), f32),
            pltpu.VMEM((bq,), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
