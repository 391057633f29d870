"""Pallas TPU kernels: packed-symmetric mixed-precision TVM E-step.

The two dominant E-step contractions (DESIGN.md §9) both have a symmetric
[R, R] operand per item, so both run on the packed upper triangle
(P = R(R+1)/2), halving HBM bytes AND MXU FLOPs versus the dense form
(R=400: 80 200 vs 160 000 columns):

  L-assembly       L_packed[U, P] = n[U, C]   @ U_packed[C, P]
  A-accumulation   A_packed[C, P] = nᵀ[C, U] @ PP_packed[U, P]

Both are the same tiled matmul with an accumulated reduction over the
last grid axis; inputs may be bf16 (mixed precision) — the MXU always
accumulates in f32 via ``preferred_element_type``. Grids:
(M/BM, P/BP, K/BK) with K the reduction (C for L, U for A).

Shapes must divide the blocks — the `ops.py` wrappers zero-pad ragged
U/C/P to block multiples and slice back (zero rows/columns contribute
exactly nothing to a sum-reduction), mirroring `ops.gmm_loglik`.
f32 inputs contract at HIGHEST precision (the TPU's default f32 matmul
rounds its inputs to bf16); bf16 inputs use the MXU's native pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32

# default block sizes; the ops.py wrappers pad ragged shapes against these
BLOCK_U = 128   # utterance tile (L rows / A reduction)
BLOCK_P = 256   # packed-triangle tile
BLOCK_C = 128   # component tile (L reduction / A rows)


def _matmul_kernel(a_ref, b_ref, out_ref, *, widen: bool):
    """out[i, j] += a[i, :] @ b[:, j], f32 accumulation over grid axis 2.

    Inputs stay in their storage dtype (f32 or bf16); the MXU widens to
    f32 via ``preferred_element_type`` — the mixed-precision contract.
    ``widen`` casts bf16 blocks to f32 first, for interpret mode: the CPU
    runtime has no bf16 x bf16 -> f32 dot, and the cast is exact (a
    product of two 8-bit mantissas fits in f32's 24).
    """
    k = pl.program_id(2)
    a, b = a_ref[...], b_ref[...]
    prec = jax.lax.Precision.HIGHEST if a.dtype == f32 else None
    if widen:
        a, b = a.astype(f32), b.astype(f32)
    part = jax.lax.dot(a, b, precision=prec, preferred_element_type=f32)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k != 0)
    def _acc():
        out_ref[...] += part


def _packed_matmul(a, b, *, bm: int, bp: int, bk: int, interpret: bool,
                   name: str):
    """a: [M, K]; b: [K, P] -> [M, P] f32, reduction accumulated over K;
    ``name`` names the kernel (its calling wrapper's name)."""
    M, K = a.shape
    P = b.shape[1]
    bm, bp, bk = min(bm, M), min(bp, P), min(bk, K)
    assert M % bm == 0 and P % bp == 0 and K % bk == 0, (M, P, K, bm, bp, bk)
    grid = (M // bm, P // bp, K // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, widen=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bp), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, P), f32),
        interpret=interpret,
        name=name,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("block_u", "block_p", "block_c",
                                             "interpret"))
def tvm_estep_l(n, U_packed, *, block_u: int = BLOCK_U,
                block_p: int = BLOCK_P, block_c: int = BLOCK_C,
                interpret: bool = False):
    """L-assembly: n [U, C] @ U_packed [C, P] -> L_packed [U, P] (f32).

    The packed Σ_c n_uc U_c precision accumulation — add I after
    unpacking at the Cholesky boundary (`core/tvm.posterior`).
    """
    return _packed_matmul(n, U_packed, bm=block_u, bp=block_p, bk=block_c,
                          interpret=interpret, name="tvm_estep_l")


@functools.partial(jax.jit, static_argnames=("block_u", "block_p", "block_c",
                                             "interpret"))
def tvm_estep_a(n, PP_packed, *, block_u: int = BLOCK_U,
                block_p: int = BLOCK_P, block_c: int = BLOCK_C,
                interpret: bool = False):
    """A-accumulation: nᵀ [C, U] @ PP_packed [U, P] -> A_packed [C, P].

    PP_packed holds the packed per-utterance second moment
    Phi_u + φ_u φ_uᵀ; the result is the packed M-step operand A_c.
    """
    return _packed_matmul(n.T, PP_packed, bm=block_c, bp=block_p,
                          bk=block_u, interpret=interpret, name="tvm_estep_a")
