"""Jitted public wrappers for the Pallas kernels.

The backend picks the path: on a TPU every wrapper runs its compiled
Pallas kernel, elsewhere the pure-jnp reference (`ref.py`). Tests steer
that choice with ``use_pallas(True/False)``; on the CPU the forced
kernels run in Pallas interpret mode. The i-vector core calls these
wrappers, so the kernel path is a drop-in.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.kernels import bw_stats as _bw
from repro.kernels import flash_attention as _fa
from repro.kernels import gmm_loglik as _gl
from repro.kernels import gmm_rescore as _gr
from repro.kernels import ref
from repro.kernels import tvm_estep as _te

f32 = jnp.float32

# None: decided by the backend; True/False: forced by ``use_pallas``
_USE_PALLAS = contextvars.ContextVar("repro_use_pallas", default=None)
_INTERPRET = contextvars.ContextVar("repro_pallas_interpret", default=False)


@contextlib.contextmanager
def use_pallas(enable: bool = True, interpret: bool = True):
    """Force the kernels on (``interpret`` runs them in Pallas interpret
    mode, for the CPU) or off, overriding the backend's choice."""
    t1 = _USE_PALLAS.set(enable)
    t2 = _INTERPRET.set(interpret)
    try:
        yield
    finally:
        _USE_PALLAS.reset(t1)
        _INTERPRET.reset(t2)


def _kernels_on() -> bool:
    forced = _USE_PALLAS.get()
    return jax.default_backend() == "tpu" if forced is None else forced


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def gmm_loglik(x, const, lin, P_flat, **kw):
    if _kernels_on():
        # The Pallas grid needs F and C to divide into whole blocks; ragged
        # shapes (variable-length serving traffic) are zero-padded here and
        # the result sliced back — padding rows/components never escape.
        F, C = x.shape[0], const.shape[0]
        bf = min(kw.get("block_f", _gl.BLOCK_F), F)
        bc = min(kw.get("block_c", _gl.BLOCK_C), C)
        Fp, Cp = _ceil_to(F, bf), _ceil_to(C, bc)
        if Fp != F:
            x = jnp.pad(x, ((0, Fp - F), (0, 0)))
        if Cp != C:
            const = jnp.pad(const, (0, Cp - C))
            lin = jnp.pad(lin, ((0, 0), (0, Cp - C)))
            P_flat = jnp.pad(P_flat, ((0, Cp - C), (0, 0)))
        out = _gl.gmm_loglik(x, const[None, :], lin, P_flat,
                             interpret=_INTERPRET.get(), **kw)
        return out[:F, :C] if (Fp, Cp) != (F, C) else out
    return ref.gmm_loglik(x, const, lin, P_flat)


def gmm_rescore(x, sel, const, lin, P_flat, pack=None, **kw):
    """Sparse top-K rescoring: loglik of only the selected components.

    x: [F, D]; sel: [F, K] component ids; const/lin/P_flat as in
    ``gmm_loglik``. ``pack`` optionally supplies the pre-built
    ``ref.rescore_pack`` matrix (serving caches it per session) so the
    Pallas path skips the concat. The kernel scores each gathered row
    against the frame expansion [1 | x | -0.5·vec(x xᵀ)] built here.
    Ragged F is zero-padded to the kernel's frame-tile and sliced back;
    indices are clipped into [0, C) so padding rows (and garbage
    preselections from masked frames) can never DMA out of bounds.
    """
    if _kernels_on():
        F, D = x.shape
        C = const.shape[0]
        A = ref.rescore_pack(const, lin, P_flat) if pack is None else pack
        Ep = _ceil_to(A.shape[1], 128)
        A = jnp.pad(A, ((0, 0), (0, Ep - A.shape[1]))).reshape(C, 1, Ep)
        x = x.astype(f32)
        x2 = (x[:, :, None] * x[:, None, :]).reshape(F, D * D)
        xe = jnp.concatenate([jnp.ones((F, 1), f32), x, -0.5 * x2], axis=1)
        xe = jnp.pad(xe, ((0, 0), (0, Ep - xe.shape[1])))
        bf = min(kw.get("block_f", _gr.BLOCK_F), F)
        Fp = _ceil_to(F, bf)
        sel = jnp.clip(sel.astype(jnp.int32), 0, C - 1)
        if Fp != F:
            xe = jnp.pad(xe, ((0, Fp - F), (0, 0)))
            sel = jnp.pad(sel, ((0, Fp - F), (0, 0)))
        out = _gr.gmm_rescore(xe, sel, A, interpret=_INTERPRET.get(), **kw)
        return out[:F] if Fp != F else out
    return ref.gmm_rescore(x, sel, const, lin, P_flat)


def align_expand_operand(D: int, E2: int):
    """[D*D, E2] 0/1 selection operand mapping vec(x x^T) to the packed
    quadratic columns of ``ref.expand_quadratic``: both (i, j) and (j, i)
    of an off-diagonal pair route to the same packed column with weight 1,
    so ``x2 @ op`` reproduces the doubled off-diagonal terms as a MATMUL —
    the in-kernel expansion needs no data-dependent gathers."""
    i0, i1, _ = ref._quad_pairs(D)
    P2 = i0.shape[0]
    cols = jnp.arange(P2, dtype=jnp.int32) + 1 + D
    op = jnp.zeros((D * D, E2), f32)
    op = op.at[i0 * D + i1, cols].add(1.0)
    op = op.at[i1 * D + i0, cols].add(jnp.where(i0 == i1, 0.0, 1.0))
    return op


def gmm_rescore_fused(x, sel, A2, *, strategy=None, block_f=None, **kw):
    """Fused packed-GEMM rescoring (DESIGN.md §12): loglik of the selected
    components via one GEMM against the packed-symmetric ``align_pack``
    rows instead of per-slot row gathers.

    x: [F, D]; sel: [F, K] component ids; A2: [C, E2]. ``strategy``/
    ``block_f`` default to the roofline autotuner's pick for this
    (C, K, D, backend) cell (``analysis.roofline.autotune_align``).
    Same pad-and-clip contract as ``gmm_rescore``: ragged F is zero-padded
    to the frame-tile and sliced back, ids are clipped into [0, C).
    """
    F, D = x.shape
    C = A2.shape[0]
    K = sel.shape[1]
    if strategy is None or block_f is None:
        from repro.analysis.roofline import autotune_align
        tune = autotune_align(C=C, K=K, D=D)
        strategy = strategy or tune.strategy
        block_f = block_f or tune.block_f
    sel = jnp.clip(sel.astype(jnp.int32), 0, C - 1)
    bf = max(1, min(block_f, F))
    Fp = _ceil_to(F, bf)
    if Fp != F:
        x = jnp.pad(x, ((0, Fp - F), (0, 0)))
        sel = jnp.pad(sel, ((0, Fp - F), (0, 0)))
    out = ref.gmm_rescore_fused(x, sel, A2, strategy=strategy, block_f=bf)
    return out[:F] if Fp != F else out


def gmm_align(x, dconst, dlin, dquad, A2, *, top_k: int, block_f=None,
              dma_depth=None, **kw):
    """The whole fused alignment front half: diag preselect + top-K +
    coalesced gather + packed rescore -> (sel_ll [F, K], sel [F, K]).

    Routes to the single fused Pallas kernel (`kernels/gmm_align.py`)
    where kernels run (see the module docstring); the jnp path composes
    the same stages (shared ``lax.top_k`` preselect +
    ``gmm_rescore_fused``) so both produce the
    identical selected set and scores to f32 rounding. dconst: [C];
    dlin/dquad: [D, C] diag score coefficients; A2: [C, E2].
    """
    F, D = x.shape
    C = A2.shape[0]
    if block_f is None or dma_depth is None:
        from repro.analysis.roofline import autotune_align
        tune = autotune_align(C=C, K=top_k, D=D)
        block_f = block_f or tune.block_f
        dma_depth = dma_depth or tune.dma_depth
    if _kernels_on():
        from repro.kernels import gmm_align as _ga
        E2 = A2.shape[1]
        bf = max(1, min(block_f, F))
        Fp = _ceil_to(F, bf)
        if Fp != F:
            x = jnp.pad(x, ((0, Fp - F), (0, 0)))
        sexp = align_expand_operand(D, E2)
        ll, sel = _ga.gmm_align(
            x, dconst[None, :], dlin, dquad, sexp, A2, top_k=top_k,
            block_f=bf, dma_depth=dma_depth,
            interpret=_INTERPRET.get(), **kw)
        return (ll[:F], sel[:F]) if Fp != F else (ll, sel)
    scores = (dconst[None]
              + jnp.dot(x, dlin, precision=ref.HI,
                        preferred_element_type=f32)
              + jnp.dot(x * x, dquad, precision=ref.HI,
                        preferred_element_type=f32))
    _, sel = jax.lax.top_k(scores, top_k)
    sel = sel.astype(jnp.int32)
    ll = gmm_rescore_fused(x, sel, A2, block_f=block_f)
    return ll, sel


tri_inverse = ref.tri_inverse


def bw_stats(gamma, x, **kw):
    if _kernels_on():
        return _bw.bw_stats(gamma, x, interpret=_INTERPRET.get(), **kw)
    return ref.bw_stats(gamma, x)


def second_moments(x, values, indices, C: int):
    """Second-order Baum-Welch moments of sparse posteriors -> [C, D*D].

    x: [N, D]; values/indices: [N, K] (indices in [0, C)). Where kernels
    run, the N·K (frame, slot) pairs are sorted by component and each
    component's weighted frames contract with its frames as one grouped
    matmul (``lax.ragged_dot_general`` with a ragged contracting axis, a
    Mosaic kernel on TPU): 2·N·K·D² FLOPs and [N·K, D] operands. The
    reference scatter-add builds an [N·K, D²] update buffer instead,
    41 GB for the trainer's 512-utterance chunk at D=72, which XLA:TPU
    refuses to compile for a 16 GB chip. Elsewhere the scatter runs: the
    CPU lowers the grouped matmul to a dense masked one.
    """
    if not _kernels_on():
        return ref.second_moments(x, values, indices, C)
    N, D = x.shape
    K = values.shape[1]
    comp = indices.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(comp)
    xs = jnp.take(x.astype(f32), order // K, axis=0)         # [N*K, D]
    w = jnp.take(values.reshape(-1).astype(f32), order)
    bounds = jnp.searchsorted(jnp.take(comp, order),
                              jnp.arange(C + 1, dtype=jnp.int32))
    sizes = jnp.diff(bounds).astype(jnp.int32)               # [C]
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    S = jax.lax.ragged_dot_general(
        xs * w[:, None], xs, sizes, dn, precision=ref.HI,
        preferred_element_type=f32)                          # [C, D, D]
    return S.reshape(C, D * D)


def _estep_cast(a, b, dtype):
    """Mixed-precision knob for the packed E-step contractions: bf16
    INPUTS, f32 accumulation (both the kernels and the jnp references
    contract with ``preferred_element_type=f32``)."""
    if dtype in ("bfloat16", "bf16"):
        return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    if dtype not in ("float32", "f32"):
        raise ValueError(
            f"estep dtype must be 'float32'|'bfloat16', got {dtype!r}")
    return a.astype(jnp.float32), b.astype(jnp.float32)


def _pad_matmul(a, b, bm, bp, bk):
    """Zero-pad a [M, K] @ b [K, P] operands to block multiples. Zero
    rows/cols are exact for a sum-reduction: padding never escapes."""
    M, K = a.shape
    P = b.shape[1]
    Mp, Kp, Pp = _ceil_to(M, bm), _ceil_to(K, bk), _ceil_to(P, bp)
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Pp) != (K, P):
        b = jnp.pad(b, ((0, Kp - K), (0, Pp - P)))
    return a, b


def tvm_estep_l(n, U_packed, *, dtype: str = "float32", **kw):
    """Packed L-assembly: n [U, C] @ U_packed [C, P] -> [U, P] f32.

    ``dtype`` selects the contraction input precision ('float32' |
    'bfloat16'); accumulation is always f32. Ragged U/C/P (any rank R —
    odd P included) are zero-padded to the kernel's block multiples and
    sliced back, mirroring ``gmm_loglik``.
    """
    n, U_packed = _estep_cast(n, U_packed, dtype)
    if _kernels_on():
        U, C = n.shape
        P = U_packed.shape[1]
        bu = min(kw.get("block_u", _te.BLOCK_U), U)
        bp = min(kw.get("block_p", _te.BLOCK_P), P)
        bc = min(kw.get("block_c", _te.BLOCK_C), C)
        np_, Up_ = _pad_matmul(n, U_packed, bu, bp, bc)
        out = _te.tvm_estep_l(np_, Up_, interpret=_INTERPRET.get(), **kw)
        return out[:U, :P] if out.shape != (U, P) else out
    return ref.tvm_estep_l(n, U_packed)


def tvm_estep_a(n, PP_packed, *, dtype: str = "float32", **kw):
    """Packed A-accumulation: nᵀ [C, U] @ PP_packed [U, P] -> [C, P] f32.

    Same mixed-precision and pad-and-clip contract as ``tvm_estep_l``
    (the reduction here is over utterances, so zero-padded utterance rows
    contribute exactly nothing).
    """
    n, PP_packed = _estep_cast(n, PP_packed, dtype)
    if _kernels_on():
        U, C = n.shape
        P = PP_packed.shape[1]
        bu = min(kw.get("block_u", _te.BLOCK_U), U)
        bp = min(kw.get("block_p", _te.BLOCK_P), P)
        bc = min(kw.get("block_c", _te.BLOCK_C), C)
        Cp, Up = _ceil_to(C, bc), _ceil_to(U, bu)
        Pp = _ceil_to(P, bp)
        if (Up, Cp) != (U, C):
            n = jnp.pad(n, ((0, Up - U), (0, Cp - C)))
        if (Up, Pp) != (U, P):
            PP_packed = jnp.pad(PP_packed, ((0, Up - U), (0, Pp - P)))
        out = _te.tvm_estep_a(n, PP_packed, interpret=_INTERPRET.get(), **kw)
        return out[:C, :P] if out.shape != (C, P) else out
    return ref.tvm_estep_a(n, PP_packed)


def flash_attention(q, k, v, **kw):
    if _kernels_on():
        return _fa.flash_attention(q, k, v, interpret=_INTERPRET.get(), **kw)
    return ref.flash_attention(q, k, v)


pack_symmetric = ref.pack_symmetric
unpack_symmetric = ref.unpack_symmetric


def selective_scan(dt, dx, A, Bc, Cc, **kw):
    from repro.kernels import selective_scan as _ss
    from repro.models.mamba import _ssm_scan
    if _kernels_on():
        return _ss.selective_scan(dt, dx, A, Bc, Cc,
                                  interpret=_INTERPRET.get(), **kw)
    h0 = jnp.zeros((dt.shape[0], dt.shape[2], A.shape[1]), jnp.float32)
    y, _ = _ssm_scan(dt, dx, A, Bc, Cc, h0)
    return y
