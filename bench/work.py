"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick of the per-layer shares (``mfu.train`` and the
``*_roofline`` metrics). They count the algorithm, never an
implementation: no padding, no tile schedule, no recomputation, and the
same terms whatever rescoring or E-step layout the program picks. A
multiply-add is two operations. Symbols: C components, D feature
dimension, R i-vector rank, P = R(R+1)/2 the packed symmetric width, K
components rescored per frame, U utterances, F frames in all.

One EM iteration:

* diagonal preselection        2*F*C*2D    (x.lin and x^2.quad)
* top-K full-covariance rescore 2*F*K*(D^2 + D)
* Baum-Welch moments           2*F*K*(D + D^2), the second order only
                               when Sigma is updated (else 2*F*K*D)
* E-step precision assembly L  2*U*C*P
* E-step accumulation A        2*U*C*P
* first-order projection       2*U*C*D*R
* posterior solves             U*R^3/3
* M-step                       C*(R^3/3 + 2*R^2*D)
* precompute T^T Sigma^-1 T    2*C*R^2*D

Alignment and moments are counted only where the iteration realigns;
with statistics at rest they are set-up work.
"""
from __future__ import annotations

F32 = 4   # bytes of one float32


def packed(R: int) -> int:
    return R * (R + 1) // 2


def em_iteration_flops(*, C: int, D: int, R: int, K: int, U: int, F: int,
                       realign: bool, update_sigma: bool) -> float:
    """Operations of one EM iteration over U utterances of F frames in
    all (the table in the module docstring)."""
    P = packed(R)
    flops = (2.0 * U * C * P * 2          # L and A
             + 2.0 * U * C * D * R        # first-order projection
             + U * R ** 3 / 3.0           # posterior solves
             + C * (R ** 3 / 3.0 + 2.0 * R * R * D)   # M-step
             + 2.0 * C * R * R * D)       # precompute
    if realign:
        flops += (2.0 * F * C * 2 * D                 # preselect
                  + 2.0 * F * K * (D * D + D)         # rescore
                  + 2.0 * F * K * (D + (D * D if update_sigma else 0)))
    return flops


def estep_least_seconds(*, C: int, R: int, U: int, peak_flops: float,
                        peak_bytes: float) -> tuple:
    """Least time of one iteration's two E-step contractions, each
    operand read once and each result written once per iteration
    whatever the chunking: L = n [U,C] @ U_packed [C,P] -> [U,P] and
    A = n^T [C,U] @ PP [U,P] -> [C,P], float32 operands.
    Returns (seconds, 'compute' | 'memory')."""
    P = packed(R)
    flops = 2.0 * 2.0 * U * C * P
    nbytes = F32 * ((U * C + C * P + U * P)       # L
                    + (U * C + U * P + C * P))    # A
    return _bound(flops, nbytes, peak_flops, peak_bytes)


def rescore_least_seconds(*, C: int, D: int, K: int, frames: int,
                          peak_flops: float, peak_bytes: float) -> tuple:
    """Least time of one rescoring call over ``frames`` frames: the
    operations of scoring frames*K selected components, and the bytes
    of the frames once, the [frames, K] scores once and each
    component's packed row (const | lin | vec P, E = 1 + D + D^2 floats)
    at most once per call: min(C, frames*K) rows. No implementation can
    move fewer bytes, so a kernel that deduplicates rows cannot pass
    100%. Returns (seconds, bound)."""
    E = 1 + D + D * D
    flops = 2.0 * frames * K * (D * D + D)
    nbytes = F32 * (frames * D + frames * K + min(C, frames * K) * E)
    return _bound(flops, nbytes, peak_flops, peak_bytes)


def _bound(flops: float, nbytes: float, peak_flops: float,
           peak_bytes: float) -> tuple:
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
