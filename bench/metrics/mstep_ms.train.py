"""mstep_ms.train: device milliseconds per traced EM iteration under the
``ivec_mstep`` named scope (``core/tvm.m_step`` and ``min_divergence``),
its nested ``ivec_sigma_floor`` and ``ivec_min_divergence`` included:
the solves for the new T, the residual covariances with their floor, and
the minimum-divergence re-estimation.

The scope of each device op is read from the compiled program that ran
the window (``bench/stages.py``); a program without the scopes reads
nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.stage_ms(r, "ivec_mstep")
