"""estep_ms.train: device milliseconds per traced EM iteration under the
``ivec_estep`` named scope (``core/tvm.precompute``, ``em_accumulate``,
``em_accumulate_scan`` and ``core/engine.TVMAccum .update``): the
precompute of T, the assembly of L, the posterior solves and the
accumulation of A, B, h and H.

The scope of each device op is read from the compiled program that ran
the window (``bench/stages.py``); a program without the scopes reads
nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.stage_ms(r, "ivec_estep")
