"""preselect_ms.train: device milliseconds per traced EM iteration under
the ``ivec_preselect`` named scope (``core/alignment.preselect`` and the
two-stage top-K of ``core/engine._align_sharded``): the diagonal scores
of every frame and their top-K.

The scope of each device op is read from the compiled program that ran
the window (``bench/stages.py``); a program without the scopes reads
nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.stage_ms(r, "ivec_preselect")
