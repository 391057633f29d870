"""rescore_ms.train: device milliseconds per traced EM iteration under the
``ivec_rescore`` named scope (``core/alignment.rescore_selected`` and
``finalise_posteriors``, the matching part of
``core/engine._align_sharded``, and ``engine.pack_ubm``): the rescoring
kernel with its wrapper's sort, gather and padding, the floor and
renormalisation of the posteriors, and the precompute of the
full-covariance UBM.

The scope of each device op is read from the compiled program that ran
the window (``bench/stages.py``); a program without the scopes reads
nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.stage_ms(r, "ivec_rescore")
