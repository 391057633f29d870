"""unstaged_ms.train: device milliseconds per traced EM iteration under
no EM stage: ops whose scope holds no ``ivec_`` component (copies of
the step's arguments and results, work outside the stages), ops of
other programs (the UBM refresh), and ops whose program or scope
``bench/stages.py`` cannot tell. With the five ``<stage>_ms.train``
metrics it adds up to the device time of the iteration, so work that
leaves the stages shows here.

A program without the scopes reads nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.unstaged_ms(r)
