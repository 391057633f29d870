"""moments_ms.train: device milliseconds per traced EM iteration under the
``ivec_moments`` named scope (``core/stats.scatter_accumulate`` and
``core/engine.TotalsAccum.update``): the Baum-Welch scatters of the
zeroth and first moments, the sort and grouped matmul of the second
moments (``kernels/ops.second_moments``: the compiler's ``ragged-dot``
kernel and the sort before it, joined to the scope through the work
they feed), and the running totals.

The scope of each device op is read from the compiled program that ran
the window (``bench/stages.py``); a program without the scopes reads
nothing. Moves ``em_utts_per_s``.
"""
from bench import stages


def read(r):
    return stages.stage_ms(r, "ivec_moments")
