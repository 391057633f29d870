"""mfu.train: the whole EM iteration's share of the chip's peak.

The algorithm's operations of one iteration (``bench/work.py``, the same
terms whatever rescoring or E-step layout the program runs) times the
iterations per second of the traced window, over the chip's bf16 peak
from ``bench/peaks.json``. The program's contractions run in float32 at
HIGHEST precision, several bf16 passes each, so the bf16 peak is a
bound the program cannot reach. Moves ``em_utts_per_s``.
"""
from bench import work


def read(r):
    its, wall = r.counters.get("iterations"), r.counters.get("wall_s")
    if not its or not wall:
        return None
    s = r.shapes
    flops = work.em_iteration_flops(
        C=s["C"], D=s["D"], R=s["R"], K=s["K"], U=s["U"], F=s["F"],
        realign=s["realign"], update_sigma=s["update_sigma"])
    return 100.0 * flops * its / wall / r.peaks["bf16_flops"]
