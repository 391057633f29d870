"""rescore_roofline.train: the sparse rescoring kernel against its
roofline.

Device time of the gather-and-rescore kernel (``kernels/gmm_rescore.py``)
over the traced iterations, against the least time of its calls: one
call per utterance chunk of the iteration's stream, each scoring
frames*K selected components, with the bytes of the frames once, the
scores once and each component's packed row at most once per call
(``work.rescore_least_seconds``). The kernel is found by the name the
trace gives it, which is fragile until the program names its kernels.
Moves ``em_utts_per_s``.
"""
from bench import trace, work

KERNEL = r"^gmm_rescore\."


def read(r):
    its = r.counters.get("iterations")
    if r.trace is None or not its:
        return None
    t = trace.op_seconds(r.trace, KERNEL)
    if t <= 0:
        return None
    s = r.shapes
    per_utt = s["F"] // s["U"]
    full, rem = divmod(s["U"], s["chunk"])
    calls = [s["chunk"] * per_utt] * full + ([rem * per_utt] if rem else [])
    least = sum(work.rescore_least_seconds(
        C=s["C"], D=s["D"], K=s["K"], frames=f,
        peak_flops=r.peaks["bf16_flops"],
        peak_bytes=r.peaks["hbm_bytes_per_s"])[0] for f in calls)
    return 100.0 * least * its / t
