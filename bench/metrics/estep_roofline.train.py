"""estep_roofline.train: the E-step contraction kernel against its
roofline.

Device time of the packed E-step kernel (``kernels/tvm_estep.py``; the
L assembly and the A accumulation are one Pallas kernel) over the
traced iterations, against the least time of those iterations' L and A
work: each operand read once and each result written once per
iteration, whatever the chunking (``work.estep_least_seconds``). The
kernel is found by the name the trace gives it, which is fragile until
the program names its kernels. Moves ``em_utts_per_s``.
"""
from bench import trace, work

KERNEL = r"^tvm_estep_[la]\."


def read(r):
    its = r.counters.get("iterations")
    if r.trace is None or not its:
        return None
    t = trace.op_seconds(r.trace, KERNEL)
    if t <= 0:
        return None
    s = r.shapes
    least, _ = work.estep_least_seconds(
        C=s["C"], R=s["R"], U=s["U"], peak_flops=r.peaks["bf16_flops"],
        peak_bytes=r.peaks["hbm_bytes_per_s"])
    return 100.0 * least * its / t
