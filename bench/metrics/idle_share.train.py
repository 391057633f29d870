"""idle_share.train: the share of the traced window in which no operation
ran on the device: 1 - (union of device-op intervals) / window, averaged
over the chips (``bench/trace.py``). Moves ``em_utts_per_s``.
"""
from bench import trace


def read(r):
    if r.trace is None:
        return None
    share = trace.idle_share(r.trace)
    return None if share is None else 100.0 * share
