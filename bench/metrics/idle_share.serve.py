"""idle_share.serve: the share of the traced window in which no operation
ran on the device: 1 - (union of device-op intervals) / window, averaged
over the chips (``bench/trace.py``). Moves ``extract_p95_ms``.
"""
from bench import trace


def read(r):
    if r.trace is None:
        return None
    share = trace.idle_share(r.trace)
    return None if share is None else 100.0 * share
