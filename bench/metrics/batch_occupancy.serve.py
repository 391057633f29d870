"""batch_occupancy.serve: how full the extractor's batches run over the
traced window, requests / (batches x max_batch), from its own counters
and the mix's ``serving.max_batch``. Each request fills one row of one
batch; a batch of a bucket holds at most max_batch rows and is padded
to max_batch. Moves ``extract_p95_ms``.
"""


def read(r):
    reqs, batches = r.counters.get("requests"), r.counters.get("batches")
    if not batches:
        return None
    return 100.0 * reqs / (batches * int(
        r.cell.traffic["serving"]["max_batch"]))
