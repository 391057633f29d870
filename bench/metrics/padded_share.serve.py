"""padded_share.serve: the share of the frames the extractor ran that
were padding, from its own counters over the traced window:
padded_frames / (real_frames + padded_frames). Power-of-two frame
buckets and batches padded to max_batch both add to it. Moves
``extract_p95_ms``.
"""


def read(r):
    real, pad = r.counters.get("real_frames"), r.counters.get("padded_frames")
    if not real and not pad:
        return None
    return 100.0 * pad / (real + pad)
