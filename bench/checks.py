"""The comparison that decides ``correct``: readings of the program and
of the reference, the numbers compared, and their limits.

A reading is a norm, taken on the host in float64. Numbers compare the
two sides' norms leaf by leaf (the gap between the norms, not the norm
of the difference), so a rotation of the i-vector space, which minimum
divergence fixes only up to the signs of eigenvectors, reads as no gap.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import median
from typing import Dict, Iterable, Tuple

import numpy as np


def norm(x) -> float:
    """Frobenius norm of an array (device or host), summed in float64
    a slice at a time."""
    a = np.asarray(x).reshape(-1)
    step = 1 << 24
    return math.sqrt(sum(float(np.sum(np.square(a[i:i + step],
                                                 dtype=np.float64)))
                         for i in range(0, a.size, step)))


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float]
               ) -> Tuple[float, str]:
    """max over leaves of |prog - ref| / max(ref, the median leaf's ref):
    the gap measured against the leaf's own norm or, where that is
    smaller, the median leaf's, since some leaves are all but zero."""
    med = median(ref.values())
    best = (-1.0, "")
    for k, r in ref.items():
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        best = max(best, (gap, k))
    return best


def worst_relative(prog: Iterable[float], ref: Iterable[float]) -> float:
    """max |p - r| / |r| over paired scalars."""
    gaps = [abs(p - r) / max(abs(r), 1e-30) for p, r in zip(prog, ref)]
    return max((g if math.isfinite(g) else math.inf) for g in gaps)


def load_limits(path: Path) -> Dict[str, float]:
    """{number: limit} of a cell's limits file."""
    spec = json.loads(Path(path).read_text())
    return {k: float(v["limit"]) for k, v in spec["numbers"].items()}


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, dict]]:
    """(every number within its limit, {number: {value, limit}})."""
    compared = {k: {"value": float(values[k]), "limit": limits[k]}
                for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values())
    return ok, compared
