"""Roofline-term derivation from compiled dry-run artifacts.

Per the brief (TPU v5e targets):
    compute term    = HLO_FLOPs / (chips x 197e12 FLOP/s bf16)
    memory term     = HLO_bytes / (chips x 819e9 B/s HBM)
    collective term = collective_bytes / (chips x 50e9 B/s per ICI link)

``cost_analysis()`` on the partitioned module reports PER-DEVICE flops and
bytes (verified empirically in tests), so totals are per-device x chips and
the division by chips cancels: terms are computed from per-device numbers
directly. collective_bytes is parsed from the optimized HLO text: the sum of
link-crossing byte counts for all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops (all-reduce counts 2x: reduce-scatter +
all-gather phases).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.analysis import optable

# ---------------------------------------------------------------------------
# Hardware table, keyed by jax's ``device_kind``
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Hardware:
    name: str                # jax ``device_kind``
    peak_flops: float        # bf16 FLOP/s per chip
    hbm_bw: float            # B/s per chip
    link_bw: float           # B/s per ICI link
    hbm_bytes: float         # per-chip capacity
    # fused-alignment cost-model constants (DESIGN.md §12), unmeasured:
    gather_bw: float         # effective B/s of data-dependent row gathers
    dma_issue_s: float       # exposed per-DMA issue overhead
    gather_overlap: bool     # row gathers hide under the rescore GEMM
    resident_bytes: float    # on-chip budget for a resident [C, E2] pack


HARDWARE: Dict[str, Hardware] = {h.name: h for h in (
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s of ICI (4 links, 50 GB/s each). The TPU
    # kernel's sorted row DMAs run near HBM bandwidth and its DMA ring
    # prefetches the next tile's rows under the current tile's matmul;
    # the resident budget is half of the 16 MB scoped VMEM.
    Hardware(name="TPU v5 lite", peak_flops=197e12, hbm_bw=819e9,
             link_bw=50e9, hbm_bytes=16e9, gather_bw=600e9,
             dma_issue_s=10e-9, gather_overlap=True, resident_bytes=8e6),
    # single-core container numbers (measured GEMM throughput ~8e10
    # FLOP/s f32; streaming ~2e10 B/s). Row gathers on the CPU jnp path
    # materialise through scalar copy loops (~1.5 GB/s) and run before
    # the GEMM, not under it — what flips the union/full crossover.
    Hardware(name="cpu", peak_flops=8e10, hbm_bw=2e10, link_bw=1e9,
             hbm_bytes=4e9, gather_bw=1.5e9, dma_issue_s=0.0,
             gather_overlap=False, resident_bytes=2e6),
)}


def hardware(device_kind: str) -> Hardware:
    """The table entry for ``device_kind``; an unknown device is an
    error, never a default."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline entry for device_kind {device_kind!r}; "
            f"known: {sorted(HARDWARE)}") from None


def local_hardware() -> Hardware:
    """The table entry for this process's first device."""
    import jax
    return hardware(jax.devices()[0].device_kind)


# the chip the dry-run roofline reports are computed for
HW = hardware("TPU v5 lite")

# shared op-table (DESIGN.md §15): this module used to carry its own
# dtype/shape/collective copies and had already drifted from hlo_cost's
# (no ``token`` entry here); both walkers now read ``optable``
_DTYPE_BYTES = optable.DTYPE_BYTES
_COLLECTIVES = optable.COLLECTIVES
_SHAPE_RE = optable.SHAPE_RE
_shape_bytes = optable.shape_bytes


def _line_output_bytes(line: str) -> int:
    """Bytes of the op's output (handles tuple-shaped outputs)."""
    lhs = line.split(" = ", 1)
    if len(lhs) != 2:
        return 0
    rhs = lhs[1]
    # output type(s) appear before the op name
    for op in _COLLECTIVES:
        k = rhs.find(op)
        if k >= 0:
            type_str = rhs[:k]
            return sum(_shape_bytes(m.group(1), m.group(2))
                       for m in _SHAPE_RE.finditer(type_str))
    return 0


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum link-crossing bytes per collective kind from optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        for op in _COLLECTIVES:
            # match op invocation, not metadata mentions
            if re.search(rf"\b{op}(-start|-done)?\(", s):
                b = _line_output_bytes(s)
                if op == "all-reduce":
                    b *= 2  # reduce-scatter + all-gather phases
                if op.endswith("done"):
                    b = 0
                out[op] += b
                out["total"] += b
                break
    return out


# ---------------------------------------------------------------------------
# Fused-alignment block-size autotuner (DESIGN.md §12)
# ---------------------------------------------------------------------------

_ALIGN_BLOCK_F = (8, 16, 32, 64, 128)
_ALIGN_DMA_DEPTH = (2, 4, 8)


@dataclass(frozen=True)
class AlignTune:
    """Winning fused-alignment schedule for one (C, K, D, device) cell."""
    strategy: str            # 'union' (tile-union gather-GEMM) | 'full'
    block_f: int             # frame-tile BF
    dma_depth: int           # DMA semaphore ring depth
    t_predicted: float       # cost-model seconds for `frames` frames
    candidates: tuple = ()   # ((strategy, bf, depth, t_pred), ...) swept


def align_cost_model(C: int, K: int, D: int, *, block_f: int,
                     strategy: str, dma_depth: int = 4,
                     frames: int = 4096, hw: Hardware = HW) -> float:
    """Predicted seconds for the fused rescore stage of `frames` frames.

    roofline t = max(flops/peak, bytes/bw) + exposed DMA issue overhead.
    'union' gathers the sorted BF·K tile-union rows per frame-tile and
    GEMMs against them (u = min(BF·K, C) distinct-row upper bound);
    'full' streams the whole [C, E2] pack through one GEMM — no gather,
    C/u more FLOPs. The preselect term is shared by every candidate and
    therefore omitted.
    """
    E2 = 1 + D + D * (D + 1) // 2
    tiles = -(-frames // block_f)
    xe_bytes = 4.0 * frames * E2
    gather_bw = hw.gather_bw
    if strategy == "union":
        u = min(block_f * K, C)
        flops = 2.0 * frames * u * E2
        gather_bytes = 4.0 * tiles * u * E2
        t_gather = gather_bytes / gather_bw
        t_issue = tiles * u * hw.dma_issue_s / max(dma_depth, 1)
        if hw.gather_overlap:
            t_mem = t_gather + xe_bytes / hw.hbm_bw
        else:
            # sequential gather-then-GEMM: the gather never hides under
            # the matmul, so it lands outside the roofline max()
            t_mem = xe_bytes / hw.hbm_bw
            t_issue += t_gather
    elif strategy == "full":
        flops = 2.0 * frames * C * E2
        pack_bytes = 4.0 * C * E2
        if pack_bytes > hw.resident_bytes:
            pack_bytes *= tiles            # re-streamed every frame-tile
        t_mem = (pack_bytes + xe_bytes) / hw.hbm_bw
        t_issue = 0.0
    else:
        raise ValueError(f"strategy must be 'union' or 'full': {strategy!r}")
    return max(flops / hw.peak_flops, t_mem) + t_issue


_ALIGN_TUNE_CACHE: Dict[tuple, "AlignTune"] = {}


def autotune_align(C: int, K: int, D: int, *,
                   device_kind: Optional[str] = None,
                   frames: int = 4096) -> AlignTune:
    """Pick the fused-alignment schedule for one (C, K, D, device) cell
    (``device_kind`` defaults to this process's first device).

    Sweeps (strategy, BF, dma_depth) through ``align_cost_model`` and
    caches the winner — the sweep is pure arithmetic, so tuning happens
    at trace time with no measurement; `benchmarks/roofline_table.py`
    records predicted-vs-measured for every candidate into
    ``BENCH_autotune.json`` to keep the model honest.
    """
    hw = local_hardware() if device_kind is None else hardware(device_kind)
    key = (C, K, D, hw.name, frames)
    hit = _ALIGN_TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    rows = []
    # 'full' first: exact ties (u == C makes both strategies pure
    # whole-pack GEMMs FLOP-wise) resolve to the gather-free path
    for strategy in ("full", "union"):
        for bf in _ALIGN_BLOCK_F:
            if bf > max(frames, 1):
                continue
            depths = _ALIGN_DMA_DEPTH if strategy == "union" else (4,)
            for depth in depths:
                t = align_cost_model(C, K, D, block_f=bf, strategy=strategy,
                                     dma_depth=depth, frames=frames, hw=hw)
                rows.append((strategy, bf, depth, t))
    win = min(rows, key=lambda r: r[3])
    tune = AlignTune(strategy=win[0], block_f=win[1], dma_depth=win[2],
                     t_predicted=win[3], candidates=tuple(rows))
    _ALIGN_TUNE_CACHE[key] = tune
    return tune


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    model_flops_total: float            # 6*N*D / 2*N_active*D etc.
    peak_memory_per_device: Optional[float] = None
    collectives: Dict[str, int] = field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / HW.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HW.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / HW.link_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_total = self.flops_per_device * self.chips
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-compute-time / bound-time: how close the step is to the
        compute roofline given its dominant term."""
        t_useful = (self.model_flops_total / self.chips) / HW.peak_flops
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_useful / t_bound if t_bound else 0.0

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.collective_bytes_per_device,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "model_flops": self.model_flops_total,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "peak_memory_per_device": self.peak_memory_per_device,
            "collectives": self.collectives,
        }


def roofline_from_compiled(compiled, *, arch: str, shape: str, mesh_desc: str,
                           chips: int, model_flops: float) -> RooflineReport:
    """Derive roofline terms with the trip-count-aware HLO walker.

    ``compiled.cost_analysis()`` counts while (scan) bodies once, so a
    layer-scanned program under-reports by ~n_layers; the walker multiplies
    through ``known_trip_count`` (see hlo_cost.py). Raw cost_analysis values
    are preserved in ``collectives['_raw_cost_analysis']`` for reference.
    """
    from repro.analysis.hlo_cost import analyze_hlo

    ca = compiled.cost_analysis() or {}
    walk = analyze_hlo(compiled.as_text())
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                        ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        mem = None
    coll = dict(walk["coll_by_kind"])
    coll["_raw_cost_analysis"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    if walk["warnings"]:
        coll["_warnings"] = walk["warnings"]
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        flops_per_device=float(walk["flops"]),
        bytes_per_device=float(walk["bytes"]),
        collective_bytes_per_device=float(walk["coll_bytes"]),
        model_flops_total=model_flops, peak_memory_per_device=mem,
        collectives=coll,
    )
