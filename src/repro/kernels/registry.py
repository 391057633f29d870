"""Kernel registry: static metadata the Pallas verifier enumerates.

Each production kernel (DESIGN.md §2, §8, §9, §12) registers a
:class:`KernelSpec` describing — WITHOUT launching anything — what the
checker needs to re-derive its safety argument:

  * the grid and every operand's BlockSpec (block shape + index map +
    memory space), so block/grid divisibility and output-coverage /
    write-write-race checks are mechanical (rule KRN001/KRN002);
  * which grid axes are declared reductions (out blocks legally revisited
    with accumulation, e.g. the E-step matmul's K axis);
  * the kernel body function itself, so the DMA-discipline pass can read
    its source (rule KRN003: every ``start()`` waited, ring slot
    ``j % depth`` reused only after its wait, a drain loop present);
  * per-grid-step VMEM residency (blocks + scratch) against the roofline
    budget (rule KRN004).

The metadata mirrors the ``pl.pallas_call`` in each kernel module; specs
take a ``config`` dict of the same shape names the wrappers use, so the
verifier can check both the registered baseline configs (must be clean)
and hypothetical paper-scale configs (where e.g. the fused-align gather
scratch legitimately over-fills VMEM — a finding, not a runtime surprise).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

_DT_BYTES = {"float32": 4, "int32": 4, "bfloat16": 2}


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


@dataclass(frozen=True)
class BlockMap:
    """One operand/output of a ``pallas_call``: block + index map."""
    name: str
    array_shape: Tuple[int, ...]            # full (possibly padded) shape
    block: Optional[Tuple[int, ...]]        # None => whole array (ANY/HBM)
    index_map: Optional[Callable]           # grid point -> block index
    memory: str = "vmem"                    # 'vmem' | 'smem' | 'any'
    dtype: str = "float32"

    def block_bytes(self) -> int:
        if self.block is None:
            return 0
        n = 1
        for d in self.block:
            n *= int(d)
        return n * _DT_BYTES.get(self.dtype, 4)


@dataclass(frozen=True)
class DmaRing:
    """A semaphore-ring DMA pipeline inside the kernel body."""
    name: str
    depth: int


@dataclass(frozen=True)
class KernelInstance:
    """A KernelSpec instantiated at one concrete config."""
    grid: Tuple[int, ...]
    inputs: Tuple[BlockMap, ...]
    outputs: Tuple[BlockMap, ...]
    scratch_bytes: int
    rings: Tuple[DmaRing, ...] = ()


@dataclass(frozen=True)
class KernelSpec:
    name: str
    kernel_fn: Callable                     # the Pallas body (AST target)
    describe: Callable[[dict], KernelInstance]
    default_config: dict
    reduction_axes: Tuple[int, ...] = ()    # grid axes that accumulate
    padded_by_wrapper: bool = True          # ops.py pad-and-clip wrapper
    has_dma_ring: bool = False

    def instance(self, config: Optional[dict] = None) -> KernelInstance:
        cfg = dict(self.default_config)
        if config:
            cfg.update(config)
        return self.describe(cfg)


KERNELS: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    KERNELS[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    return KERNELS[name]


def all_specs():
    return [KERNELS[k] for k in sorted(KERNELS)]


# ---------------------------------------------------------------------------
# gmm_loglik — dense vec-trick loglik (DESIGN.md §2)
# ---------------------------------------------------------------------------


def _gmm_loglik_instance(cfg: dict) -> KernelInstance:
    from repro.kernels import gmm_loglik as _gl
    F, C, D = cfg["F"], cfg["C"], cfg["D"]
    bf = min(cfg.get("block_f", _gl.BLOCK_F), F)
    bc = min(cfg.get("block_c", _gl.BLOCK_C), C)
    Fp, Cp = _ceil_to(F, bf), _ceil_to(C, bc)
    grid = (Fp // bf, Cp // bc)
    return KernelInstance(
        grid=grid,
        inputs=(
            BlockMap("x", (Fp, D), (bf, D), lambda i, j: (i, 0)),
            BlockMap("const", (1, Cp), (1, bc), lambda i, j: (0, j)),
            BlockMap("lin", (D, Cp), (D, bc), lambda i, j: (0, j)),
            BlockMap("P_flat", (Cp, D * D), (bc, D * D),
                     lambda i, j: (j, 0)),
        ),
        outputs=(
            BlockMap("out", (Fp, Cp), (bf, bc), lambda i, j: (i, j)),
        ),
        scratch_bytes=0,
    )


def _register_gmm_loglik():
    from repro.kernels import gmm_loglik as _gl
    register(KernelSpec(
        name="gmm_loglik", kernel_fn=_gl._kernel,
        describe=_gmm_loglik_instance,
        default_config={"F": 512, "C": 256, "D": 12},
    ))


# ---------------------------------------------------------------------------
# gmm_rescore — sparse gather-and-rescore with a DMA semaphore ring (§8)
# ---------------------------------------------------------------------------


def _gmm_rescore_instance(cfg: dict) -> KernelInstance:
    from repro.kernels import gmm_rescore as _gr
    F, D, K = cfg["F"], cfg["D"], cfg["K"]
    C = cfg["C"]
    E = _ceil_to(1 + D + D * D, 128)        # ops.py pads E to a lane multiple
    bf = min(cfg.get("block_f", _gr.BLOCK_F), F)
    Fp = _ceil_to(F, bf)
    n = bf * K
    depth = max(1, min(cfg.get("dma_depth", _gr.DMA_DEPTH), n))
    T = Fp // bf
    return KernelInstance(
        grid=(T,),
        inputs=(
            BlockMap("ids", (T, 1, n), (1, 1, n), lambda i: (i, 0, 0),
                     memory="smem", dtype="int32"),
            BlockMap("dst", (T, 1, n), (1, 1, n), lambda i: (i, 0, 0),
                     memory="smem", dtype="int32"),
            BlockMap("xe", (Fp, E), (bf, E), lambda i: (i, 0)),
            BlockMap("A", (C, 1, E), None, None, memory="any"),
        ),
        outputs=(
            BlockMap("out", (Fp, K), (bf, K), lambda i: (i, 0)),
        ),
        scratch_bytes=n * E * 4,
        rings=(DmaRing("sem", depth),),
    )


def _register_gmm_rescore():
    from repro.kernels import gmm_rescore as _gr
    register(KernelSpec(
        name="gmm_rescore", kernel_fn=_gr._kernel,
        describe=_gmm_rescore_instance,
        default_config={"F": 512, "C": 256, "D": 12, "K": 8},
        has_dma_ring=True,
    ))


# ---------------------------------------------------------------------------
# gmm_align — fused preselect/top-K/gather/rescore (§12)
# ---------------------------------------------------------------------------


def _gmm_align_instance(cfg: dict) -> KernelInstance:
    from repro.kernels import gmm_align as _ga
    F, D, C, K = cfg["F"], cfg["D"], cfg["C"], cfg["K"]
    E2 = cfg.get("E2", 1 + D + D * (D + 1) // 2)
    bf = min(cfg.get("block_f", _ga.BLOCK_F), F)
    Fp = _ceil_to(F, bf)
    depth = max(1, min(cfg.get("dma_depth", _ga.DMA_DEPTH), bf * K))
    return KernelInstance(
        grid=(Fp // bf,),
        inputs=(
            BlockMap("x", (Fp, D), (bf, D), lambda i: (i, 0)),
            BlockMap("dconst", (1, C), (1, C), lambda i: (0, 0)),
            BlockMap("dlin", (D, C), (D, C), lambda i: (0, 0)),
            BlockMap("dquad", (D, C), (D, C), lambda i: (0, 0)),
            BlockMap("sexp", (D * D, E2), (D * D, E2), lambda i: (0, 0)),
            BlockMap("A2", (C, E2), None, None, memory="any"),
        ),
        outputs=(
            BlockMap("ll", (Fp, K), (bf, K), lambda i: (i, 0)),
            BlockMap("sel", (Fp, K), (bf, K), lambda i: (i, 0),
                     dtype="int32"),
        ),
        # diag scores + ids/work/inv + gathered rows
        scratch_bytes=(bf * C + 3 * bf * K + bf * K * E2) * 4,
        rings=(DmaRing("sem", depth),),
    )


def _register_gmm_align():
    from repro.kernels import gmm_align as _ga
    register(KernelSpec(
        name="gmm_align", kernel_fn=_ga._kernel,
        describe=_gmm_align_instance,
        default_config={"F": 512, "C": 256, "D": 12, "K": 8},
        has_dma_ring=True,
    ))


# ---------------------------------------------------------------------------
# tvm_estep — packed-symmetric E-step matmul with grid-axis-2 reduction (§9)
# ---------------------------------------------------------------------------


def _tvm_estep_instance(cfg: dict) -> KernelInstance:
    from repro.kernels import tvm_estep as _te
    M, K, P = cfg["M"], cfg["K"], cfg["P"]
    bm = min(cfg.get("block_m", _te.BLOCK_U), M)
    bp = min(cfg.get("block_p", _te.BLOCK_P), P)
    bk = min(cfg.get("block_k", _te.BLOCK_C), K)
    Mp, Kp, Pp = _ceil_to(M, bm), _ceil_to(K, bk), _ceil_to(P, bp)
    dt = cfg.get("dtype", "float32")
    return KernelInstance(
        grid=(Mp // bm, Pp // bp, Kp // bk),
        inputs=(
            BlockMap("a", (Mp, Kp), (bm, bk), lambda i, j, k: (i, k),
                     dtype=dt),
            BlockMap("b", (Kp, Pp), (bk, bp), lambda i, j, k: (k, j),
                     dtype=dt),
        ),
        outputs=(
            # constant in the reduction axis k: the legal accumulation
            # pattern (init at k==0, += after) — NOT a write-write race
            BlockMap("out", (Mp, Pp), (bm, bp), lambda i, j, k: (i, j)),
        ),
        scratch_bytes=0,
    )


def _register_tvm_estep():
    from repro.kernels import tvm_estep as _te
    register(KernelSpec(
        name="tvm_estep", kernel_fn=_te._matmul_kernel,
        describe=_tvm_estep_instance,
        default_config={"M": 256, "K": 256, "P": 512, "dtype": "bfloat16"},
        reduction_axes=(2,),
    ))


# ---------------------------------------------------------------------------
# bw_stats — fused Baum-Welch accumulation, frame axis the reduction
# ---------------------------------------------------------------------------


def _bw_stats_instance(cfg: dict) -> KernelInstance:
    F, C, D = cfg["F"], cfg["C"], cfg["D"]
    bf = min(cfg.get("block_f", 256), F)
    bc = min(cfg.get("block_c", 128), C)
    Fp, Cp = _ceil_to(F, bf), _ceil_to(C, bc)
    return KernelInstance(
        grid=(Cp // bc, Fp // bf),
        inputs=(
            BlockMap("gamma", (Fp, Cp), (bf, bc), lambda j, i: (i, j)),
            BlockMap("x", (Fp, D), (bf, D), lambda j, i: (i, 0)),
        ),
        outputs=(
            BlockMap("n", (Cp,), (bc,), lambda j, i: (j,)),
            BlockMap("f", (Cp, D), (bc, D), lambda j, i: (j, 0)),
            BlockMap("S", (Cp, D * D), (bc, D * D), lambda j, i: (j, 0)),
        ),
        scratch_bytes=0,
    )


def _register_bw_stats():
    from repro.kernels import bw_stats as _bw
    register(KernelSpec(
        name="bw_stats", kernel_fn=_bw._kernel,
        describe=_bw_stats_instance,
        default_config={"F": 1024, "C": 256, "D": 12},
        reduction_axes=(1,),
    ))


_register_gmm_loglik()
_register_gmm_rescore()
_register_gmm_align()
_register_tvm_estep()
_register_bw_stats()
