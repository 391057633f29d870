"""Pass 2: Pallas kernel verifier (KRN001-KRN004).

Consumes :mod:`repro.kernels.registry` metadata — no kernel is launched.

  * KRN001 grid/BlockSpec divisibility: every blocked dimension of every
    operand must divide evenly, or the spec must declare the ops.py
    pad-and-clip wrapper.
  * KRN002 write-write races and coverage: enumerate the grid, evaluate
    every output index map; a block index produced by two grid points
    that differ outside the declared reduction axes is a race, and an
    output block no grid point produces is a coverage hole.
  * KRN003 DMA ring discipline: parse the kernel body's source AST —
    each ``.start()`` needs ``.wait()`` counterparts, slot reuse must be
    guarded (a ``pl.when``-style predicate or modular slot indexing with
    declared depth >= 1), and a drain loop must wait out the tail.
    Kernels with no declared ring must contain no async-copy calls.
  * KRN004 VMEM residency: per-grid-step block bytes + scratch bytes vs
    2x the roofline resident budget (the full per-core VMEM).
"""
from __future__ import annotations

import ast
import inspect
import itertools
import textwrap
from typing import List, Optional

from repro.analysis import roofline
from repro.analysis.check.findings import Finding, make_finding

# resident_bytes is the *streaming* working-set target (half VMEM, so
# the pipeline can double-buffer); a kernel instance may legally fill
# the whole core => budget is 2x.
VMEM_BUDGET_BYTES = 2 * roofline.hardware("TPU v5 lite").resident_bytes


def _check_divisibility(spec, inst) -> List[Finding]:
    out: List[Finding] = []
    loc = f"kernel:{spec.name}"
    for bm in list(inst.inputs) + list(inst.outputs):
        if bm.block is None:
            continue
        for d, (dim, blk) in enumerate(zip(bm.array_shape, bm.block)):
            if blk and dim % blk:
                if spec.padded_by_wrapper:
                    continue
                out.append(make_finding(
                    "KRN001", loc,
                    f"operand '{bm.name}' dim {d} (extent {dim}) not "
                    f"divisible by block {blk} and no pad-and-clip "
                    "wrapper declared",
                    "pad the array to a block multiple in the host "
                    "wrapper and clip the result"))
    return out


def _check_races_and_coverage(spec, inst) -> List[Finding]:
    out: List[Finding] = []
    loc = f"kernel:{spec.name}"
    grid_points = list(itertools.product(*[range(g) for g in inst.grid]))
    red = set(spec.reduction_axes)
    for bm in inst.outputs:
        if bm.index_map is None or bm.block is None:
            continue
        writers = {}
        for pt in grid_points:
            idx = tuple(bm.index_map(*pt))
            writers.setdefault(idx, []).append(pt)
        # race: same output block from grid points differing outside
        # the reduction axes
        for idx, pts in writers.items():
            non_red = {tuple(c for a, c in enumerate(pt) if a not in red)
                       for pt in pts}
            if len(non_red) > 1:
                out.append(make_finding(
                    "KRN002", loc,
                    f"output '{bm.name}' block {idx} written by "
                    f"{len(pts)} grid points differing outside declared "
                    f"reduction axes {sorted(red) or '()'}",
                    "make the output index map injective over "
                    "non-reduction grid axes, or declare the axis in "
                    "reduction_axes with an init/accumulate body"))
                break
        # coverage: every ceil-div output block must be produced
        nblocks = tuple(-(-dim // blk) if blk else 1
                        for dim, blk in zip(bm.array_shape, bm.block))
        expect = set(itertools.product(*[range(n) for n in nblocks]))
        missing = expect - set(writers)
        if missing:
            out.append(make_finding(
                "KRN002", loc,
                f"output '{bm.name}' blocks never written: "
                f"{sorted(missing)[:4]}{'...' if len(missing) > 4 else ''}",
                "extend the grid or fix the output index map so every "
                "output block has a writer"))
    return out


class _DmaVisitor(ast.NodeVisitor):
    """Collect async-copy start/wait calls and guard/slot evidence."""

    def __init__(self):
        self.starts = 0
        self.waits = 0
        self.guarded_waits = 0       # wait under a pl.when predicate
        self.mod_slots = False       # j % depth style semaphore slotting
        self.loops = 0               # fori_loop / for statements
        self._when_depth = 0

    def visit_FunctionDef(self, node):
        for dec in node.decorator_list:
            if "when" in ast.dump(dec):
                self._when_depth += 1
                self.generic_visit(node)
                self._when_depth -= 1
                return
        self.generic_visit(node)

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if fn.attr == "start":
                self.starts += 1
            elif fn.attr == "wait":
                self.waits += 1
                if self._when_depth:
                    self.guarded_waits += 1
            elif fn.attr == "fori_loop" or (
                    isinstance(fn, ast.Attribute) and "loop" in fn.attr):
                self.loops += 1
        elif isinstance(fn, ast.Name) and "loop" in fn.id:
            self.loops += 1
        self.generic_visit(node)

    def visit_BinOp(self, node):
        if isinstance(node.op, ast.Mod):
            self.mod_slots = True
        self.generic_visit(node)

    def visit_For(self, node):
        self.loops += 1
        self.generic_visit(node)


def _check_dma(spec) -> List[Finding]:
    out: List[Finding] = []
    loc = f"kernel:{spec.name}"
    try:
        src = textwrap.dedent(inspect.getsource(spec.kernel_fn))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError):
        return [make_finding(
            "KRN003", loc,
            "kernel body source unavailable; DMA discipline unverifiable",
            "register a kernel_fn whose source inspect can reach")]
    vis = _DmaVisitor()
    vis.visit(tree)

    inst = spec.instance()
    if not spec.has_dma_ring:
        if vis.starts or vis.waits:
            out.append(make_finding(
                "KRN003", loc,
                f"async-copy calls ({vis.starts} start / {vis.waits} "
                "wait) in a kernel with no declared DMA ring",
                "declare the ring (has_dma_ring + DmaRing in the "
                "instance) so its discipline is verified"))
        return out

    for ring in inst.rings:
        if ring.depth < 1:
            out.append(make_finding(
                "KRN003", loc,
                f"ring '{ring.name}' depth {ring.depth} < 1",
                "clamp depth to max(1, ...)"))
    if vis.starts == 0:
        out.append(make_finding(
            "KRN003", loc,
            "declared DMA ring but kernel body issues no start()",
            "drop has_dma_ring or issue the copies"))
        return out
    if vis.waits == 0:
        out.append(make_finding(
            "KRN003", loc,
            f"{vis.starts} start() with no wait(): in-flight DMA read "
            "or semaphore leak",
            "wait slot j % depth before reuse and drain the tail"))
        return out
    deep = any(r.depth > 1 for r in inst.rings)
    if deep and vis.guarded_waits == 0:
        out.append(make_finding(
            "KRN003", loc,
            "ring depth > 1 but no guarded wait (pl.when) before slot "
            "reuse",
            "guard the steady-state wait with @pl.when(j >= depth)"))
    if deep and not vis.mod_slots:
        out.append(make_finding(
            "KRN003", loc,
            "ring depth > 1 but no modular slot indexing (j % depth) "
            "found",
            "index semaphores with slot = j % depth"))
    if vis.guarded_waits and vis.guarded_waits == vis.waits:
        out.append(make_finding(
            "KRN003", loc,
            "every wait() is predicate-guarded: no unconditional drain "
            "for the last in-flight copies",
            "add a drain loop waiting the final min(depth, n) slots"))
    return out


def _check_vmem(spec, inst) -> List[Finding]:
    resident = sum(bm.block_bytes()
                   for bm in list(inst.inputs) + list(inst.outputs))
    resident += inst.scratch_bytes
    if resident > VMEM_BUDGET_BYTES:
        return [make_finding(
            "KRN004", f"kernel:{spec.name}",
            f"per-grid-step residency {resident / 1e6:.2f} MB exceeds "
            f"VMEM budget {VMEM_BUDGET_BYTES / 1e6:.1f} MB",
            "shrink block_f / dma window or spill the gather table to "
            "ANY memory with explicit copies")]
    return []


def check_kernel(spec, config: Optional[dict] = None) -> List[Finding]:
    """Run KRN001-KRN004 over one registered KernelSpec."""
    inst = spec.instance(config)
    findings: List[Finding] = []
    findings += _check_divisibility(spec, inst)
    findings += _check_races_and_coverage(spec, inst)
    findings += _check_dma(spec)
    findings += _check_vmem(spec, inst)
    return findings


def check_all_kernels() -> List[Finding]:
    from repro.kernels import registry
    out: List[Finding] = []
    for spec in registry.all_specs():
        out += check_kernel(spec)
    return out
