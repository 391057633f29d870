"""StatsEngine: the single streaming align→Baum-Welch path (DESIGN.md §7),
mesh-aware end to end (DESIGN.md §11).

Every statistics consumer in the repo — UBM EM (`ubm.train_ubm`), TVM
training (`trainer.train`), i-vector extraction (`trainer.extract`,
`serving.IVectorExtractor`), and the launch-scale macro-step
(`launch/ivector_cell.py`) — streams utterance chunks through ONE
canonical chunk body:

    chunk_body:  [u, F, D] feats (+ [u, F] mask)
        -> flatten frames -> alignment (diag preselect, optional full-cov
           rescoring, floor + renormalise)            [alignment.py]
        -> scatter-add Baum-Welch moments             [stats.scatter_accumulate]
        -> ChunkStats(n [u, C], f [u, C, D], S, loglik, frames)

`stream` scans chunk_body over utterance chunks (`lax.scan` + an exact
remainder chunk), so nothing frame-resident — `[F, C]` posteriors,
`[F, D²]` expansions — outlives one chunk, and feeds pluggable
accumulators.

Mesh mode (``stream(..., mesh=...)``): the same scan runs inside one
`shard_map` over an utterance×component mesh — utterances block-sharded
over the data axes, UBM components (and the TVM `T_c` blocks) over
'model'. `chunk_body` stays the single source of truth; only the
alignment's component selection changes (``_align_sharded``: rank-local
diag preselect on the local C-block, two-stage top-K candidate exchange,
owner-local rescore, masked pmax — then the SAME
`alignment.finalise_posteriors` / `stats.scatter_accumulate` tail).
Accumulator results are all-reduced ONCE, at chunk-scan exit (a single
psum of the packed `[C, P]` / `(N, F)` carriers over the data axes), not
per chunk body. A 1-device mesh (or ``mesh=None``) takes the local path
bit-identically.

Accumulator contract (DESIGN.md §7, §11): an accumulator is a Python
object with three traced-pure methods —

    init()                  -> zero carry (a pytree)
    update(carry, chunk)    -> new carry   (chunk: ChunkStats)
    finalize(carry)         -> result

plus, for mesh mode, three structural hooks —

    mesh_args()             -> pytree of arrays needing component sharding
    mesh_in_specs(M)        -> matching pytree of PartitionSpecs
    with_mesh(spec, args, axis) -> rank-local clone (called inside shard_map)
    mesh_out_specs(M)       -> PartitionSpec pytree of finalize()'s result

`update` must be associative-merge style (it runs inside `lax.scan`).
Provided accumulators: `TotalsAccum` (global n/f/S sufficient stats +
loglik — the UBM-EM and Σ-update consumer) and `TVMAccum` (the TVM
E-step, merging `tvm.EMAccum` per chunk). Per-utterance n/f for
extraction are collected as scan outputs (`collect_nf=True`), not as a
reduction.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import alignment as AL
from repro.core import stats as ST
from repro.core import tvm as TV
from repro.core import ubm as U
from repro.kernels import ops

f32 = jnp.float32


@dataclass(frozen=True)
class EngineSpec:
    """Static (hashable) description of one align→stats configuration."""
    n_components: int
    top_k: int
    floor: float
    second_order: Optional[str] = None   # None | 'diag' | 'full'
    chunk: int = 0                       # utterances per scan chunk; 0 = all
    rescore: str = "dense"               # 'dense' | 'sparse' | 'fused'
    # (DESIGN.md §8, §12 — 'fused' is the packed-GEMM single-kernel path)


# The rescoring fallback ladder (DESIGN.md §12, §13), fastest first: a
# runtime failure of one mode demotes to the next — every mode feeds the
# identical downstream math, so demotion is a speed decision, not a
# semantic one. Serving sessions and the training supervisor's safety
# ladder both walk this tuple.
RESCORE_LADDER = ("fused", "sparse", "dense")


def degrade_rescore(mode: str) -> Optional[str]:
    """The next-safer rescore mode, or None when already at 'dense' (the
    reference path — a failure there is a real bug, not a kernel issue)."""
    i = RESCORE_LADDER.index(mode)
    return RESCORE_LADDER[i + 1] if i + 1 < len(RESCORE_LADDER) else None


def warn_demotion(mode: str, nxt: str, exc: BaseException) -> None:
    """A demotion is never silent: a kernel the compiler refuses would
    otherwise be served by the jnp reference with no sign of it."""
    warnings.warn(f"rescore mode {mode!r} failed with {exc!r}; demoting "
                  f"to {nxt!r}", RuntimeWarning, stacklevel=3)


class UBMPack(NamedTuple):
    """The per-model precompute the chunk body scores against (built once
    per pass/session, passed as a jit argument so device buffers are
    shared across compiled shapes). Every leaf has leading dim C, so in
    mesh mode the whole pack shards uniformly over 'model'."""
    full: Optional[U.FullGMM]     # None => diag-only scoring (UBM diag EM)
    diag: U.DiagGMM               # preselection (and diag-phase) GMM
    pre: Optional[Tuple]          # full_precisions(full)
    rescore_A: Optional[jax.Array] = None  # ubm.rescore_pack(pre): the
    # packed [C, 1+D+D²] gather rows the sparse rescoring kernel DMAs
    align_A: Optional[jax.Array] = None    # ubm.align_pack(pre): the
    # packed-symmetric [C, 1+D+D(D+1)/2] GEMM rows of the fused path


def pack_ubm(ubm: U.FullGMM) -> UBMPack:
    pre = U.full_precisions(ubm)
    return UBMPack(ubm, ubm.to_diag(), pre, U.rescore_pack(pre),
                   U.align_pack(pre))


def pack_diag(gmm: U.DiagGMM) -> UBMPack:
    return UBMPack(None, gmm, None, None, None)


class ChunkStats(NamedTuple):
    n: jax.Array                  # [u, C] per-utterance occupancies
    f: jax.Array                  # [u, C, D] per-utterance first order
    S: Optional[jax.Array]        # [C, D] | [C, D*D] chunk-summed | None
    loglik: jax.Array             # [] Σ valid-frame logsumexp (selected set)
    frames: jax.Array             # [] number of valid frames


class UBMStats(NamedTuple):
    """Finalized global sufficient statistics (TotalsAccum output)."""
    n: jax.Array                  # [C]
    f: jax.Array                  # [C, D]
    ss: Optional[jax.Array]       # [C, D] | [C, D, D] | None
    loglik: jax.Array             # []
    frames: jax.Array             # []


def _align_sharded(spec: EngineSpec, pack: UBMPack, x, m, axis: str):
    """Rank-local alignment of flattened frames against the LOCAL C-block
    (components sharded over ``axis``), collectives explicit:

      1. each rank diag-preselects over its C_loc block,
      2. two-stage top-K: local top-min(K, C_loc) per rank, all-gather
         only the [*, P·k_loc] candidates (never the [*, C] scores),
         global top-K — K ≤ P·k_loc always holds (K ≤ C = P·C_loc), and
         `top_k`'s lowest-index tie-break over the rank-ordered gather
         reproduces the unsharded lowest-global-id tie-break exactly,
      3. selected-set loglik per ``spec.rescore`` ('dense' vec-trick over
         the local block + gather, or 'sparse' gather-and-rescore of only
         the owned slots); unowned slots are masked to -inf and the
         replicated [*, K] logliks assembled with a pmax (each component
         is owned by exactly one rank),
      4. the SAME `alignment.finalise_posteriors` tail as the local path.

    Returns (values [*, K] owner-masked posteriors, indices [*, K] LOCAL
    component ids, lse [*] replicated) — the scatter in `chunk_body` then
    accumulates owner-locally with zero stats comms.
    """
    r = jax.lax.axis_index(axis)
    C_loc = pack.diag.means.shape[0]
    K = spec.top_k
    dll = U.diag_loglik(pack.diag, x)                 # [f, C_loc]
    k_loc = min(K, C_loc)
    lv, li = jax.lax.top_k(dll, k_loc)
    gi = li + r * C_loc                               # global ids
    lv_all = jax.lax.all_gather(lv, axis, axis=1, tiled=True)
    gi_all = jax.lax.all_gather(gi, axis, axis=1, tiled=True)
    sv, sp = jax.lax.top_k(lv_all, K)
    sel = jnp.take_along_axis(gi_all, sp, axis=1)     # [f, K] global ids
    own = (sel // C_loc) == r
    loc = jnp.where(own, sel % C_loc, 0)
    if pack.pre is None:
        # diag phase: the preselection scores ARE the selected-set scores
        vals = jnp.take_along_axis(dll, loc, axis=1)
    elif spec.rescore == "sparse":
        # gather-and-rescore only the selected slots against the local
        # C-block — [f, C_loc] full-cov scores never materialise
        fc, fl, fP = pack.pre
        vals = ops.gmm_rescore(x, loc, fc, fl.T,
                               fP.reshape(fP.shape[0], -1),
                               pack=pack.rescore_A)
    elif spec.rescore == "fused":
        # fused packed-GEMM rescore of the selected slots against the
        # local C-block's align_A rows ([C_loc, E2] — shards uniformly
        # over 'model' like every other pack leaf)
        vals = ops.gmm_rescore_fused(x, loc, pack.align_A)
    else:
        fc, fl, fP = pack.pre
        fll = ops.gmm_loglik(x, fc, fl.T, fP.reshape(fP.shape[0], -1))
        vals = jnp.take_along_axis(fll, loc, axis=1)
    vals = jnp.where(own, vals, -jnp.inf)
    sel_ll = jax.lax.pmax(vals, axis)                 # [f, K] replicated
    post, lse = AL.finalise_posteriors(sel_ll, spec.floor, m)
    return jnp.where(own, post, 0.0), loc, lse


def chunk_body(spec: EngineSpec, pack: UBMPack, feats_c,
               mask_c=None, axis: Optional[str] = None) -> ChunkStats:
    """THE canonical align→BW-stats body for one utterance chunk.

    feats_c: [u, F, D]; mask_c: [u, F] optional. Frames are flattened so
    alignment is one matmul; the scatter groups statistics back by
    utterance. Nothing here retains a frame-resident array beyond the
    chunk.

    With ``axis`` set (inside the engine's shard_map mode) the component
    dimension is the rank-local block: alignment runs through
    `_align_sharded` (same preselect/rescore/floor math, collectives for
    the candidate exchange) and the scatter stays owner-local. The loglik
    and frame counters come out replicated over ``axis`` — they reduce
    over the data axes only.
    """
    u, F, D = feats_c.shape
    x = feats_c.reshape(u * F, D)
    m = None if mask_c is None else mask_c.reshape(u * F)
    if axis is None:
        post, lse = AL.align_frames(
            x, pack.full, pack.diag, top_k=spec.top_k, floor=spec.floor,
            precomp=pack.pre, mask=m, with_loglik=True, rescore=spec.rescore,
            rescore_pack=pack.rescore_A, align_pack=pack.align_A)
        values, indices = post.values, post.indices
    else:
        values, indices, lse = _align_sharded(spec, pack, x, m, axis)
    n, f, S = ST.scatter_accumulate(
        x, values, indices, jnp.repeat(jnp.arange(u), F), u,
        spec.n_components, second_order=spec.second_order, mask=m)
    frames = (jnp.asarray(u * F, f32) if m is None
              else jnp.sum(m.astype(f32)))
    return ChunkStats(n, f, S, jnp.sum(lse), frames)


def session_stats(spec: EngineSpec, pack: UBMPack, feats, mask=None):
    """One streaming-session chunk: [F, D] frames (+ optional [F] mask)
    -> (n [C], f [C, D], loglik [], frames []).

    The serving session store (serving/session.py) accumulates these
    per-stream: because Baum-Welch statistics are additive over frames,
    summing per-chunk (n, f) over a live audio stream is EXACTLY the
    statistics of the whole utterance so far — the chunk boundary is a
    pure performance decision, like the frame mask (DESIGN.md §4, §14).
    Runs THE canonical `chunk_body`, so a streamed chunk and a batch
    request score through identical math.
    """
    cs = chunk_body(spec, pack, feats[None],
                    None if mask is None else mask[None])
    return cs.n[0], cs.f[0], cs.loglik, cs.frames


# ---------------------------------------------------------------------------
# Accumulators
# ---------------------------------------------------------------------------


def pin(x):
    """Materialise ``x`` as a buffer of its own, so XLA cannot fuse its
    producer into the ops that read it.

    The 'ordered' exit reduction is bit-exact against the one-device scan
    (DESIGN.md §11) only if each chunk is computed the same way in both.
    The trainer pins the E-step precompute: computed in the same program
    as the chunk scan, XLA would otherwise fuse it into the scan's dots,
    which on the CPU picks a dot with another summation order than the
    mesh path, where the shard_map boundary keeps the two apart.
    `TotalsAccum` pins each chunk's second-order moments: a scatter-add
    into zeros followed by an add to the scan carry is rewritten into a
    scatter into the carry, which sums in another order."""
    return jax.lax.optimization_barrier(x)


class TotalsAccum:
    """Global sufficient statistics: Σ_u n, Σ_u f, Σ S, loglik, frames.

    Feeds the UBM M-steps (`ubm.diag_m_step`/`full_m_step`), the TVM
    Σ-update, and the full UBM refresh at realignment. In mesh mode n/f/S
    stay owner-local over 'model' and psum over the data axes only;
    loglik/frames come out of the chunk body replicated over 'model'.
    """

    def __init__(self, spec: EngineSpec, feat_dim: int):
        self.spec = spec
        self.D = feat_dim

    def init(self):
        C, D = self.spec.n_components, self.D
        S0 = None
        if self.spec.second_order == "diag":
            S0 = jnp.zeros((C, D), f32)
        elif self.spec.second_order == "full":
            S0 = jnp.zeros((C, D * D), f32)
        return (jnp.zeros((C,), f32), jnp.zeros((C, D), f32), S0,
                jnp.zeros((), f32), jnp.zeros((), f32))

    def update(self, carry, chunk: ChunkStats):
        n, f, S, ll, fr = carry
        if chunk.S is not None:
            # pinned: XLA would fold a scattered S into the carry and
            # reorder the sums against the mesh path's per-chunk S
            S = S + pin(chunk.S)
        return (n + jnp.sum(chunk.n, axis=0), f + jnp.sum(chunk.f, axis=0),
                S, ll + chunk.loglik, fr + chunk.frames)

    def finalize(self, carry) -> UBMStats:
        n, f, S, ll, fr = carry
        if self.spec.second_order == "full":
            C, D = self.spec.n_components, self.D
            S = S.reshape(C, D, D)
        return UBMStats(n, f, S, ll, fr)

    # -- mesh protocol ------------------------------------------------------

    def mesh_args(self):
        return None

    def mesh_in_specs(self, M):
        return None

    def with_mesh(self, spec: EngineSpec, args, axis) -> "TotalsAccum":
        return TotalsAccum(spec, self.D)

    def mesh_out_specs(self, M):
        so = self.spec.second_order
        ss = (None if so is None
              else P(M, None) if so == "diag" else P(M, None, None))
        return UBMStats(n=P(M), f=P(M, None), ss=ss, loglik=P(), frames=P())


class TVMAccum:
    """TVM E-step accumulator: per-chunk (n, f) -> merged `tvm.EMAccum`.

    ``center_means`` (standard formulation) centres each chunk's
    first-order stats around the UBM means before the posterior solve.
    A packed ``pre`` (DESIGN.md §9) carries the A accumulator packed
    through the whole stream; ``estep_dtype`` selects the contraction
    input precision (bf16 inputs, f32 accumulation).

    In mesh mode (``axis`` set by `with_mesh`) the E-step contractions run
    on the rank-local C-block: the partial precision rows [u, P] and rhs
    [u, R] psum over 'model' inside `tvm.posterior` (the only model-axis
    collective), then A/B/n_tot stay owner-local and h/H replicated — the
    exact `[C, P]`/`[C, D, R]` packing the exit psum carries.
    """

    def __init__(self, model: TV.TVModel, pre: TV.Precomp,
                 center_means=None, estep_dtype: str = "float32",
                 axis: Optional[str] = None):
        self.model = model
        self.pre = pre
        self.center_means = center_means
        self.estep_dtype = estep_dtype
        self.axis = axis

    def init(self):
        C, D, R = self.model.T.shape
        return TV.EMAccum.zeros(
            C, D, R, estep="packed" if self.pre.packed else "dense")

    def update(self, carry, chunk: ChunkStats):
        n, f = chunk.n, chunk.f
        if self.center_means is not None:
            st = ST.center(ST.BWStats(n, f, None), self.center_means)
            n, f = st.n, st.f
        return TV.merge_accums(
            carry, TV.em_accumulate(self.model, self.pre, n, f,
                                    estep_dtype=self.estep_dtype,
                                    axis=self.axis))

    def finalize(self, carry) -> TV.EMAccum:
        return carry

    # -- mesh protocol ------------------------------------------------------

    def mesh_args(self):
        return (self.model, self.pre, self.center_means)

    def mesh_in_specs(self, M):
        mspec = TV.TVModel(T=P(M, None, None), Sigma=P(M, None, None),
                           prior=P(), means=P(M, None),
                           formulation=self.model.formulation)
        pspec = TV.Precomp(P(M, None) if self.pre.packed
                           else P(M, None, None), P(M, None, None))
        cspec = None if self.center_means is None else P(M, None)
        return (mspec, pspec, cspec)

    def with_mesh(self, spec: EngineSpec, args, axis) -> "TVMAccum":
        model, pre, center = args
        return TVMAccum(model, pre, center_means=center,
                        estep_dtype=self.estep_dtype, axis=axis)

    def mesh_out_specs(self, M):
        return TV.EMAccum(
            A=P(M, None) if self.pre.packed else P(M, None, None),
            B=P(M, None, None), h=P(), H=P(), n_tot=P(M), n_utts=P())


# ---------------------------------------------------------------------------
# Streaming
# ---------------------------------------------------------------------------


def _stream_local(spec: EngineSpec, pack: UBMPack, feats, mask,
                  accums: Sequence, collect_nf: bool = False,
                  axis: Optional[str] = None):
    """Scan `chunk_body` over utterance chunks, feeding ``accums``.

    The single scan implementation: the public `stream` calls it directly
    (mesh None / 1 device) or wraps it in `shard_map` (``axis`` is then
    the model axis the chunk body's collectives run over). A ragged tail
    (U % chunk != 0) runs as one exact remainder chunk, so arbitrary
    batch sizes keep the bounded per-chunk footprint.
    """
    n_utts, F, D = feats.shape
    chunk = n_utts if spec.chunk <= 0 else min(spec.chunk, n_utts)
    g, rem = divmod(n_utts, chunk)
    carries = tuple(a.init() for a in accums)

    def body(carries, inp):
        feats_c, mask_c = inp
        cs = chunk_body(spec, pack, feats_c, mask_c, axis=axis)
        new = tuple(a.update(c, cs) for a, c in zip(accums, carries))
        return new, ((cs.n, cs.f) if collect_nf else None)

    C = spec.n_components
    ns = fs = None
    if g:
        fr = feats[:g * chunk].reshape(g, chunk, F, D)
        mr = (None if mask is None
              else mask[:g * chunk].reshape(g, chunk, F))
        carries, ys = jax.lax.scan(body, carries, (fr, mr))
        if collect_nf:
            ns = ys[0].reshape(g * chunk, C)
            fs = ys[1].reshape(g * chunk, C, D)
    if rem:
        tail_m = None if mask is None else mask[g * chunk:]
        carries, ys_t = body(carries, (feats[g * chunk:], tail_m))
        if collect_nf:
            ns = ys_t[0] if ns is None else jnp.concatenate([ns, ys_t[0]])
            fs = ys_t[1] if fs is None else jnp.concatenate([fs, ys_t[1]])
    results = tuple(a.finalize(c) for a, c in zip(accums, carries))
    return results, ((ns, fs) if collect_nf else None)


def _ordered_data_sum(x, data_axes):
    """Deterministic data-axis reduction: all-gather the per-rank partial
    accumulators and fold them LEFT in rank order. When the chunk
    partition aligns with the shard boundaries (U/Pd a multiple of the
    chunk size, or one chunk per rank) this reproduces the single-device
    scan's merge association bit-for-bit — `lax.psum`'s reduction order
    would not (DESIGN.md §11). Costs Pd× the psum bytes; pod-scale runs
    opt into ``exit_reduce='psum'`` instead."""
    g = jax.lax.all_gather(x, data_axes, axis=0, tiled=False)
    acc = g[0]
    for i in range(1, g.shape[0]):
        acc = acc + g[i]
    return acc


def _stream_sharded(spec: EngineSpec, pack: UBMPack, feats, mask,
                    accums: Sequence, collect_nf: bool, mesh,
                    exit_reduce: str = "ordered"):
    """One `shard_map` around the whole chunk scan (DESIGN.md §11).

    Utterances block-shard over the data axes, every dim-0==C operand
    (UBMPack, TVModel/Precomp rows) over 'model'. Inside, each rank runs
    the plain `_stream_local` scan on its shard; the finalized accumulator
    results — and ONLY those packed carriers — all-reduce over the data
    axes once, at scan exit. Per-utterance collect_nf outputs stay sharded
    (reassembled by the out_specs), never all-reduced.

    ``exit_reduce`` picks the exit collective: 'ordered' (default) folds
    the gathered per-rank partials in rank order — bit-reproducible
    against the single-device scan when chunk boundaries align with shard
    boundaries; 'psum' is the bandwidth-optimal tree all-reduce for
    pod-scale meshes (fp-reassociation tolerance, DESIGN.md §11).
    """
    if exit_reduce not in ("ordered", "psum"):
        raise ValueError(f"exit_reduce must be 'ordered' or 'psum': "
                         f"{exit_reduce!r}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    M = "model" if "model" in sizes else None
    Pm = sizes.get("model", 1)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    C = spec.n_components
    if C % Pm:
        raise ValueError(f"n_components={C} does not divide the mesh's "
                         f"model extent {Pm}")
    spec_loc = dataclasses.replace(spec, n_components=C // Pm)
    # a size-1 model axis needs no collectives: the local alignment math
    # runs bit-identically to the unsharded path
    axis = M if Pm > 1 else None

    margs = tuple(a.mesh_args() for a in accums)

    def fn(feats_l, mask_l, pack_l, margs_l):
        accs = tuple(a.with_mesh(spec_loc, ma, axis)
                     for a, ma in zip(accums, margs_l))
        results, nf = _stream_local(spec_loc, pack_l, feats_l, mask_l,
                                    accs, collect_nf, axis=axis)
        if data_axes:
            red = (_ordered_data_sum if exit_reduce == "ordered"
                   else jax.lax.psum)
            results = jax.tree.map(lambda x: red(x, data_axes), results)
        return results, nf

    pack_spec = jax.tree.map(
        lambda l: P(M, *([None] * (l.ndim - 1))), pack)
    in_specs = (P(data_axes, None, None),
                None if mask is None else P(data_axes, None),
                pack_spec,
                tuple(a.mesh_in_specs(M) for a in accums))
    out_specs = (tuple(a.mesh_out_specs(M) for a in accums),
                 (P(data_axes, M), P(data_axes, M, None)) if collect_nf
                 else None)
    fn_sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    return fn_sm(feats, mask, pack, margs)


def stream(spec: EngineSpec, pack: UBMPack, feats, mask,
           accums: Sequence, collect_nf: bool = False, mesh=None,
           exit_reduce: str = "ordered"):
    """Scan `chunk_body` over utterance chunks, feeding ``accums``.

    feats: [U, F, D]; mask: [U, F] or None. Returns
    (tuple of finalized accumulator results,
     (n [U, C], f [U, C, D]) if ``collect_nf`` else None).

    ``mesh`` selects the substrate: None or a 1-device mesh streams
    locally (bit-identical to the historical path); a larger mesh runs the
    same scan inside `shard_map` over (data..., 'model') with ONE
    accumulator all-reduce at scan exit. With the default
    ``exit_reduce='ordered'`` a data-only mesh whose shard size is a
    multiple of the chunk size reproduces the single-device results
    bit-for-bit; 'psum' (pod scale) and model-sharded meshes agree up to
    fp reassociation of that exit reduction (DESIGN.md §11).
    """
    if mesh is None or mesh.size == 1:
        return _stream_local(spec, pack, feats, mask, accums, collect_nf)
    return _stream_sharded(spec, pack, feats, mask, accums, collect_nf,
                           mesh, exit_reduce=exit_reduce)


def stream_bw(spec: EngineSpec, pack: UBMPack, feats, mask=None, mesh=None):
    """Streamed Baum-Welch stats with per-utterance n/f (extraction and
    the TVM stats path): -> (BWStats, (loglik, frames))."""
    (tot,), nf = stream(spec, pack, feats, mask,
                        (TotalsAccum(spec, feats.shape[-1]),),
                        collect_nf=True, mesh=mesh)
    return ST.BWStats(nf[0], nf[1], tot.ss), (tot.loglik, tot.frames)


def stream_ubm(spec: EngineSpec, pack: UBMPack, feats,
               mask=None, mesh=None) -> UBMStats:
    """Streamed global sufficient statistics (UBM EM): no per-utterance
    arrays are retained at all."""
    (tot,), _ = stream(spec, pack, feats, mask,
                       (TotalsAccum(spec, feats.shape[-1]),), mesh=mesh)
    return tot
