"""Batched variable-length i-vector extraction service (DESIGN.md §5, §13).

The training stack works on fixed [U, F, D] blocks; production traffic is
ragged — one utterance per request, each a different number of frames. This
module turns the trained (UBM, TVM) pair into a serving session:

  * **cached precompute** — ``full_precisions(ubm)`` (Cholesky + inverse of
    C full covariances), the diag preselection GMM, the packed rescoring
    rows for both sparse and fused alignment (``ubm.rescore_pack`` /
    ``ubm.align_pack``, DESIGN.md §8/§12, carried in ``engine.UBMPack``),
    and ``TV.precompute`` (T^T Σ^{-1} T) are computed once per session,
    not once per call;
  * **power-of-two frame buckets** — each utterance is zero-padded (with a
    frame mask) to the next power-of-two frame count, so the number of
    distinct jitted shapes is O(log max_frames) instead of O(#lengths);
  * **micro-batching** — requests sharing a bucket are batched up to
    ``max_batch`` and extracted in one device call; the batch dim is also
    padded (zero-mask rows), so each bucket compiles exactly once;
  * **length-norm** — i-vectors are projected to the unit sphere (the form
    every downstream scorer in this repo consumes).

Masking (core/alignment.py, core/stats.py) makes the padding exact: a
padded-and-masked utterance produces bit-identical Baum-Welch statistics
to the unpadded one, so bucketing is a pure performance decision.

Serving guardrails (DESIGN.md §13): inputs are validated instead of
trusted — non-finite (NaN/Inf) frames are masked out and counted,
over-long utterances are truncated with an explicit per-request
``truncated`` flag (never silently), and empty/all-invalid utterances
come back as flagged zero vectors. A runtime failure of the alignment
kernel demotes the session down the rescore ladder fused → sparse →
dense (`engine.degrade_rescore`) and keeps serving — a kernel bug
degrades throughput, it does not kill the server. `health_check` runs a
canary extraction through the same path as real traffic, so a readiness
probe exercises (and, if needed, pre-demotes) the session before traffic
arrives. Admission control lives in `serving/guard.py`.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.ivector_tvm import IVectorConfig
from repro.core import backend as BK
from repro.core import engine as EN
from repro.core import stats as ST
from repro.core import tvm as TV
from repro.core import ubm as U

f32 = jnp.float32


def bucket_cap(min_bucket: int, max_bucket: int) -> int:
    """Largest bucket on the power-of-two grid (min_bucket * 2^k) that
    does not exceed ``max_bucket`` — the shape long requests are
    truncated to. Truncating to ``max_bucket`` itself would land
    off-grid whenever it is not a power-of-two multiple of
    ``min_bucket``, and every off-grid shape is a fresh jit."""
    cap = max(1, int(min_bucket))
    while cap * 2 <= max_bucket:
        cap *= 2
    return cap


def bucket_for(n_frames: int, min_bucket: int, cap: int) -> int:
    """Smallest power-of-two bucket holding ``n_frames``, capped."""
    b = max(1, int(min_bucket))
    while b < n_frames and b < cap:
        b *= 2
    return min(b, cap)


@dataclass(frozen=True)
class ServingConfig:
    max_batch: int = 16      # micro-batch size (batch dim of each jitted fn)
    min_bucket: int = 64     # smallest frame bucket
    max_bucket: int = 8192   # hard cap; longer utterances are truncated to
    #                          the largest power-of-two bucket <= this, so
    #                          truncation always lands on the bucket grid
    length_norm: bool = True


@dataclass
class RequestInfo:
    """Per-request validation outcome, returned alongside the i-vector
    (``extract(..., return_info=True)``). Nothing here is silent: the
    counters in ``IVectorExtractor.stats`` aggregate the same events."""
    n_frames: int = 0          # frames that actually entered extraction
    bucket: int = 0
    truncated: bool = False    # clipped at ServingConfig.max_bucket
    empty: bool = False        # zero valid frames -> zero i-vector
    nonfinite_frames: int = 0  # NaN/Inf frames masked out of the input


class IVectorExtractor:
    """One serving session: cached per-model precompute + per-bucket jits.

    >>> ex = IVectorExtractor.from_state(cfg, trained_state)
    >>> ivecs = ex.extract(list_of_[F_i, D]_arrays)   # [N, R] length-normed
    """

    def __init__(self, cfg: IVectorConfig, model: TV.TVModel,
                 ubm: U.FullGMM, serving: ServingConfig = ServingConfig()):
        self.cfg = cfg
        self.model = model
        self.ubm = ubm
        self.serving = serving
        self.bundle = None        # set by from_bundle (provenance access)
        # expensive per-model precompute, shared by every request: the
        # engine pack (diag preselection GMM + full-cov precisions) and
        # the TVM precompute (T^T Sigma^{-1} T)
        self._spec = EN.EngineSpec(
            n_components=cfg.n_components, top_k=cfg.posterior_top_k,
            floor=cfg.posterior_floor, rescore=cfg.rescore)
        self._pack = EN.pack_ubm(ubm)
        # packed-symmetric U (cfg.estep='packed', DESIGN.md §9) halves the
        # cached precompute's bytes; extraction itself runs the mean-only
        # posterior (no [B, R, R] covariance solve) via extract_ivectors
        self._tv_pre = TV.precompute(model, estep=cfg.estep)
        # one jitted fn PER rescore mode (jit specializes per input shape,
        # so each covers every bucket); the session starts at the config's
        # mode and demotes down engine.RESCORE_LADDER on kernel failure
        self.mode: str = cfg.rescore
        # truncation target: the largest ON-GRID bucket <= max_bucket —
        # a truncated request must reuse an existing jitted shape, not
        # compile a fresh off-bucket one (e.g. min=64, max=100: cap=64)
        self._cap = bucket_cap(serving.min_bucket, serving.max_bucket)
        self._fns: Dict[str, object] = {}
        # chaos hook (tests): modes whose device call raises, simulating
        # a kernel failure
        self._chaos_fail_modes: set = set()
        self._seen_buckets: set = set()
        self.stats = {"requests": 0, "batches": 0, "compiles": 0,
                      "real_frames": 0, "padded_frames": 0, "truncated": 0,
                      "empty": 0, "nonfinite_frames": 0,
                      "degradations": 0, "mode": self.mode}

    @classmethod
    def from_state(cls, cfg: IVectorConfig, state,
                   serving: ServingConfig = ServingConfig()
                   ) -> "IVectorExtractor":
        return cls(cfg, state.model, state.ubm, serving)

    @classmethod
    def from_bundle(cls, path, serving: ServingConfig = ServingConfig()
                    ) -> "IVectorExtractor":
        """Serving session from a saved artifact bundle (api/bundle.py):
        the train-once/serve-anywhere path. The bundle's own config drives
        the session, so the extraction is bit-identical to the in-memory
        state that saved it."""
        from repro.api.bundle import Bundle
        b = Bundle.load(path)
        ex = cls(b.cfg, b.model, b.ubm, serving)
        ex.bundle = b
        return ex

    # -- bucketing ----------------------------------------------------------

    def bucket_for(self, n_frames: int) -> int:
        return bucket_for(n_frames, self.serving.min_bucket, self._cap)

    def buckets(self) -> List[int]:
        return sorted(self._seen_buckets)

    # -- the jitted per-bucket extraction -----------------------------------

    def _make_fn(self, mode: str):
        """Jitted [B, bucket, D], [B, bucket] -> [B, R] for one rescore
        mode (zero rows where mask=0).

        The cached model/precompute pytrees come in as jit ARGUMENTS, not
        closure constants: constants would be re-embedded into every
        bucket-shape executable (hundreds of MB each at production scale),
        arguments share one device buffer across all buckets. The
        align->stats math is the engine's canonical chunk body — the same
        implementation the training stack streams through — and every
        mode computes the same statistics (fp-tolerance equal), so a
        mid-session demotion changes speed, not answers.
        """
        spec = replace(self._spec, rescore=mode)

        def fn(pack, model, tv_pre, feats, mask):
            cs = EN.chunk_body(spec, pack, feats, mask)
            st = ST.BWStats(cs.n, cs.f, None)
            if model.formulation == "standard":
                stc = ST.center(ST.BWStats(st.n, st.f, None), model.means)
                n_, f_ = stc.n, stc.f
            else:
                n_, f_ = st.n, st.f
            iv = TV.extract_ivectors(model, tv_pre, n_, f_,
                                     estep_dtype=self.cfg.estep_dtype)
            if self.serving.length_norm:
                iv = BK.length_norm(iv)
            # zero-occupancy padding rows extract the prior mean; blank
            return iv * jnp.any(mask > 0, axis=1)[:, None]

        return jax.jit(fn)

    def _run_batch(self, feats, mask) -> np.ndarray:
        """One device call at the session's current mode, demoting down
        the rescore ladder on failure instead of raising (DESIGN.md §13),
        with a warning naming the mode and the exception. Only a failure
        of the reference 'dense' path propagates."""
        while True:
            mode = self.mode
            try:
                if mode in self._chaos_fail_modes:
                    raise RuntimeError(
                        f"injected {mode}-kernel failure (chaos)")
                if mode not in self._fns:
                    self._fns[mode] = self._make_fn(mode)
                return np.asarray(self._fns[mode](
                    self._pack, self.model, self._tv_pre, feats, mask))
            except Exception as e:
                nxt = EN.degrade_rescore(mode)
                if nxt is None:
                    raise
                EN.warn_demotion(mode, nxt, e)
                self.mode = nxt
                self.stats["mode"] = nxt
                self.stats["degradations"] += 1

    # -- input validation ---------------------------------------------------

    def _validate(self, u: np.ndarray, D: int
                  ) -> Tuple[np.ndarray, np.ndarray, RequestInfo]:
        """One raw utterance -> (clean feats, valid-frame flags, info).
        Non-finite frames are zeroed AND masked out — masking is exactly
        inert (bit-identical stats; DESIGN.md §5) so a poisoned frame
        contributes nothing instead of flooding the batch with NaNs."""
        if u.ndim != 2 or u.shape[1] != D:
            raise ValueError(f"utterance must be [F, {D}], got {u.shape}")
        info = RequestInfo(n_frames=int(u.shape[0]))
        if u.shape[0] > self._cap:
            u = u[:self._cap]
            info.truncated = True
            info.n_frames = int(u.shape[0])
            self.stats["truncated"] += 1
        valid = np.isfinite(u).all(axis=1)
        bad = int(u.shape[0] - valid.sum())
        if bad:
            info.nonfinite_frames = bad
            self.stats["nonfinite_frames"] += bad
            u = np.where(valid[:, None], u, 0.0).astype(np.float32)
        if valid.sum() == 0:
            info.empty = True
            self.stats["empty"] += 1
        info.bucket = self.bucket_for(max(int(u.shape[0]), 1))
        return u, valid, info

    # -- public API ---------------------------------------------------------

    def extract(self, utterances: Sequence, return_info: bool = False):
        """Ragged [F_i, D] utterances -> [N, R] i-vectors (input order).
        With ``return_info`` also returns the per-request `RequestInfo`
        list (truncation/empty/non-finite flags)."""
        D = self.ubm.means.shape[1]
        R = self.model.rank
        B = self.serving.max_batch
        utts, valids, infos = [], [], []
        for raw in utterances:
            u, valid, info = self._validate(np.asarray(raw, np.float32), D)
            utts.append(u)
            valids.append(valid)
            infos.append(info)
        groups: Dict[int, List[int]] = {}
        for i, info in enumerate(infos):
            groups.setdefault(info.bucket, []).append(i)
        out = np.zeros((len(utts), R), np.float32)
        for bucket in sorted(groups):
            if bucket not in self._seen_buckets:
                self._seen_buckets.add(bucket)
                self.stats["compiles"] += 1
            idxs = groups[bucket]
            for s in range(0, len(idxs), B):
                chunk = idxs[s:s + B]
                feats = np.zeros((B, bucket, D), np.float32)
                mask = np.zeros((B, bucket), np.float32)
                for j, i in enumerate(chunk):
                    n = min(utts[i].shape[0], bucket)
                    feats[j, :n] = utts[i][:n]
                    mask[j, :n] = valids[i][:n].astype(np.float32)
                    self.stats["real_frames"] += n
                    self.stats["padded_frames"] += bucket - n
                out[chunk] = self._run_batch(
                    jnp.asarray(feats), jnp.asarray(mask))[:len(chunk)]
                self.stats["batches"] += 1
        self.stats["requests"] += len(utts)
        if return_info:
            return out, infos
        return out

    __call__ = extract

    # -- health / readiness -------------------------------------------------

    def health_check(self) -> Dict:
        """Readiness probe: extract a deterministic canary utterance
        through the SAME path as real traffic (validation, bucketing,
        degradation wrapper) and verify the result is finite and
        non-trivial. A broken fused kernel therefore demotes during the
        probe, before traffic arrives. Does not touch request stats."""
        D = self.ubm.means.shape[1]
        F = self.serving.min_bucket
        canary = np.asarray(
            np.sin(np.arange(F)[:, None] * 0.37
                   + np.arange(D)[None, :] * 1.13), np.float32)
        before = dict(self.stats)
        t0 = time.perf_counter()
        try:
            iv = self.extract([canary])
            latency = time.perf_counter() - t0
            norm = float(np.linalg.norm(iv[0]))
            ok = bool(np.isfinite(iv).all()) and norm > 0.0
            err = None
        except Exception as e:   # dense path failed too: not servable
            latency = time.perf_counter() - t0
            ok, norm, err = False, float("nan"), repr(e)
        # the canary is a probe, not traffic: restore request counters
        # (mode/degradations reflect what the probe learned and stay)
        for k in ("requests", "batches", "real_frames", "padded_frames"):
            self.stats[k] = before[k]
        return {"ok": ok, "mode": self.mode,
                "degradations": self.stats["degradations"],
                "latency_s": latency, "canary_norm": norm,
                "buckets_compiled": len(self._seen_buckets),
                "error": err}
