"""Serving cells: open-loop i-vector extraction through the program's
admission queue.

The traffic file fixes the offered load: a rate and a length
distribution. Every seed gets the same work: the same number of
requests, the same set of lengths and the same set of inter-arrival
gaps (quantiles of the lognormal and of the exponential), each set in
an order drawn from the seed.

Requests carry frames from a pool of seeded utterances: request i takes
the first ``length_i`` frames of a pool utterance drawn from the seed.

The server loop is one thread: it submits every request that is due to
``serving.guard.AdmissionQueue`` over ``IVectorExtractor`` and then
drains the queue once, or sleeps until the next request is due. A
request's latency runs from when it was due to when the drain that
served it returned its i-vector to the host. Requests are admitted for
``--seconds``; the loop then serves what is left, for a minute at most.
A request never served counts as missing: its latency runs to the end
of that minute.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Dict, Optional

import jax
import numpy as np

from bench import data as BD
from bench import reference as REF
from bench.drivers.train import ivector_config

GRACE_S = 60.0


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def schedule(traffic: dict, seed: int, seconds: float):
    """(due offsets [N] in seconds, lengths [N] in frames) of a window
    of ``seconds``: a fixed set of gaps and lengths, shuffled by seed."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds
                                                          / gaps.sum())
    ln = traffic["lengths"]
    z = np.array([NormalDist().inv_cdf(v) for v in q])
    lengths = np.clip(np.round(float(ln["median"])
                               * np.exp(float(ln["sigma"]) * z)),
                      int(ln["min"]), int(ln["max"])).astype(np.int64)
    return t, rng.permutation(lengths)


@dataclass
class State:
    cell: object
    cfg: object
    inputs: BD.Inputs
    ex: object
    pool: np.ndarray                   # [P, max frames, D] on the host
    due: np.ndarray                    # [N]
    lengths: np.ndarray                # [N]
    which: np.ndarray                  # [N] pool utterance of each request
    seed: int
    ivecs: Dict[int, np.ndarray] = field(default_factory=dict)
    latency: Optional[np.ndarray] = None
    counters: dict = field(default_factory=dict)

    def request(self, i: int) -> np.ndarray:
        return self.pool[self.which[i], :self.lengths[i]]


def shapes(state: State) -> dict:
    c = state.cell.config
    return {"C": int(c["n_components"]), "D": int(c["feat_dim"]),
            "R": int(c["ivector_dim"]), "K": int(c["posterior_top_k"])}


def prepare(cell, seed: int, seconds: float, log) -> State:
    from repro.core import tvm as TV
    from repro.core import ubm as UB
    from repro.serving import IVectorExtractor, ServingConfig
    c, traffic = cell.config, cell.traffic
    cfg = ivector_config(c, traffic)
    due, lengths = schedule(traffic, seed, seconds)
    P = int(traffic["pool_utterances"])
    hi = int(traffic["lengths"]["max"])
    gen = BD.generator(c, P, int(traffic["utts_per_speaker"]))
    with _span("bench.make_inputs"):
        inp = BD.inputs(gen, seed, cfg.ivector_dim, cfg.prior_offset)
        pool = np.asarray(BD.utterances(gen, seed, hi, P))
    which = np.random.default_rng([seed, 1]).integers(0, P, size=len(due))
    sv = traffic["serving"]
    model = TV.TVModel(T=inp.T, Sigma=inp.covs, prior=inp.prior,
                       means=inp.means, formulation=cfg.formulation)
    ex = IVectorExtractor(cfg, model, UB.FullGMM(inp.weights, inp.means,
                                                 inp.covs),
                          ServingConfig(max_batch=int(sv["max_batch"]),
                                        min_bucket=int(sv["min_bucket"]),
                                        max_bucket=int(sv["max_bucket"])))
    state = State(cell, cfg, inp, ex, pool, due, lengths, which, seed)
    buckets = sorted({ex.bucket_for(int(n)) for n in lengths})
    log(f"{len(due)} requests over {seconds}s, lengths "
        f"{int(lengths.min())}-{int(lengths.max())} (mean "
        f"{lengths.mean():.1f}), buckets {buckets}")
    with _span("bench.warm_up"):
        for b in buckets:
            ex.extract([pool[0, :min(b, hi)]] * ex.serving.max_batch)
        _serve(state, np.zeros(1), np.array([0]))
    state.ivecs = {}
    return state


def _serve(state: State, due: np.ndarray, idx: np.ndarray):
    """Open-loop server loop over requests ``idx`` due at ``due``
    (offsets from now); returns (latency [n], submit lag [n])."""
    from repro.serving.guard import AdmissionQueue
    n = len(idx)
    q = AdmissionQueue(state.ex, max_pending=n + 1, default_timeout=1e12)
    rid_to = {}
    done = np.full(n, np.nan)
    lag = np.zeros(n)
    t0 = time.perf_counter()
    at = t0 + due
    nxt = 0
    while True:
        now = time.perf_counter()
        if now - t0 > due[-1] + GRACE_S:
            break
        with _span("bench.submit"):
            while nxt < n and at[nxt] <= now:
                rid_to[q.submit(state.request(int(idx[nxt])))] = nxt
                lag[nxt] = now - at[nxt]
                nxt += 1
        if len(q):
            with _span("bench.drain"):
                res = q.drain()
            t = time.perf_counter()
            for rid, r in res.items():
                j = rid_to.pop(rid)
                if r.ivector is not None:
                    done[j] = t
                    state.ivecs[int(idx[j])] = r.ivector
        elif nxt < n:
            with _span("bench.wait_arrival"):
                time.sleep(max(0.0, at[nxt] - time.perf_counter()))
        else:
            break
    end = time.perf_counter()
    lat = np.where(np.isnan(done), end, done) - at
    return lat, lag


def _counters(ex) -> dict:
    return {k: ex.stats[k] for k in ("requests", "batches", "real_frames",
                                     "padded_frames", "compiles")}


def _run(state: State, log) -> dict:
    before = _counters(state.ex)
    lat, lag = _serve(state, state.due, np.arange(len(state.due)))
    after = _counters(state.ex)
    state.counters = {k: after[k] - before[k] for k in after}
    state.latency = lat
    served = len(state.ivecs)
    log(f"generator lag (s): p50 {np.percentile(lag, 50):.6f} p95 "
        f"{np.percentile(lag, 95):.6f} max {lag.max():.6f}")
    log(f"served {served} of {len(lat)}; extractor {state.counters}")
    return {"attempted": len(lat), "served": served}


def window(state: State, seconds: float, log) -> dict:
    out = _run(state, log)
    ms = state.latency * 1e3
    out.update(extract_p50_ms=float(np.percentile(ms, 50)),
               extract_p95_ms=float(np.percentile(ms, 95)))
    return out


def traced_window(state: State, log) -> dict:
    out = _run(state, log)
    return {**out, **state.counters}


def release(state: State):
    state.ex = None
    gc.collect()


def sample(state: State) -> np.ndarray:
    """Requests compared with the reference: the longest and a seeded
    draw of the rest, ``check_requests`` in all."""
    n = len(state.due)
    k = min(int(state.cell.traffic["check_requests"]), n)
    longest = int(np.argmax(state.lengths))
    rest = np.setdiff1d(np.arange(n), [longest])
    pick = np.random.default_rng([state.seed, 2]).choice(
        rest, size=k - 1, replace=False)
    return np.concatenate([[longest], np.sort(pick)]).astype(np.int64)


def reference_ivectors(state: State, idx: np.ndarray,
                       prec=REF.HIGHEST) -> np.ndarray:
    c, inp = state.cell.config, state.inputs
    hi = state.pool.shape[1]
    feats = np.zeros((len(idx), hi, state.pool.shape[2]), np.float32)
    mask = np.zeros((len(idx), hi), np.float32)
    for j, i in enumerate(idx):
        n = int(state.lengths[i])
        feats[j, :n] = state.request(int(i))
        mask[j, :n] = 1.0
    return REF.extract(REF.UBM(inp.weights, inp.means, inp.covs),
                       REF.Model(inp.T, inp.covs, inp.prior), feats, mask,
                       top_k=int(c["posterior_top_k"]),
                       floor=float(c["posterior_floor"]), prec=prec)


def numbers(state: State, idx: np.ndarray, ref: np.ndarray) -> dict:
    """The widest distance between a served i-vector and the
    reference's, both of unit length (2 where one was never served)."""
    gaps = [float(np.linalg.norm(state.ivecs[int(i)] - r))
            if int(i) in state.ivecs else 2.0 for i, r in zip(idx, ref)]
    return {"ivector_gap": max(gaps)}


def check(state: State, log) -> tuple:
    idx = sample(state)
    t0 = time.perf_counter()
    ref = reference_ivectors(state, idx)
    log(f"reference of {len(idx)} requests {time.perf_counter() - t0:.3f}s")
    failed = len(state.due) - len(state.ivecs)
    return numbers(state, idx, ref), failed
