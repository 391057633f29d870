"""Seeded inputs of every cell, made on the device.

A copy of the repository's synthetic speech generator
(``repro.data.speech.make_generator`` / ``build_dataset``): frames are
drawn from a full-covariance GMM whose component means are shifted per
speaker by a low-rank speaker subspace and per utterance by a smaller
channel subspace. The copy differs in one way: it is vectorised over
utterances, so one jitted program makes a whole corpus for any seed,
where the original compiles one sampler per speaker. For the same seed
and sizes it draws the same random numbers, so it gives the original's
frames to float32 rounding.

The generator's own GMM (uniform weights, its means and covariances) is
the cells' UBM, and the starting total-variability model follows the
program's initialisation (random T, column 0 the UBM means over the
prior offset, prior offset on the first coordinate). Nothing here comes
from the program: the benchmark hands these arrays to it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


class Generator(NamedTuple):
    """Sizes of the synthetic speech generator (one configuration's)."""
    n_components: int
    feat_dim: int
    n_speakers: int
    utts_per_speaker: int
    speaker_rank: int
    channel_rank: int
    speaker_scale: float
    channel_scale: float


class Inputs(NamedTuple):
    """The generator's GMM (the UBM) and the starting TV model."""
    weights: jax.Array     # [C]
    means: jax.Array       # [C, D]
    covs: jax.Array        # [C, D, D]
    T: jax.Array           # [C, D, R]
    prior: jax.Array       # [R]


def seed_key(seed: int) -> jax.Array:
    """PRNG key of a whole-number seed of up to 64 bits. ``PRNGKey``
    keeps only the low 32 bits of a Python int, so the high word is
    folded in; below 2**32 this is ``PRNGKey(seed)`` itself."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return key if seed >> 32 == 0 else jax.random.fold_in(key, seed >> 32)


def _params(key, g: Generator):
    k_mu, k_sp, k_ch, k_spk = jax.random.split(key, 4)
    C, D = g.n_components, g.feat_dim
    means = jax.random.normal(k_mu, (C, D), f32) * 2.0
    A = jax.random.normal(jax.random.fold_in(k_mu, 1), (C, D, D), f32) * 0.3
    covs = (jnp.einsum("cij,ckj->cik", A, A, precision=HI)
            + 0.5 * jnp.eye(D, dtype=f32)[None])
    chols = jnp.linalg.cholesky(covs)
    V = (jax.random.normal(k_sp, (C, D, g.speaker_rank), f32)
         * g.speaker_scale / np.sqrt(g.speaker_rank))
    Wc = (jax.random.normal(k_ch, (C, D, g.channel_rank), f32)
          * g.channel_scale / np.sqrt(g.channel_rank))
    spk = jax.random.normal(k_spk, (g.n_speakers, g.speaker_rank), f32)
    return means, covs, chols, V, Wc, spk


def _utterance(g: Generator, frames: int, params, base, index):
    """Utterance ``index`` (speaker-major order): the original's
    ``sample_utterance(speaker, fold_in(fold_in(base, s), u))``."""
    means, _, chols, V, Wc, spk = params
    s = index // g.utts_per_speaker
    u = index % g.utts_per_speaker
    k1, k2, k3 = jax.random.split(
        jax.random.fold_in(jax.random.fold_in(base, s), u), 3)
    ch = jax.random.normal(k1, (g.channel_rank,), f32)
    mu = (means + jnp.einsum("cdr,r->cd", V, spk[s], precision=HI)
          + jnp.einsum("cdr,r->cd", Wc, ch, precision=HI))
    logw = jnp.full((g.n_components,), -np.log(g.n_components), f32)
    comp = jax.random.categorical(k2, logw[None].repeat(frames, 0))
    eps = jax.random.normal(k3, (frames, g.feat_dim), f32)
    return mu[comp] + jnp.einsum("fij,fj->fi", chols[comp], eps,
                                 precision=HI)


def _base_key(seed: int) -> jax.Array:
    # the original draws utterances from PRNGKey(seed + 1)
    return seed_key(seed + 1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _make_utterances(g: Generator, frames: int, count: int, batch: int,
                     key, base, first):
    params = _params(key, g)
    idx = first + jnp.arange(count)
    fn = functools.partial(_utterance, g, frames, params, base)
    return jax.lax.map(fn, idx, batch_size=min(batch, count))


def utterances(g: Generator, seed: int, frames: int, count: int,
               first: int = 0, batch: int = 16) -> jax.Array:
    """Utterances ``first .. first + count - 1`` of the seed's corpus,
    [count, frames, D] on the device; ``batch`` utterances are drawn at
    a time, which bounds the per-frame covariance gathers."""
    return _make_utterances(g, frames, count, batch, seed_key(seed),
                            _base_key(seed), jnp.int32(first))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_inputs(g: Generator, rank: int, key, t_key, p) -> Inputs:
    # p is traced: a constant divisor would be folded into a multiply by
    # its reciprocal, which rounds column 0 differently from the
    # program's initialisation
    means, covs, _, _, _, _ = _params(key, g)
    C, D = g.n_components, g.feat_dim
    T = jax.random.normal(t_key, (C, D, rank), f32)
    T = T.at[:, :, 0].set(means / p)
    prior = jnp.zeros((rank,), f32).at[0].set(p)
    return Inputs(jnp.full((C,), 1.0 / C, f32), means, covs, T, prior)


def tv_key(seed: int) -> jax.Array:
    """Key of the starting T (the program's ``init_model`` key)."""
    return jax.random.fold_in(seed_key(seed), 0x7456)


def inputs(g: Generator, seed: int, rank: int,
           prior_offset: float) -> Inputs:
    """The UBM (the generator's GMM) and the starting augmented TV
    model of rank ``rank`` for the seed, in one jitted call."""
    return _make_inputs(g, rank, seed_key(seed), tv_key(seed),
                        jnp.float32(prior_offset))


def generator(config: dict, n_utterances: int,
              utts_per_speaker: int) -> Generator:
    """The generator of a configuration file's ``generator`` block for a
    corpus of ``n_utterances`` (speakers of ``utts_per_speaker`` each)."""
    gen = config["generator"]
    return Generator(
        n_components=int(config["n_components"]),
        feat_dim=int(config["feat_dim"]),
        n_speakers=-(-n_utterances // utts_per_speaker),
        utts_per_speaker=int(utts_per_speaker),
        speaker_rank=int(gen["speaker_rank"]),
        channel_rank=int(gen["channel_rank"]),
        speaker_scale=float(gen["speaker_scale"]),
        channel_scale=float(gen["channel_scale"]))
