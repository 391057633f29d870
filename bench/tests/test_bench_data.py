"""bench/data.py against the repository's speech generator and the
program's model initialisation, for the same seed."""
import jax
import numpy as np
import pytest

from bench import data as BD

GEN = BD.Generator(n_components=8, feat_dim=6, n_speakers=3,
                   utts_per_speaker=4, speaker_rank=4, channel_rank=2,
                   speaker_scale=1.6, channel_scale=0.6)


def _speech_config(seed):
    from repro.data.speech import SpeechDataConfig
    return SpeechDataConfig(feat_dim=6, n_components=8, n_speakers=3,
                            utts_per_speaker=4, frames_per_utt=10,
                            speaker_rank=4, channel_rank=2, seed=seed)


@pytest.mark.parametrize("seed", [0, 7])
def test_vectorised_corpus_matches_build_dataset(seed):
    from repro.data.speech import build_dataset
    want, _ = build_dataset(_speech_config(seed))
    got = BD.utterances(GEN, seed, 10, 12, batch=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a_slice_of_the_corpus_is_the_same_utterances():
    whole = np.asarray(BD.utterances(GEN, 7, 10, 12))
    part = np.asarray(BD.utterances(GEN, 7, 10, 4, first=5))
    np.testing.assert_allclose(part, whole[5:9], rtol=1e-6, atol=1e-6)


def test_inputs_are_the_generator_gmm_and_the_program_initial_model():
    from repro.core import tvm as TV
    from repro.data.speech import make_generator
    gen, _ = make_generator(_speech_config(7))
    inp = BD.inputs(GEN, 7, 5, 100.0)
    np.testing.assert_allclose(np.asarray(inp.means),
                               np.asarray(gen["means"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inp.covs), np.asarray(gen["covs"]),
                               rtol=1e-5, atol=1e-6)
    assert np.allclose(np.asarray(inp.weights), 1.0 / 8)
    m = TV.init_model(BD.tv_key(7), inp.means, inp.covs, 5, "augmented",
                      100.0)
    assert np.array_equal(np.asarray(m.T), np.asarray(inp.T))
    assert np.array_equal(np.asarray(m.prior), np.asarray(inp.prior))


def test_seed_keys_keep_all_64_bits():
    assert np.array_equal(np.asarray(BD.seed_key(5)),
                          np.asarray(jax.random.PRNGKey(5)))
    a = np.asarray(BD.seed_key(2 ** 31 + 5))
    b = np.asarray(BD.seed_key(2 ** 32 + 2 ** 31 + 5))
    assert not np.array_equal(a, b)
