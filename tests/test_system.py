"""End-to-end behaviour tests for the paper's system + framework glue."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import analyze_hlo
from repro.configs import get_config
from repro.configs.ivector_tvm import SMOKE as IV_SMOKE
from repro.core.pipeline import evaluate_state, prepare, run_variant
from repro.data.speech import SpeechDataConfig
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.models import api


@pytest.fixture(scope="module")
def ivec_setup():
    cfg = IV_SMOKE.with_overrides(feat_dim=10, n_components=16,
                                  ivector_dim=16, posterior_top_k=8,
                                  lda_dim=10)
    dc = SpeechDataConfig(feat_dim=10, n_components=12, n_speakers=20,
                          utts_per_speaker=6, frames_per_utt=64,
                          speaker_rank=8, channel_rank=4,
                          speaker_scale=0.5, channel_scale=1.1)
    feats, labels, ubm = prepare(cfg, dc)
    return cfg, feats, labels, ubm


def test_speaker_verification_end_to_end(ivec_setup):
    """The full paper pipeline yields a usable verifier (EER << 0.5) and
    improves with EM iterations."""
    cfg, feats, labels, ubm = ivec_setup
    r = run_variant(cfg, feats, labels, ubm, n_iters=4, eval_every=4)
    (it, e_final) = r["curve"][-1]
    assert e_final < 0.3, r["curve"]


def test_paper_claim_min_divergence_helps(ivec_setup):
    """Paper Fig. 2: minimum-divergence re-estimation reduces EER."""
    cfg, feats, labels, ubm = ivec_setup
    e_md = run_variant(cfg, feats, labels, ubm, 4,
                       eval_every=4)["curve"][-1][1]
    e_no = run_variant(cfg.with_overrides(min_divergence=False), feats,
                       labels, ubm, 4, eval_every=4)["curve"][-1][1]
    # averaged claims need the fig2 benchmark's ensemble; here we assert the
    # variant at least does not catastrophically regress
    assert e_md <= e_no + 0.05, (e_md, e_no)


def test_lm_training_loss_decreases():
    from repro.optim import AdamWConfig
    cfg = get_config("stablelm-1.6b", smoke=True)
    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=64, global_batch=8, noise=0.2,
        active_vocab=64))
    state = api.init_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(api.make_train_step(
        cfg, AdamWConfig(lr=1e-3, warmup_steps=5)), donate_argnums=0)
    losses = []
    for _ in range(30):
        batch = jax.tree.map(jnp.asarray, pipe.next())
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses[::6]


def test_hlo_walker_counts_trip_counts():
    """The roofline walker multiplies scanned-layer flops by trip count."""
    def f(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h
    x = jnp.ones((64, 64))
    w = jnp.ones((9, 64, 64))
    compiled = jax.jit(f).lower(x, w).compile()
    r = analyze_hlo(compiled.as_text())
    want = 2 * 64 * 64 * 64 * 9  # 9 iterations of a 64^3 matmul
    assert abs(r["flops"] - want) / want < 0.05, r["flops"]
    raw = compiled.cost_analysis()
    if isinstance(raw, (list, tuple)):   # older jax returns [dict]
        raw = raw[0]
    assert raw["flops"] < r["flops"] / 4  # XLA's counter misses trip count


def test_roofline_report_fields():
    import json
    from pathlib import Path
    f = Path("experiments/dryrun/stablelm-1.6b__train_4k__single.json")
    if not f.exists():
        pytest.skip("dry-run artifacts not generated yet")
    row = json.loads(f.read_text())
    assert row["status"] == "ok"
    for k in ("t_compute_s", "t_memory_s", "t_collective_s", "dominant",
              "useful_flops_ratio", "roofline_fraction"):
        assert k in row


def test_roofline_table_keyed_by_device_kind():
    """Peaks and tuning constants come from one table keyed by jax's
    device_kind; an unknown device is an error, never a default."""
    from repro.analysis import roofline
    v5e = roofline.hardware("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                          16e9)
    assert roofline.local_hardware().name == jax.devices()[0].device_kind
    with pytest.raises(ValueError, match="no roofline entry"):
        roofline.hardware("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no roofline entry"):
        roofline.autotune_align(2048, 20, 72, device_kind="gpu")


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed <repo>/.jax_cache."""
    from pathlib import Path
    from repro.launch import cache
    was = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cache.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            repo = Path(__file__).resolve().parents[1]
            assert got == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
