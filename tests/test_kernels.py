"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


@pytest.mark.parametrize("F,D,C,bf,bc", [
    (256, 8, 32, 128, 32),
    (512, 12, 64, 256, 64),
    (128, 20, 16, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_loglik(F, D, C, bf, bc, dtype):
    x = jax.random.normal(k(1), (F, D), dtype)
    const = jax.random.normal(k(2), (C,), jnp.float32)
    lin = jax.random.normal(k(3), (D, C), jnp.float32)
    A = jax.random.normal(k(4), (C, D, D)) * 0.3
    P = (jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)).reshape(C, D * D)
    want = ref.gmm_loglik(x.astype(jnp.float32), const, lin, P)
    with ops.use_pallas(True):
        got = ops.gmm_loglik(x, const, lin, P, block_f=bf, block_c=bc)
    tol = 2e-5 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("F,C,bf,bc", [
    (300, 32, 128, 32),    # ragged F (serving traffic)
    (256, 30, 128, 16),    # ragged C
    (193, 23, 64, 16),     # both ragged
])
def test_gmm_loglik_ragged_shapes(F, C, bf, bc):
    """The ops wrapper pads ragged F/C to block multiples and slices back —
    variable-length serving shapes must match the reference exactly."""
    D = 8
    x = jax.random.normal(k(11), (F, D))
    const = jax.random.normal(k(12), (C,), jnp.float32)
    lin = jax.random.normal(k(13), (D, C), jnp.float32)
    A = jax.random.normal(k(14), (C, D, D)) * 0.3
    P = (jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)).reshape(C, D * D)
    want = ref.gmm_loglik(x, const, lin, P)
    with ops.use_pallas(True):
        got = ops.gmm_loglik(x, const, lin, P, block_f=bf, block_c=bc)
    assert got.shape == (F, C)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _spd_precisions(key, C, D):
    const = jax.random.normal(jax.random.fold_in(key, 0), (C,), jnp.float32)
    lin = jax.random.normal(jax.random.fold_in(key, 1), (D, C), jnp.float32)
    A = jax.random.normal(jax.random.fold_in(key, 2), (C, D, D)) * 0.3
    P = (jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)).reshape(C, D * D)
    return const, lin, P


@pytest.mark.parametrize("F,D,C,K,bf", [
    (64, 8, 32, 5, 8),
    (128, 12, 64, 20, 16),
    (40, 6, 16, 16, 8),     # K == C: rescore everything
])
def test_gmm_rescore(F, D, C, K, bf):
    """Fused gather-and-rescore (interpret) == oracle == dense-then-gather."""
    x = jax.random.normal(k(30), (F, D))
    const, lin, P = _spd_precisions(k(31), C, D)
    sel = jax.random.randint(k(32), (F, K), 0, C)
    want = ref.gmm_rescore(x, sel, const, lin, P)
    dense_gather = jnp.take_along_axis(
        ref.gmm_loglik(x, const, lin, P), sel, axis=1)
    with ops.use_pallas(True):
        got = ops.gmm_rescore(x, sel, const, lin, P, block_f=bf)
    assert got.shape == (F, K)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, dense_gather, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("F,bf", [(37, 8), (5, 8), (61, 16)])
def test_gmm_rescore_ragged_frames(F, bf):
    """Ragged F (serving traffic) is padded to the frame-tile and sliced
    back; duplicate and boundary component ids are legal."""
    D, C, K = 7, 24, 6
    x = jax.random.normal(k(33), (F, D))
    const, lin, P = _spd_precisions(k(34), C, D)
    sel = jnp.concatenate([
        jnp.zeros((F, 2), jnp.int32),                    # duplicates
        jnp.full((F, 1), C - 1, jnp.int32),              # boundary
        jax.random.randint(k(35), (F, K - 3), 0, C),
    ], axis=1)
    want = ref.gmm_rescore(x, sel, const, lin, P)
    with ops.use_pallas(True):
        got = ops.gmm_rescore(x, sel, const, lin, P, block_f=bf)
    assert got.shape == (F, K)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_gmm_rescore_cached_pack_matches():
    """The serving-cached packed gather matrix (``ref.rescore_pack``) is
    just a layout change: same result as packing on the fly."""
    F, D, C, K = 32, 6, 16, 4
    x = jax.random.normal(k(36), (F, D))
    const, lin, P = _spd_precisions(k(37), C, D)
    sel = jax.random.randint(k(38), (F, K), 0, C)
    pack = ref.rescore_pack(const, lin, P)
    assert pack.shape == (C, 1 + D + D * D)
    with ops.use_pallas(True):
        a = ops.gmm_rescore(x, sel, const, lin, P)
        b = ops.gmm_rescore(x, sel, const, lin, P, pack=pack)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Fused single-kernel alignment: preselect + top-K + gather + rescore
# (DESIGN.md §12) — interpret mode vs the two-phase reference
# ---------------------------------------------------------------------------


def _fused_inputs(key, C, D, F):
    const, lin, P = _spd_precisions(key, C, D)
    dconst = jax.random.normal(jax.random.fold_in(key, 10), (C,))
    dlin = jax.random.normal(jax.random.fold_in(key, 11), (D, C))
    dquad = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 12),
                                       (D, C))) - 0.1
    x = jax.random.normal(jax.random.fold_in(key, 13), (F, D))
    A2 = ref.align_pack(const, lin, P)
    return x, dconst, dlin, dquad, (const, lin, P), A2


@pytest.mark.parametrize("C,D,K,F,bf,depth", [
    (32, 5, 4, 64, 8, 2),
    (64, 12, 8, 64, 16, 4),
    (37, 7, 5, 48, 8, 8),      # ragged C, deep ring
    (16, 3, 16, 24, 8, 4),     # K == C
    (24, 6, 3, 40, 8, 1),      # depth 1: fully serialised DMAs
])
def test_gmm_align_fused_kernel(C, D, K, F, bf, depth):
    """The fused Pallas kernel (interpret) == diag preselect + lax.top_k
    + dense-then-gather, ids and logliks both, across tile schedules
    including the autotuner's candidate block sizes."""
    x, dconst, dlin, dquad, (const, lin, P), A2 = _fused_inputs(
        k(50 + C), C, D, F)
    from repro.kernels import gmm_align as GA
    E2 = A2.shape[1]
    sexp = ops.align_expand_operand(D, E2)
    ll, sel = GA.gmm_align(x, dconst[None, :], dlin, dquad, sexp, A2,
                           top_k=K, block_f=bf, dma_depth=depth,
                           interpret=True)
    scores = dconst[None, :] + x @ dlin + (x * x) @ dquad
    _, want_sel = jax.lax.top_k(scores, K)
    assert (np.sort(np.asarray(sel), 1)
            == np.sort(np.asarray(want_sel), 1)).all()
    want_ll = jnp.take_along_axis(ref.gmm_loglik(x, const, lin, P),
                                  sel, axis=1)
    np.testing.assert_allclose(np.asarray(ll), np.asarray(want_ll),
                               rtol=3e-5, atol=3e-5)


def test_gmm_align_wrapper_autotuned_configs():
    """`ops.gmm_align` under the Pallas flag == the jnp path, at every
    candidate block config the autotuner sweeps for this cell (the
    schedule must change the schedule, never the numbers)."""
    from repro.analysis.roofline import autotune_align
    C, D, K, F = 48, 8, 6, 32
    x, dconst, dlin, dquad, _, A2 = _fused_inputs(k(70), C, D, F)
    ll_ref, sel_ref_ = ops.gmm_align(x, dconst, dlin, dquad, A2, top_k=K)
    tune = autotune_align(C, K, D, device_kind="cpu", frames=F)
    swept = sorted({(bf, dp) for _, bf, dp, _ in tune.candidates
                    if bf <= F})[:4]
    for bf, dp in swept:
        with ops.use_pallas(True):
            ll, sel = ops.gmm_align(x, dconst, dlin, dquad, A2, top_k=K,
                                    block_f=bf, dma_depth=dp)
        np.testing.assert_array_equal(np.asarray(sel),
                                      np.asarray(sel_ref_))
        np.testing.assert_allclose(np.asarray(ll), np.asarray(ll_ref),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("F,D,C", [(256, 8, 32), (512, 16, 64)])
def test_bw_stats(F, D, C):
    x = jax.random.normal(k(5), (F, D))
    g = jax.nn.softmax(jax.random.normal(k(6), (F, C)))
    wn, wf, wS = ref.bw_stats(g, x)
    with ops.use_pallas(True):
        gn, gf, gS = ops.bw_stats(g, x, block_f=128, block_c=16)
    np.testing.assert_allclose(gn, wn, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gf, wf, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gS, wS, rtol=1e-5, atol=1e-4)
    # invariant: sum_c n_c == number of frames (posteriors sum to 1)
    np.testing.assert_allclose(jnp.sum(gn), F, rtol=1e-5)


@pytest.mark.parametrize("N,D,C,K", [(64, 5, 16, 4), (200, 8, 32, 8)])
def test_second_moments_grouped(N, D, C, K):
    """The grouped-matmul second-order moments (the TPU path) == the
    scatter-add reference; ids repeat within a frame and some components
    receive no frame at all."""
    x = jax.random.normal(k(50), (N, D))
    sel = jax.random.randint(k(51), (N, K), 0, C - 2)
    g = jax.random.uniform(k(52), (N, K))
    want = ref.second_moments(x, g, sel, C)
    with ops.use_pallas(True):
        got = ops.second_moments(x, g, sel, C)
    assert got.shape == (C, D * D)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got)[C - 2:], 0.0)


@pytest.mark.parametrize("U,C,R", [(32, 16, 12), (64, 64, 24)])
def test_tvm_estep_l_packed(U, C, R):
    """Packed L-assembly kernel == dense einsum after unpacking."""
    n = jax.random.uniform(k(7), (U, C))
    M = jax.random.normal(k(8), (C, R, R))
    M = M + jnp.swapaxes(M, 1, 2)
    Up = ref.pack_symmetric(M)
    want_dense = jnp.einsum("uc,crs->urs", n, M)
    with ops.use_pallas(True):
        got_packed = ops.tvm_estep_l(n, Up, block_u=16, block_p=64,
                                     block_c=16)
    got_dense = ref.unpack_symmetric(got_packed, R)
    np.testing.assert_allclose(got_dense, want_dense, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("U,C,R", [(32, 16, 12), (64, 64, 24)])
def test_tvm_estep_a_packed(U, C, R):
    """Packed A-accumulation kernel == dense einsum after unpacking."""
    n = jax.random.uniform(k(40), (U, C))
    M = jax.random.normal(k(41), (U, R, R))
    M = M + jnp.swapaxes(M, 1, 2)
    PPp = ref.pack_symmetric(M)
    want_dense = jnp.einsum("uc,urs->crs", n, M)
    with ops.use_pallas(True):
        got_packed = ops.tvm_estep_a(n, PPp, block_u=16, block_p=64,
                                     block_c=16)
    got_dense = ref.unpack_symmetric(got_packed, R)
    np.testing.assert_allclose(got_dense, want_dense, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,S,H,KVH,hd,bq,bk", [
    (2, 128, 4, 2, 32, 64, 64),
    (1, 256, 8, 1, 16, 64, 128),   # MQA
    (2, 64, 2, 2, 64, 32, 32),     # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, KVH, hd, bq, bk, dtype):
    q = jax.random.normal(k(9), (B, S, H, hd), dtype)
    kk = jax.random.normal(k(10), (B, S, KVH, hd), dtype)
    v = jax.random.normal(k(11), (B, S, KVH, hd), dtype)
    want = ref.flash_attention(q.astype(jnp.float32),
                               kk.astype(jnp.float32),
                               v.astype(jnp.float32))
    with ops.use_pallas(True):
        got = ops.flash_attention(q, kk, v, block_q=bq, block_k=bk)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("R", [1, 2, 5, 9, 16])   # odd + even P tilings
def test_pack_unpack_roundtrip(R):
    M = jax.random.normal(k(12), (5, R, R))
    M = M + jnp.swapaxes(M, 1, 2)
    Mp = ref.pack_symmetric(M)
    assert Mp.shape == (5, R * (R + 1) // 2)
    np.testing.assert_allclose(
        ref.unpack_symmetric(Mp, R), M, rtol=1e-6)
    # unpack is a pure gather: EXACTLY symmetric for arbitrary vectors
    v = jax.random.normal(k(13), (3, R * (R + 1) // 2))
    out = ref.unpack_symmetric(v, R)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.swapaxes(out, -1, -2)))


@pytest.mark.parametrize("B,T,di,ds,bt,bd", [(2, 64, 32, 8, 32, 16),
                                             (1, 128, 16, 4, 64, 16)])
def test_selective_scan_kernel(B, T, di, ds, bt, bd):
    from repro.kernels.selective_scan import selective_scan
    dt = jax.nn.softplus(jax.random.normal(k(20), (B, T, di)))
    dx = jax.random.normal(k(21), (B, T, di))
    A = -jnp.exp(jax.random.normal(k(22), (di, ds)) * 0.2)
    Bc = jax.random.normal(k(23), (B, T, ds))
    Cc = jax.random.normal(k(24), (B, T, ds))
    got = selective_scan(dt, dx, A, Bc, Cc, block_t=bt, block_d=bd,
                         interpret=True)
    # sequential oracle
    h = jnp.zeros((B, di, ds))
    ys = []
    for t in range(T):
        a = jnp.exp(dt[:, t, :, None] * A[None])
        h = a * h + dx[:, t, :, None] * Bc[:, t, None, :]
        ys.append(jnp.einsum("bds,bs->bd", h, Cc[:, t]))
    want = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_kernels_selected_by_platform(monkeypatch):
    """The backend turns the kernels on (compiled, never interpreted);
    ``use_pallas`` only steers tests."""
    assert not ops._kernels_on()                 # CPU: jnp references
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._kernels_on() and not ops._INTERPRET.get()
    with ops.use_pallas(False):
        assert not ops._kernels_on()
    with ops.use_pallas(True):
        assert ops._kernels_on() and ops._INTERPRET.get()


@pytest.mark.parametrize("module,fn", [
    ("gmm_rescore", "gmm_rescore"), ("gmm_loglik", "gmm_loglik"),
    ("tvm_estep", "tvm_estep_l"), ("tvm_estep", "tvm_estep_a"),
    ("gmm_align", "gmm_align"), ("bw_stats", "bw_stats"),
    ("flash_attention", "flash_attention"),
    ("selective_scan", "selective_scan"),
])
def test_kernels_compile_by_default(module, fn):
    """No kernel defaults to interpret mode."""
    import importlib
    import inspect
    f = getattr(importlib.import_module(f"repro.kernels.{module}"), fn)
    assert inspect.signature(f).parameters["interpret"].default is False
