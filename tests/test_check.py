"""Static-analysis suite tests (DESIGN.md §15): golden fixtures that
each trip exactly their intended rule, the clean-repo gate, and the
numerics regressions the new rules enforce (near-singular SPD solves,
bf16-contraction f32 accumulation)."""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.check import (check_jaxpr, check_kernel, check_source,
                                  run_all)
from repro.analysis.check.cli import report_json
from repro.core import backend, tvm, ubm
from repro.kernels import registry

f32 = jnp.float32
REPO = pathlib.Path(__file__).resolve().parents[1]


def _ids(findings, unsuppressed_only=True):
    return sorted(f.rule_id for f in findings
                  if not (unsuppressed_only and f.suppressed))


# ---------------------------------------------------------------------------
# Pass 1 golden fixtures — jaxpr rules
# ---------------------------------------------------------------------------


class TestJaxprRules:
    def test_num001_bf16_dot_without_preferred(self):
        a = jnp.zeros((8, 16), jnp.bfloat16)
        b = jnp.zeros((16, 4), jnp.bfloat16)
        found = check_jaxpr(lambda x, y: jnp.dot(x, y), a, b)
        assert _ids(found) == ["NUM001"]

    def test_num001_mixed_promotion_is_clean(self):
        # mixed bf16 x f32: jnp's promotion pins preferred=f32 on the
        # dot itself, so accumulation is already f32 — no finding
        a = jnp.zeros((8, 16), jnp.bfloat16)
        b = jnp.zeros((16, 4), f32)
        found = check_jaxpr(lambda x, y: jnp.dot(x, y), a, b)
        assert _ids(found) == []

    def test_num001_downcast_before_dot(self):
        # the harmful mixed-precision idiom: f32 inputs explicitly cast
        # to bf16 at the contraction without pinning f32 accumulation
        a = jnp.zeros((8, 16), f32)
        b = jnp.zeros((16, 4), f32)
        found = check_jaxpr(
            lambda x, y: jnp.dot(x.astype(jnp.bfloat16),
                                 y.astype(jnp.bfloat16)), a, b)
        assert "NUM001" in _ids(found)

    def test_num001_clean_with_preferred(self):
        a = jnp.zeros((8, 16), jnp.bfloat16)
        b = jnp.zeros((16, 4), jnp.bfloat16)
        found = check_jaxpr(
            lambda x, y: jnp.dot(x, y, preferred_element_type=f32), a, b)
        assert _ids(found) == []

    def test_num002_inv(self):
        m = jnp.eye(4) * 2.0
        found = check_jaxpr(jnp.linalg.inv, m)
        assert "NUM002" in _ids(found)

    def test_num002_solve_and_slogdet(self):
        m = jnp.eye(4) * 2.0
        v = jnp.ones((4,))
        assert "NUM002" in _ids(check_jaxpr(jnp.linalg.solve, m, v))
        assert "NUM002" in _ids(check_jaxpr(
            lambda x: jnp.linalg.slogdet(x)[1], m))

    def test_num002_cholesky_sanctioned(self):
        m = jnp.eye(4) * 2.0
        v = jnp.ones((4, 1))
        found = check_jaxpr(
            lambda a, b: jax.scipy.linalg.cho_solve(
                (jnp.linalg.cholesky(a), True), b), m, v)
        assert _ids(found) == []

    def test_num003_unmasked_frame_mean(self):
        F = 97
        x = jnp.zeros((F, 6))
        m = jnp.ones((F,))
        found = check_jaxpr(lambda feats, mask: jnp.mean(feats, axis=0),
                            x, m, input_roles=("feats", "mask"),
                            frame_extent=F)
        assert "NUM003" in _ids(found)

    def test_num003_masked_is_clean(self):
        F = 97
        x = jnp.zeros((F, 6))
        m = jnp.ones((F,))

        def fn(feats, mask):
            z = jnp.where(mask[:, None] > 0, feats, 0.0)
            return jnp.sum(z, axis=0) / jnp.maximum(jnp.sum(mask), 1.0)

        found = check_jaxpr(fn, x, m, input_roles=("feats", "mask"),
                            frame_extent=F)
        assert _ids(found) == []

    def test_num003_inactive_without_mask_input(self):
        # a mask-free entry (pure parameter math) must not fire NUM003
        x = jnp.zeros((97, 6))
        found = check_jaxpr(lambda feats: jnp.mean(feats, axis=0), x,
                            input_roles=("feats",), frame_extent=97)
        assert _ids(found) == []

    def test_num003_sees_into_scan(self):
        F = 97
        x = jnp.zeros((3, F, 6))
        m = jnp.ones((3, F))

        def fn(feats, mask):
            def body(c, xs):
                f_c, _ = xs
                return c + jnp.sum(f_c, axis=0), None

            out, _ = jax.lax.scan(body, jnp.zeros((6,)), (feats, mask))
            return out

        found = check_jaxpr(fn, x, m, input_roles=("feats", "mask"),
                            frame_extent=F)
        assert "NUM003" in _ids(found)

    def test_num004_f64_leak(self):
        with jax.enable_x64(True):
            x = jnp.zeros((4,), jnp.float64)
            found = check_jaxpr(lambda v: (v * 2.0).sum(), x)
        assert "NUM004" in _ids(found)


# ---------------------------------------------------------------------------
# Pass 2 golden fixtures — kernel rules
# ---------------------------------------------------------------------------


def _spec(name="fixture", *, kernel_fn=None, describe=None,
          padded=True, reduction_axes=(), has_ring=False, config=None):
    def _nop(a_ref, o_ref):
        o_ref[...] = a_ref[...]

    return registry.KernelSpec(
        name=name, kernel_fn=kernel_fn or _nop,
        describe=describe, default_config=config or {},
        padded_by_wrapper=padded, reduction_axes=reduction_axes,
        has_dma_ring=has_ring)


class TestKernelRules:
    def test_krn001_indivisible_without_wrapper(self):
        def describe(cfg):
            return registry.KernelInstance(
                grid=(2,),
                inputs=(registry.BlockMap("x", (100, 8), (64, 8),
                                          lambda i: (i, 0)),),
                outputs=(registry.BlockMap("o", (100, 8), (64, 8),
                                           lambda i: (i, 0)),),
                scratch_bytes=0)

        found = check_kernel(_spec(describe=describe, padded=False))
        assert "KRN001" in _ids(found)
        # same geometry with the pad-and-clip wrapper declared: clean
        found = check_kernel(_spec(describe=describe, padded=True))
        assert "KRN001" not in _ids(found)

    def test_krn002_two_writers_race(self):
        # grid axis 1 is NOT declared a reduction, yet both j values map
        # to output block (i, 0): a write-write race
        def describe(cfg):
            return registry.KernelInstance(
                grid=(2, 2),
                inputs=(registry.BlockMap("x", (128, 128), (64, 64),
                                          lambda i, j: (i, j)),),
                outputs=(registry.BlockMap("o", (128, 64), (64, 64),
                                           lambda i, j: (i, 0)),),
                scratch_bytes=0)

        found = check_kernel(_spec(describe=describe))
        assert "KRN002" in _ids(found)
        # declaring axis 1 as a reduction makes it the legal
        # init/accumulate pattern
        found = check_kernel(_spec(describe=describe, reduction_axes=(1,)))
        assert "KRN002" not in _ids(found)

    def test_krn002_coverage_hole(self):
        def describe(cfg):
            return registry.KernelInstance(
                grid=(2,),
                inputs=(registry.BlockMap("x", (128, 8), (64, 8),
                                          lambda i: (i, 0)),),
                outputs=(registry.BlockMap("o", (128, 8), (64, 8),
                                           lambda i: (0, 0)),),
                scratch_bytes=0)

        found = check_kernel(_spec(describe=describe, reduction_axes=(0,)))
        assert "KRN002" in _ids(found)

    def test_krn003_start_without_wait(self):
        def leaky(x_ref, o_ref, sem):
            cp = jax.experimental.pallas.tpu  # placeholder namespace
            copy = cp.make_async_copy(x_ref, o_ref, sem)
            copy.start()
            o_ref[...] = x_ref[...]

        def describe(cfg):
            return registry.KernelInstance(
                grid=(1,), inputs=(), outputs=(), scratch_bytes=0,
                rings=(registry.DmaRing("sem", 2),))

        found = check_kernel(_spec(kernel_fn=leaky, describe=describe,
                                   has_ring=True))
        assert "KRN003" in _ids(found)

    def test_krn003_undeclared_ring(self):
        def sneaky(x_ref, o_ref, sem):
            copy = make_async_copy(x_ref, o_ref, sem)  # noqa: F821
            copy.start()
            copy.wait()

        def describe(cfg):
            return registry.KernelInstance(
                grid=(1,), inputs=(), outputs=(), scratch_bytes=0)

        found = check_kernel(_spec(kernel_fn=sneaky, describe=describe,
                                   has_ring=False))
        assert "KRN003" in _ids(found)

    def test_krn004_vmem_over_budget(self):
        spec = registry.get("gmm_align")
        # paper scale: C=2048 comps, D=60, K=20, BF=128 — the gathered
        # [bf*K, E2] scratch alone is ~19 MB
        found = check_kernel(spec, {"F": 4096, "C": 2048, "D": 60,
                                    "K": 20, "block_f": 128})
        assert "KRN004" in _ids(found)

    def test_registered_kernels_clean_at_defaults(self):
        for spec in registry.all_specs():
            found = check_kernel(spec)
            assert _ids(found) == [], (spec.name, [f.format()
                                                   for f in found])


# ---------------------------------------------------------------------------
# Pass 3 golden fixtures — source rules + suppression
# ---------------------------------------------------------------------------


def _lint(tmp_path, code, fname="mod.py"):
    p = tmp_path / fname
    p.write_text(code)
    return check_source(p)


class TestSourceRules:
    def test_src001_inv(self, tmp_path):
        found = _lint(tmp_path,
                      "import jax.numpy as jnp\n"
                      "def f(m):\n"
                      "    return jnp.linalg.inv(m)\n")
        assert _ids(found) == ["SRC001"]

    def test_src002_prngkey_literal(self, tmp_path):
        found = _lint(tmp_path,
                      "import jax\n"
                      "key = jax.random.PRNGKey(0)\n")
        assert _ids(found) == ["SRC002"]

    def test_src002_skipped_in_tests(self, tmp_path):
        found = _lint(tmp_path,
                      "import jax\n"
                      "key = jax.random.PRNGKey(0)\n",
                      fname="test_mod.py")
        assert _ids(found) == []

    def test_src003_host_sync_in_scan_body(self, tmp_path):
        found = _lint(tmp_path,
                      "import jax\n"
                      "def body(c, x):\n"
                      "    return c + float(x), None\n"
                      "def run(xs):\n"
                      "    return jax.lax.scan(body, 0.0, xs)\n")
        assert _ids(found) == ["SRC003"]

    def test_src003_host_sync_outside_traced_ok(self, tmp_path):
        found = _lint(tmp_path,
                      "def f(x):\n"
                      "    return float(x)\n")
        assert _ids(found) == []

    def test_det001_psum_exit(self, tmp_path):
        found = _lint(tmp_path,
                      "def run(stream):\n"
                      "    return stream(exit_reduce='psum')\n")
        assert _ids(found) == ["DET001"]

    def test_suppression_comment(self, tmp_path):
        found = _lint(tmp_path,
                      "import jax\n"
                      "# repro-check: disable=SRC002\n"
                      "key = jax.random.PRNGKey(0)\n")
        assert _ids(found) == []
        assert [f.rule_id for f in found if f.suppressed] == ["SRC002"]

    def test_suppression_trailing(self, tmp_path):
        found = _lint(tmp_path,
                      "def run(s):\n"
                      "    return s(exit_reduce='psum')"
                      "  # repro-check: disable=DET001\n")
        assert _ids(found) == []


# ---------------------------------------------------------------------------
# The merge gate: the repo itself lints clean
# ---------------------------------------------------------------------------


class TestCleanRepo:
    def test_repo_runs_clean(self):
        report = run_all([str(REPO / "src")])
        bad = [f.format() for f in report["findings"] if not f.suppressed]
        assert report["unsuppressed"] == 0, "\n".join(bad)
        js = report_json(report)
        assert set(js) == {"rules", "suppressed", "unsuppressed", "wall_s"}
        assert js["unsuppressed"] == 0

    def test_cli_exit_codes(self, tmp_path):
        dirty = tmp_path / "dirty.py"
        dirty.write_text("import jax.numpy as jnp\n"
                         "bad = jnp.linalg.inv\n"
                         "def f(m):\n"
                         "    return jnp.linalg.inv(m)\n")
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
               "PATH": "/usr/bin:/bin"}
        # restrict to source rules so the CLI doesn't trace entries twice
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis.check",
             str(dirty), "--rules", "SRC001"],
            env=env, capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis.check",
             str(clean), "--rules", "SRC001"],
            env=env, capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Numerics regressions enforced by the new rules (satellites)
# ---------------------------------------------------------------------------


class TestSpdSolves:
    def _near_singular_plda(self, eps):
        R = 8
        rng = np.random.RandomState(7)
        Qm = np.linalg.qr(rng.randn(R, R))[0]
        lam_w = np.array([1.0] * (R - 1) + [eps])
        W = (Qm * lam_w) @ Qm.T
        B = (Qm * np.linspace(0.5, 2.0, R)) @ Qm.T
        return backend.PLDA(jnp.zeros((R,), f32),
                            jnp.asarray(B, f32), jnp.asarray(W, f32)), B, W

    def test_plda_near_singular_matches_f64_reference(self):
        plda, B, W = self._near_singular_plda(1e-5)
        rng = np.random.RandomState(3)
        x = rng.randn(5, 8).astype(np.float32)
        y = rng.randn(5, 8).astype(np.float32)

        # float64 reference straight from the two-covariance LLR
        T = (B + W).astype(np.float64)
        Tinv = np.linalg.inv(T)
        S = T - B @ Tinv @ B
        Sinv = np.linalg.inv(S)
        Q = Tinv - Sinv
        P = Sinv @ B @ Tinv
        const = -0.5 * (np.linalg.slogdet(S)[1] - np.linalg.slogdet(T)[1])
        ref = (0.5 * (np.sum((x @ Q) * x, 1) + np.sum((y @ Q) * y, 1))
               + np.sum((x @ P) * y, 1) + const)

        got = np.asarray(backend.plda_score_pairs(
            plda, jnp.asarray(x), jnp.asarray(y)))
        assert np.all(np.isfinite(got))
        # cond(W) ~ 1e5, so f32 can't do better than ~cond * eps_f32
        np.testing.assert_allclose(got, ref, rtol=2e-2)

    def test_plda_matrix_diag_consistent(self):
        plda, _, _ = self._near_singular_plda(1e-4)
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(4, 8).astype(np.float32))
        mat = backend.plda_score_matrix(plda, x, x)
        pairs = backend.plda_score_pairs(plda, x, x)
        np.testing.assert_allclose(np.diag(np.asarray(mat)),
                                   np.asarray(pairs), rtol=1e-5,
                                   atol=1e-5)

    def test_full_precisions_near_singular(self):
        C, D = 3, 6
        rng = np.random.RandomState(11)
        covs = []
        for c in range(C):
            Qm = np.linalg.qr(rng.randn(D, D))[0]
            lam = np.array([1.0] * (D - 1) + [10.0 ** -(4 + c)])
            covs.append((Qm * lam) @ Qm.T)
        gmm = ubm.FullGMM(jnp.full((C,), 1 / C, f32),
                          jnp.zeros((C, D), f32),
                          jnp.asarray(np.stack(covs), f32))
        _, _, P = ubm.full_precisions(gmm)
        P = np.asarray(P)
        assert np.all(np.isfinite(P))
        np.testing.assert_allclose(P, np.swapaxes(P, 1, 2), rtol=0,
                                   atol=1e-4 * np.abs(P).max())

    def test_no_inv_in_scoring_jaxprs(self):
        # the lint-rule enforcement of satellite 1: neither scoring entry
        # nor the precision precompute may lower through 'lu'
        plda, _, _ = self._near_singular_plda(1e-3)
        x = jnp.zeros((4, 8), f32)
        assert "NUM002" not in _ids(check_jaxpr(
            backend.plda_score_matrix, plda, x, x))
        gmm = ubm.FullGMM(jnp.full((2,), 0.5, f32), jnp.zeros((2, 4), f32),
                          jnp.broadcast_to(jnp.eye(4, dtype=f32),
                                           (2, 4, 4)).copy())
        assert "NUM002" not in _ids(check_jaxpr(ubm.full_precisions, gmm))

    @pytest.mark.parametrize("estep", ["packed", "dense"])
    def test_no_lu_in_m_step_jaxpr(self, estep):
        # the M-step's T solve is a Cholesky factor plus the matmul-only
        # triangular inverse: a pivoted LU must not come back into it
        C, D, R = 3, 4, 5
        model = tvm.init_model(jax.random.PRNGKey(0), jnp.zeros((C, D), f32),
                               jnp.broadcast_to(jnp.eye(D, dtype=f32),
                                                (C, D, D)),
                               R, "augmented")
        acc = tvm.EMAccum.zeros(C, D, R, estep=estep)
        S_tot = jnp.zeros((C, D, D), f32)
        found = check_jaxpr(lambda m, a, s: tvm.m_step(m, a, s, True),
                            model, acc, S_tot)
        assert "NUM002" not in _ids(found, unsuppressed_only=False)


class TestBf16Accumulation:
    def test_bf16_contractions_accumulate_f32(self):
        # satellite 2: every dot_general on the bf16 E-step path pins
        # f32 accumulation — assert directly on the jaxpr params
        from repro.kernels import ops
        n = jnp.zeros((16, 8), f32)
        Up = jnp.zeros((8, 36), f32)
        jaxpr = jax.make_jaxpr(
            lambda a, b: ops.tvm_estep_l(a, b, dtype="bfloat16"))(n, Up)

        def dots(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn
                for v in eqn.params.values():
                    vs = v if isinstance(v, (tuple, list)) else (v,)
                    for sub in vs:
                        if hasattr(sub, "jaxpr"):
                            yield from dots(sub.jaxpr)
                        elif hasattr(sub, "eqns"):
                            yield from dots(sub)

        found = list(dots(jaxpr.jaxpr))
        assert found, "no dot_general in tvm_estep_l trace"
        for eqn in found:
            bf16_in = any(str(v.aval.dtype) == "bfloat16"
                          for v in eqn.invars)
            if bf16_in:
                pref = eqn.params.get("preferred_element_type")
                assert pref is not None and np.dtype(pref).name == \
                    "float32", eqn
