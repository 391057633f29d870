"""Distributed tests (8 simulated host devices via subprocess: jax locks the
device count at first init, so each scenario runs in its own process)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8) -> str:
    from repro.launch.mesh import fake_device_env
    env = fake_device_env(devices)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_a2a_matches_dense():
    """shard_map all-to-all MoE == dense one-hot dispatch on a 2x2x2 mesh,
    both in the no-drop regime."""
    out = run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import moe as MOE, layers as L, api
        from repro.sharding import make_rules, use_rules
        cfg = get_config('moonshot-v1-16b-a3b', smoke=True)
        cfg = cfg.with_overrides(moe=dataclasses.replace(
            cfg.moe, capacity_factor=64.0))
        table = {k[len('layer/moe/'):]: v for k, v in
                 api.param_table(cfg).items() if k.startswith('layer/moe/')}
        p = {k: v[0] for k, v in
             L.table_init(table, jax.random.PRNGKey(0), jnp.float32).items()}
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        want, aux_d = MOE.moe_dense(cfg, p, x)
        mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rules = make_rules(mesh, cfg, None)
        with use_rules(rules):
            got_sp, aux1 = jax.jit(lambda x, p: MOE.moe_a2a(cfg, p, x, True))(x, p)
            got_nsp, aux2 = jax.jit(lambda x, p: MOE.moe_a2a(cfg, p, x, False))(x, p)
        np.testing.assert_allclose(np.asarray(got_sp), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got_nsp), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        print('A2A_OK')
    """)
    assert "A2A_OK" in out


def test_sharded_train_step_matches_single_device():
    """One train step on a (2,2,2) mesh == the same step on 1 device."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, ShapeConfig
        from repro.models import api
        from repro.sharding import make_rules, use_rules
        cfg = get_config('phi3-medium-14b', smoke=True)
        state = api.init_state(cfg, jax.random.PRNGKey(0))
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                              0, cfg.vocab_size),
                 'labels': jax.random.randint(jax.random.PRNGKey(2), (8, 64),
                                              0, cfg.vocab_size)}
        step = api.make_train_step(cfg)
        ref_state, ref_m = jax.jit(step)(state, batch)
        mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        shape = ShapeConfig('train_4k', 64, 8, 'train')
        rules = make_rules(mesh, cfg, shape)
        with use_rules(rules):
            got_state, got_m = jax.jit(step)(state, batch)
        np.testing.assert_allclose(float(got_m['loss']),
                                   float(ref_m['loss']), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(ref_state['params']),
                        jax.tree.leaves(got_state['params'])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=3e-3, atol=3e-3)
        print('SHARDED_OK', float(got_m['loss']))
    """)
    assert "SHARDED_OK" in out


def test_elastic_remesh_restore(tmp_path):
    """Checkpoint on a (4,2) mesh restores onto (2,2) and 1-device meshes."""
    script = f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import save, restore
        from repro.configs import get_config
        from repro.models import api
        from repro.sharding import make_rules, use_rules
        cfg = get_config('gemma-2b', smoke=True)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        axes = api.params_axes(cfg)
        mesh_a = jax.make_mesh((4, 2), ('data', 'model'),
                               axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules_a = make_rules(mesh_a, cfg, None)
        sharded = {{k: jax.device_put(v, rules_a.sharding(v.shape, axes[k]))
                   for k, v in params.items()}}
        save({str(tmp_path)!r}, 1, sharded, logical_axes=axes)
        mesh_b = jax.make_mesh((2, 2), ('data', 'model'),
                               axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules_b = make_rules(mesh_b, cfg, None)
        got, step, _ = restore({str(tmp_path)!r}, params, rules=rules_b)
        for k in params:
            np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                          np.asarray(params[k], np.float32))
        # sharding actually follows the new mesh
        anyk = 'layer/attn/wq'
        assert got[anyk].sharding.mesh.shape['data'] == 2
        print('ELASTIC_OK')
    """
    out = run_py(script)
    assert "ELASTIC_OK" in out


def test_ring_attention_matches_blockwise():
    """Ring (context-parallel) attention == the single-device blockwise
    reference, on a (2, 4) mesh with seq sharded 4-ways."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import layers as L
        from repro.configs import get_config
        from repro.sharding import make_rules, use_rules
        B, S, H, KVH, hd = 4, 64, 6, 2, 16   # H=6 does not divide model=4
        k0 = jax.random.PRNGKey(0)
        q = jax.random.normal(k0, (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, KVH, hd))
        v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, KVH, hd))
        want = L.blockwise_causal_attention(q, k, v)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_config('whisper-large-v3', smoke=True)
        rules = make_rules(mesh, cfg, None)
        with use_rules(rules):
            assert L.use_ring_attention(
                cfg.with_overrides(n_heads=H, n_kv_heads=KVH), B, S)
            got = jax.jit(L.ring_attention)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        print('RING_OK')
    """)
    assert "RING_OK" in out


def test_mini_dryrun_multipod_compiles():
    """A reduced config lowers + compiles on a (2,2,2) pod mesh and the
    roofline walker extracts nonzero terms."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, ShapeConfig
        from repro.models import api
        from repro.sharding import make_rules, use_rules
        from repro.analysis.hlo_cost import analyze_hlo
        cfg = get_config('arctic-480b', smoke=True)
        shape = ShapeConfig('train', 64, 8, 'train')
        mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rules = make_rules(mesh, cfg, shape)
        batch = api.input_specs(cfg, shape)
        st = api.state_struct(cfg)
        with use_rules(rules):
            bsh = jax.tree.map(lambda s: rules.sharding(s.shape, ('batch',) +
                               (None,) * (len(s.shape) - 1)), batch,
                               is_leaf=lambda x: hasattr(x, 'shape'))
            ssh = jax.tree.map(lambda s, a: rules.sharding(s.shape, a),
                               st, api.state_axes(cfg),
                               is_leaf=lambda x: hasattr(x, 'shape') and not isinstance(x, dict))
            step = api.make_train_step(cfg)
            compiled = jax.jit(step, in_shardings=(ssh, bsh)).lower(
                st, batch).compile()
        r = analyze_hlo(compiled.as_text())
        assert r['flops'] > 0 and r['bytes'] > 0, r
        assert r['coll_bytes'] > 0, r
        print('DRYRUN_OK', int(r['flops']))
    """)
    assert "DRYRUN_OK" in out


def test_sharded_sparse_rescore_matches_dense():
    """The owner-local sharded alignment (components over 'model') gives
    the same Baum-Welch stats whether each rank scores its whole C-block
    densely, gather-and-rescores only the selected slots (DESIGN.md §8),
    or runs the fused packed-GEMM rescore on its local block (DESIGN.md
    §12) — the collectives are identical, only the rank-local scoring
    changes."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs.ivector_tvm import SMOKE
        from repro.core import ubm as U
        from repro.launch import ivector_cell as IC
        cfg = SMOKE.with_overrides(feat_dim=6, n_components=16,
                                   posterior_top_k=4)
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                    ('data', 'model'))
        key = jax.random.PRNGKey(0)
        C, D = cfg.n_components, cfg.feat_dim
        means = jax.random.normal(key, (C, D))
        A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.3
        covs = jnp.einsum('cij,ckj->cik', A, A) + jnp.eye(D)
        ubm = U.FullGMM(jnp.ones((C,)) / C, means, covs)
        feats = jax.random.normal(jax.random.fold_in(key, 2), (8, 32, D))
        pre = U.full_precisions(ubm)
        outs = {}
        for mode in ('dense', 'sparse', 'fused'):
            c = cfg.with_overrides(rescore=mode)
            with mesh:
                outs[mode] = IC.sharded_align_stats(
                    c, mesh, ubm.to_diag(), pre, feats, True)
        for mode in ('sparse', 'fused'):
            for a, b in zip(outs['dense'], outs[mode]):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-4)
        print('SPARSE_SHARD_OK')
    """)
    assert "SPARSE_SHARD_OK" in out
