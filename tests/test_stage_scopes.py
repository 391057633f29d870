"""The EM stages carry their `jax.named_scope` into the compiled program.

Each stage names itself where its work is done (``core/alignment.py``,
``core/stats.py``, ``core/engine.py``, ``core/tvm.py``), so every program
built from them carries the names in the HLO ``op_name`` metadata, which
a profiler trace reports beside each device op. Compiled here on the CPU
at toy widths: the realigning iteration (``make_iter_fn``) and the EM on
statistics at rest (``make_em_fn``) hold every stage scope, and every
matrix product, top-K, eigendecomposition, factorisation and solve in
them lies under one.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.ivector_tvm import IVectorConfig
from repro.core import trainer as TR
from repro.core import tvm as TV
from repro.core import ubm as U

C, D, R, K, NU, F = 16, 6, 8, 4, 8, 32

STAGES = ("ivec_preselect", "ivec_rescore", "ivec_moments", "ivec_estep",
          "ivec_mstep")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = .*?\s([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# the linear algebra each stage must own: products, top-K, eigh, Cholesky,
# LU and triangular solves, as ops or as library calls
_HEAVY_OPS = {"dot", "convolution", "cholesky", "triangular-solve"}
_HEAVY_TARGETS = re.compile(r"TopK|syevd|eigh|potrf|getrf|trsm|lu|solve|"
                            r"cholesky|qr", re.I)


def _cfg():
    return IVectorConfig(
        feat_dim=D, n_components=C, ivector_dim=R, posterior_top_k=K,
        frames_per_utt=F, estep_chunk=4, realign_interval=1,
        ubm_update="means").validate()


def _inputs():
    key = jax.random.PRNGKey(3)
    means = jax.random.normal(key, (C, D)) * 2.0
    A = jax.random.normal(jax.random.fold_in(key, 1), (C, D, D)) * 0.2
    covs = jnp.einsum("cij,ckj->cik", A, A) + jnp.eye(D)
    ubm = U.FullGMM(jnp.ones((C,)) / C, means, covs)
    model = TV.init_model(jax.random.fold_in(key, 2), means, covs, R,
                          "augmented")
    feats = jax.random.normal(jax.random.fold_in(key, 3), (NU, F, D))
    return model, ubm, feats


def _instructions(hlo: str):
    """(opcode, custom-call target or '', op_name) of every instruction,
    fused computations and loop bodies included."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        t = _TARGET.search(line)
        n = _OP_NAME.search(line)
        out.append((m.group(1), t.group(1) if t else "",
                    n.group(1) if n else ""))
    return out


def _heavy(ins):
    op, target, _ = ins
    return op in _HEAVY_OPS or (op == "custom-call"
                                and bool(_HEAVY_TARGETS.search(target)))


@pytest.fixture(scope="module")
def compiled():
    cfg = _cfg()
    model, ubm, feats = _inputs()
    it = TR.make_iter_fn(cfg).lower(model, ubm, feats).compile().as_text()
    stats = TR.make_stats_fn(cfg)(ubm, feats)
    em = TR.make_em_fn(cfg).lower(model, stats.n, stats.f,
                                  stats.S).compile().as_text()
    return {"iter": it, "em": em}


@pytest.mark.parametrize("program,stages", [
    ("iter", STAGES), ("em", ("ivec_estep", "ivec_mstep"))])
def test_every_stage_scope_is_in_the_compiled_program(compiled, program,
                                                      stages):
    names = {n for _, _, n in _instructions(compiled[program])}
    comps = {c for n in names for c in n.split("/")}
    for scope in stages + ("ivec_sigma_floor", "ivec_min_divergence"):
        assert scope in comps, scope
    # the M-step's nested scopes sit inside it
    assert any("ivec_mstep/ivec_sigma_floor" in n for n in names)
    assert any("ivec_mstep/ivec_min_divergence" in n for n in names)


@pytest.mark.parametrize("program", ["iter", "em"])
def test_heavy_ops_all_lie_under_a_stage(compiled, program):
    ins = _instructions(compiled[program])
    heavy = [i for i in ins if _heavy(i)]
    assert any(op == "dot" for op, _, _ in heavy)
    assert any(op == "custom-call" for op, _, _ in heavy)
    loose = [i for i in heavy
             if not any(c.startswith("ivec_") for c in i[2].split("/"))]
    assert not loose, loose
