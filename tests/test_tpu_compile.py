"""The main-path kernels compile for a TPU v5e at paper widths.

Compile-only: a ``v5e:2x2`` topology is described (no chip attached) and
each kernel, through its `kernels/ops.py` wrapper with interpret mode off,
is lowered and compiled for one of its devices. What the TPU compiler
refuses (tiling, VMEM, layouts) fails here, not on the chip. Nothing runs.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

C, D, K, R, U = 2048, 72, 20, 400, 512
P = R * (R + 1) // 2
F = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    with ops.use_pallas(True, interpret=False):
        return jax.jit(fn).lower(*shapes).compile()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gmm_rescore_compiles_for_v5e(one_chip):
    s = lambda *a: _sds(one_chip, *a)
    c = _compile(ops.gmm_rescore, s((F, D)), s((F, K), jnp.int32),
                 s((C,)), s((D, C)), s((C, D * D)))
    assert "tpu_custom_call" in c.as_text()


def test_gmm_loglik_compiles_for_v5e(one_chip):
    s = lambda *a: _sds(one_chip, *a)
    c = _compile(ops.gmm_loglik, s((F, D)), s((C,)), s((D, C)),
                 s((C, D * D)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tvm_estep_l_compiles_for_v5e(one_chip, dtype):
    s = lambda *a: _sds(one_chip, *a)
    c = _compile(lambda n, up: ops.tvm_estep_l(n, up, dtype=dtype),
                 s((U, C)), s((C, P)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tvm_estep_a_compiles_for_v5e(one_chip, dtype):
    s = lambda *a: _sds(one_chip, *a)
    c = _compile(lambda n, pp: ops.tvm_estep_a(n, pp, dtype=dtype),
                 s((U, C)), s((U, P)))
    assert "tpu_custom_call" in c.as_text()


def test_second_moments_compiles_for_v5e(one_chip):
    """The grouped second-order moments of a 4096-frame chunk lower to a
    Mosaic kernel (the scatter-add they replace does not fit the chip at
    the trainer's chunk sizes)."""
    s = lambda *a: _sds(one_chip, *a)
    N = 4096
    c = _compile(lambda x, g, sel: ops.second_moments(x, g, sel, C),
                 s((N, D)), s((N, K)), s((N, K), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
