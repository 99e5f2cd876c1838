"""Compile every Pallas kernel of the main path for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached. That catches what interpret mode cannot: block
shapes off the (8, 128) tiling, primitives the chip's compiler does not
lower, VMEM overruns. Shapes are those ``chip_smoke.py`` runs: the HSTU
kernels at ``hstu-gr`` width (B 32, H 2, S 1,040 = 1,024 history events +
16 targets, d 32, bias on, max_rel 1,024), the cached prefix at the serving
shapes (1 and 1,024 new events), the embedding bag at d 16 and d 128.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import embedding_bag as bag_kernel
from repro.kernels import hstu_attention as hstu_kernel

B, H, N_HIST, M, D = 32, 2, 1024, 16, 32
S = N_HIST + M
MAX_REL = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _hstu_args(sh, s=S):
    return (_spec(sh, (B, H, s, D)), _spec(sh, (B, H, s, D)),
            _spec(sh, (B, H, s, D)), _spec(sh, (H, 2 * MAX_REL + 1)),
            _spec(sh, (B,), jnp.int32), _spec(sh, (B,), jnp.int32))


def _hstu(q, k, v, rab, hl, tc):
    return hstu_kernel.hstu_attention(q, k, v, rab, N_HIST, hl, tc, MAX_REL,
                                      interpret=False)


def test_hstu_forward(one_chip):
    _compile(_hstu, *_hstu_args(one_chip))


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_hstu_forward_backward(one_chip, precision):
    """Also under ``highest``, where chip_smoke.py compares trajectories."""
    def fwd_bwd(q, k, v, rab, hl, tc):
        return jax.value_and_grad(
            lambda q, k, v, rab: _hstu(q, k, v, rab, hl, tc).sum(),
            argnums=(0, 1, 2, 3))(q, k, v, rab)
    with jax.default_matmul_precision(precision):
        _compile(fwd_bwd, *_hstu_args(one_chip))


@pytest.mark.parametrize("n_new", [1, 1024])
def test_hstu_cached_prefix(one_chip, n_new):
    def prefix(q, k, v, rab, pfx, nc, tc):
        return hstu_kernel.hstu_attention_prefix(
            q, k, v, rab, N_HIST, n_new, pfx, nc, tc, S, MAX_REL,
            interpret=False)
    sh = one_chip
    _compile(prefix, _spec(sh, (B, H, n_new + M, D)), _spec(sh, (B, H, S, D)),
             _spec(sh, (B, H, S, D)), _spec(sh, (H, 2 * MAX_REL + 1)),
             *[_spec(sh, (B,), jnp.int32)] * 3)


@pytest.mark.parametrize("pooling", ["sum", "mean", "max"])
@pytest.mark.parametrize("d", [16, 128])
def test_embedding_bag_forward_backward(one_chip, d, pooling):
    def fwd_bwd(table, ids, lengths):
        return jax.value_and_grad(lambda t: bag_kernel.embedding_bag(
            t, ids, lengths, pooling, backend="pallas").sum())(table)
    sh = one_chip
    _compile(fwd_bwd, _spec(sh, (4096, d)), _spec(sh, (256, 8), jnp.int32),
             _spec(sh, (256,), jnp.int32))
