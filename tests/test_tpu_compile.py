"""Mosaic compiles of the main path's kernels for a described TPU v5e.

No chip is needed: the TPU compiler is handed a ``v5e:2x2`` topology and
compiles each kernel for one of its chips at the paper's widths (N = 1024
atoms, K = 128 neighbour lanes, M = 128 embedding width, H = 256 attention
hidden width, L = 3 layers).  A compile that passes here says the kernel's
tiling, VMEM use and lowering are accepted by the chip's compiler; it says
nothing about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, because a program compiled for a described chip cannot be read
back from it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.cell_gather import cell_filter
from repro.kernels.env_mat import env_mat
from repro.kernels.nbr_attn import nbr_attention_stack

N, K, M, H, L = 1024, 128, 128, 256, 3
RC_SMTH, RC = 0.5, 0.8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_env_mat_fwd_bwd_compiles(one_chip):
    planes = [_spec((N, K), one_chip)] * 4

    def fwd_bwd(dx, dy, dz, mask):
        def e(dx, dy, dz):
            s, sx, sy, sz = env_mat(dx, dy, dz, mask, RC_SMTH, RC)
            return (s + sx * sy - sz).sum()
        return jax.value_and_grad(e, argnums=(0, 1, 2))(dx, dy, dz)

    text = _compiled_text(fwd_bwd, *planes)
    assert "tpu_custom_call" in text
    assert "env_mat_fwd" in text and "env_mat_bwd" in text


def test_cell_filter_compiles(one_chip):
    planes = [_spec((N, 27 * 128), one_chip)] * 4
    text = _compiled_text(lambda *a: cell_filter(*a, RC), *planes)
    assert "tpu_custom_call" in text and "cell_filter" in text


@pytest.mark.parametrize("dtype,heads", [("float32", 1), ("bfloat16", 1),
                                         ("float32", 4)])
def test_nbr_attention_stack_fwd_bwd_compiles(one_chip, dtype, heads):
    g = _spec((N, K, M), one_chip)
    planes = [_spec((N, K), one_chip)] * 5
    w_in = [_spec((L, M, H), one_chip)] * 3
    w_out = _spec((L, H, M), one_chip)
    ln = [_spec((L, M), one_chip)] * 2

    def fwd_bwd(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta):
        def e(g, rx, wq, wo):
            out = nbr_attention_stack(g, rx, ry, rz, sw, mask, wq, wk, wv,
                                      wo, gamma, beta, heads=heads,
                                      compute_dtype=dtype)
            return (out * out).sum()
        return jax.value_and_grad(e, argnums=(0, 1, 2, 3))(g, rx, wq, wo)

    text = _compiled_text(fwd_bwd, g, *planes, *w_in, w_out, *ln)
    assert "tpu_custom_call" in text
    assert "nbr_attn_stack_fwd" in text and "nbr_attn_stack_bwd" in text
