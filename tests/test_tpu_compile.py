"""Mosaic compiles of the main path's kernels for a described TPU v5e.

No chip is needed: the TPU compiler is handed a ``v5e:2x2`` topology and
compiles each kernel for one of its chips at the paper's widths (N = 1024
atoms, K = 128 neighbour lanes, M = 128 embedding width, H = 256 attention
hidden width, L = 3 layers), and the classical force step at the benchmark
cell's shapes.  A compile that passes here says the kernel's tiling, VMEM
use and lowering are accepted by the chip's compiler; it says nothing about
results or speed.

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
from repro.md.forcefield import ForceFieldConfig, PairTable, classical_forces
from repro.md.neighbors import NeighborList
from repro.md.system import System, Topology

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


def test_classical_forces_compile_without_padded_pair_buffers(one_chip):
    """The engine's classical step at the benchmark cell's shapes (16,063
    atoms, a 96-wide half list with its pair table, 16 exclusion columns,
    the chain's bonds and angles) holds no per-slot three-vector, whose 3
    would sit on the 128-lane axis, and compiles to far less temp memory
    than the autodiff form's 2.39 GB."""
    n, k, e, nb, na = 16063, 96, 16, 4095, 4094
    f32 = lambda *shape: _spec(shape, one_chip)
    i32 = lambda *shape: _spec(shape, one_chip, jnp.int32)
    topology = Topology(
        bonds=i32(nb, 2), bond_params=f32(nb, 2), bond_mask=f32(nb),
        angles=i32(na, 3), angle_params=f32(na, 2), angle_mask=f32(na),
        dihedrals=i32(1, 4), dihedral_params=f32(1, 3), dihedral_mask=f32(1),
        exclusions=i32(n, e))
    system = System(box=f32(3), types=i32(n), masses=f32(n), charges=f32(n),
                    lj_sigma=f32(4), lj_epsilon=f32(4), topology=topology,
                    nn_mask=f32(n))
    nlist = NeighborList(idx=i32(n, k), mask=f32(n, k),
                         ref_positions=f32(n, 3),
                         overflow=_spec((), one_chip, jnp.bool_),
                         pairs=PairTable(*[f32(n, k)] * 4))
    cfg = ForceFieldConfig(cutoff=1.2)
    compiled = jax.jit(
        lambda pos, s, nl: classical_forces(pos, s, nl, cfg)).lower(
            f32(n, 3), system, nlist).compile()
    text = compiled.as_text()
    assert "f32[16063,96,3]" not in text
    assert "f32[1542048,3]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.39e9
