"""Classical force field: conservation, symmetry, PME correctness, and the
analytic pair pass against the autodiff of the energy's definition."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.md import (EngineConfig, ForceFieldConfig, MDEngine,
                      build_neighbor_list, build_water_box, classical_forces)
from repro.md.forcefield import classical_energy, pair_table
from repro.md.observables import temperature
from repro.md.pme import ewald_reciprocal_reference, pme_reciprocal_energy


@pytest.fixture(scope="module")
def water():
    sys_, pos = build_water_box(5)
    return sys_, pos


def test_forces_finite_and_zero_sum(water):
    sys_, pos = water
    nl = build_neighbor_list(pos, sys_.box, 0.8, 128, half=True)
    e, f = classical_forces(pos, sys_, nl, ForceFieldConfig(cutoff=0.8))
    assert bool(jnp.isfinite(f).all())
    # translation invariance => net force ~ 0
    assert float(jnp.abs(f.sum(0)).max()) < 1e-2


def test_force_is_minus_grad(water):
    sys_, pos = water
    nl = build_neighbor_list(pos, sys_.box, 0.8, 128, half=True)
    cfg = ForceFieldConfig(cutoff=0.8)
    from repro.md.forcefield import classical_energy
    eps = 1e-3
    e0, f = classical_forces(pos, sys_, nl, cfg)
    # numerical check on a few coordinates
    for (i, d) in [(0, 0), (10, 1), (50, 2)]:
        dp = pos.at[i, d].add(eps)
        dm = pos.at[i, d].add(-eps)
        fd = -(classical_energy(dp, sys_, nl, cfg)
               - classical_energy(dm, sys_, nl, cfg)) / (2 * eps)
        assert abs(float(fd - f[i, d])) < 2e-2 + 0.05 * abs(float(f[i, d]))


def test_nve_energy_conservation(water):
    sys_, pos = water
    eng = MDEngine(sys_, EngineConfig(cutoff=0.8, neighbor_capacity=160,
                                      dt=0.001))
    st = eng.init_state(pos, 100.0)
    energies = []

    def obs(s, o):
        ke = 0.5 * float((sys_.masses[:, None] * s.velocities ** 2).sum())
        energies.append(o["e_classical"] + ke)

    eng.run(st, 60, observe=obs, observe_every=5)
    e = np.array(energies[1:])
    assert abs(e[-1] - e[0]) / abs(e[0]) < 0.05


def test_thermostat_drives_temperature(water):
    sys_, pos = water
    eng = MDEngine(sys_, EngineConfig(cutoff=0.8, neighbor_capacity=160,
                                      thermostat_t=250.0, thermostat_tau=0.1))
    st = eng.init_state(pos, 50.0)
    st = eng.run(st, 80)
    t = float(temperature(st.velocities, sys_.masses))
    assert 80.0 < t < 500.0  # moved sharply up from 50 K toward target


def test_pme_matches_direct_ewald():
    rng = np.random.default_rng(0)
    n = 20
    box = jnp.asarray([2.0, 2.5, 3.0], jnp.float32)
    pos = jnp.asarray(rng.uniform(0, 1, (n, 3)), jnp.float32) * box
    q = jnp.asarray(rng.uniform(-1, 1, n), jnp.float32)
    q = q - q.mean()
    e_pme = pme_reciprocal_energy(pos, q, box, (32, 32, 32), 4, 3.0)
    e_ref = ewald_reciprocal_reference(pos, q, box, 3.0, kmax=10)
    assert abs(float(e_pme - e_ref)) / abs(float(e_ref)) < 1e-3


def test_nn_exclusions_remove_bonded_terms():
    from repro.md import build_solvated_protein, mark_nn_group
    system, pos, nn_idx = build_solvated_protein(8)
    marked = mark_nn_group(system, nn_idx)
    assert float(marked.topology.bond_mask.sum()) == 0.0
    assert float(marked.topology.angle_mask.sum()) == 0.0
    assert float(marked.nn_mask.sum()) == len(nn_idx)


def _jittered_water():
    """A water box off its lattice (where forces cancel) with neutral
    alternating charges, so both LJ and Coulomb carry force."""
    sys_, pos = build_water_box(6)
    n = sys_.n_atoms
    pos = pos + 0.03 * jax.random.normal(jax.random.PRNGKey(1), pos.shape)
    q = jnp.where(jnp.arange(n) % 2 == 0, 0.4, -0.4).astype(jnp.float32)
    return dataclasses.replace(sys_, charges=q), pos


def _chain():
    """Solvated chain with its DP group marked: exclusions inside the group
    and DP-DP pairs masked out of the short range."""
    from repro.md import build_solvated_protein, mark_nn_group
    system, pos, nn_idx = build_solvated_protein(8)
    return mark_nn_group(system, nn_idx), pos


@pytest.mark.parametrize("half", [True, False], ids=["half", "full"])
@pytest.mark.parametrize("pme", [False, True], ids=["rf", "pme"])
@pytest.mark.parametrize("build", [_jittered_water, _chain],
                         ids=["water", "chain"])
def test_analytic_pair_pass_matches_autodiff(build, pme, half):
    sys_, pos = build()
    cfg = ForceFieldConfig(cutoff=0.8, use_pme=pme, pme_grid=(16, 16, 16))
    nl = build_neighbor_list(pos, sys_.box, 0.8, 96 if half else 160,
                             half=half)
    assert not bool(nl.overflow)
    e_ref, g = jax.value_and_grad(classical_energy)(pos, sys_, nl, cfg, half)
    e, f = classical_forces(pos, sys_, nl, cfg, half)
    f_ref = -np.asarray(g)
    assert abs(float(e - e_ref)) <= 1e-6 * abs(float(e_ref)) + 1e-4
    scale = float(np.abs(f_ref).max())
    np.testing.assert_allclose(np.asarray(f), f_ref, rtol=1e-5,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("half", [True, False], ids=["half", "full"])
def test_carried_pair_table_matches_table_built_in_call(half):
    sys_, pos = _chain()
    cfg = ForceFieldConfig(cutoff=0.8)
    nl = build_neighbor_list(pos, sys_.box, 0.8, 96 if half else 160,
                             half=half)
    assert nl.pairs is None
    carried = dataclasses.replace(nl, pairs=pair_table(sys_, nl, cfg))
    e0, f0 = classical_forces(pos, sys_, nl, cfg, half)
    e1, f1 = classical_forces(pos, sys_, carried, cfg, half)
    assert float(e0) == float(e1)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
