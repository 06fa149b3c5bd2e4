"""The frame the check compares: positions before the engine's last step,
recovered exactly from the state after it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perfbench_tiny import ROOT  # noqa: F401

from perfbench.check import (atom_rel_rms, compare, judge,
                             previous_positions, rel_rmse)
from repro.md.integrators import MDState, leapfrog_step


def test_previous_positions_are_recovered_bit_for_bit():
    rng = np.random.default_rng(7)
    n, dt = 4000, 0.002
    box = np.array([6.0, 7.0, 207.0], np.float32)
    x = (rng.uniform(0, 1, (n, 3)) * box).astype(np.float32)
    x[:20, 2] = rng.uniform(0, 1e-3, 20)            # atoms that wrap below 0
    x[20:40, 2] = box[2] - rng.uniform(0, 1e-3, 20)  # and above the box
    masses = rng.choice([12.011, 14.007, 18.015], n).astype(np.float32)
    v = rng.normal(0, 0.4, (n, 3)).astype(np.float32)
    v[:20, 2] = -abs(v[:20, 2]) - 0.5
    v[20:40, 2] = abs(v[20:40, 2]) + 0.5
    f = rng.normal(0, 50, (n, 3)).astype(np.float32)
    state = MDState(positions=jnp.asarray(x), velocities=jnp.asarray(v),
                    forces=jnp.zeros((n, 3)), step=jnp.zeros((), jnp.int32),
                    rng=jax.random.PRNGKey(0))

    @jax.jit
    def step(s, f):
        return leapfrog_step(s, f, jnp.asarray(masses), jnp.asarray(box), dt)

    new = step(state, jnp.asarray(f))
    prev, unresolved, ambiguous = previous_positions(
        np.asarray(new.positions), np.asarray(new.velocities), box, dt)
    assert unresolved == 0
    # a coordinate that moved up into a coarser float32 spacing (the wrap
    # from below 0 to below the box length, or across a power of two) lost
    # digits the new spacing cannot hold and comes back within it; where
    # that spacing is coarse (the wrap to 207 nm) the atom is flagged
    xn = np.asarray(new.positions)
    assert (np.abs(prev - x) <= np.spacing(xn)).all()
    assert ambiguous[:20].all() and ambiguous.sum() < 40
    exact = (prev == x).all(-1)
    assert exact.mean() > 0.99 and exact[40:].sum() >= len(x) - 60
    # and the step from the recovered positions lands where the engine did
    again = step(MDState(positions=jnp.asarray(prev),
                         velocities=state.velocities, forces=state.forces,
                         step=state.step, rng=state.rng), jnp.asarray(f))
    assert np.array_equal(np.asarray(again.positions),
                          np.asarray(new.positions))


def test_judge_fails_a_number_over_its_limit_or_without_one():
    ok, table = judge({"a": 1e-7, "b": 0}, {"a": 1e-6, "b": 0})
    assert ok and table["a"] == {"value": 1e-7, "limit": 1e-6}
    assert not judge({"a": 2e-6}, {"a": 1e-6})[0]
    assert not judge({"a": float("nan")}, {"a": 1.0})[0]
    assert not judge({"c": 0.0}, {})[0]


def test_rel_rmse():
    ref = np.ones((4, 3))
    assert rel_rmse(ref, ref) == 0.0
    assert rel_rmse(ref * 1.01, ref) == pytest.approx(0.01)


def test_near_contact_atoms_count_in_the_dp_force_number():
    rng = np.random.default_rng(3)
    n = 200
    f_dp = rng.normal(0, 5, (n, 3))
    f_dp[7] = [4000.0, 0, 0]                 # a near-contact pair's force
    ref = {"f_dp": f_dp, "f_cl": np.zeros((n, 3)), "e_dp": 1.0,
           "e_dp_scale": 1.0, "e_cl": 0.0, "e_cl_scale": 1.0}
    prog = f_dp.copy()
    key = "dp_force_atom_rel"
    assert compare(prog, 1.0, 0.0, ref, np.arange(n))[key] == 0
    # float32 conditioning there counts as that one atom's relative error
    prog[7, 0] *= 1 + 3e-5
    near = compare(prog, 1.0, 0.0, ref, np.arange(n))[key]
    med = np.median(np.linalg.norm(f_dp, axis=1))
    assert near == pytest.approx(3e-5 * 4000 / (4000 + med) / np.sqrt(n))
    skip = np.zeros(n, bool)
    skip[7] = True                           # unless the atom is skipped
    assert compare(prog, 1.0, 0.0, ref, np.arange(n), skip)[key] == 0
    prog[8] *= 1.5                           # a wrong answer elsewhere
    assert compare(prog, 1.0, 0.0, ref, np.arange(n))[key] > 1e-2


def test_atom_rel_rms_weighs_every_atom_alike():
    f_ref = np.array([[1.0, 0, 0], [1.0, 0, 0], [100.0, 0, 0]])
    f = f_ref.copy()
    f[0, 0] += 1e-3                          # median |f| is 1
    assert atom_rel_rms(f, f_ref) == pytest.approx(1e-3 / 2 / np.sqrt(3))
    f = f_ref.copy()
    f[2, 0] += 1e-1
    assert atom_rel_rms(f, f_ref) == pytest.approx(1e-1 / 101 / np.sqrt(3))
