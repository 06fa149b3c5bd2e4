"""Engine loop-mode properties: the fused ``lax.scan`` window must
reproduce the per-step host loop exactly, the Fig.-9 stage timers must all
be written in step mode, capacity overflow must grow instead of killing the
run, and the redundant step-0 rebuild stays gone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeepmdForceProvider
from repro.dp import DPModel, paper_dpa1_config
from repro.md import (EngineConfig, MDEngine, build_neighbor_list,
                      build_solvated_protein, classical_forces, mark_nn_group)


@pytest.fixture(scope="module")
def small_system():
    system, pos, nn_idx = build_solvated_protein(5, water_per_protein_atom=1.5)
    system = mark_nn_group(system, nn_idx)
    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    params = model.init_params(jax.random.PRNGKey(0))
    return system, pos, nn_idx, model, params


def _provider(small_system, skin=0.0):
    system, pos, nn_idx, model, params = small_system
    return DeepmdForceProvider(model, params, nn_idx, system.types,
                               system.box, system.n_atoms, nbr_capacity=48,
                               skin=skin)


_CFG = dict(cutoff=0.9, neighbor_capacity=96, dt=0.0005, thermostat_t=200.0)


def test_scan_matches_step_loop(small_system):
    """Satellite: scan-loop vs step-loop trajectory equivalence."""
    system, pos, nn_idx, model, params = small_system
    runs = {}
    for mode in ["scan", "step"]:
        eng = MDEngine(system, EngineConfig(loop_mode=mode, **_CFG),
                       special_force=_provider(small_system))
        runs[mode] = eng.run(eng.init_state(pos, 200.0), 12)
    d = float(jnp.abs(runs["scan"].positions - runs["step"].positions).max())
    assert d <= 1e-6, d
    assert int(runs["scan"].step) == int(runs["step"].step) == 12


def test_stateful_reuse_matches_stateless(small_system):
    """Single-domain skin reuse (assemble/evaluate split) must reproduce the
    per-call pipeline within fp tolerance over a short trajectory."""
    system, pos, nn_idx, model, params = small_system
    eng0 = MDEngine(system, EngineConfig(**_CFG),
                    special_force=_provider(small_system))
    st0 = eng0.run(eng0.init_state(pos, 200.0), 12)
    prov = _provider(small_system, skin=0.08)
    assert prov.stateful
    eng1 = MDEngine(system, EngineConfig(**_CFG), special_force=prov)
    st1 = eng1.run(eng1.init_state(pos, 200.0), 12)
    assert bool(jnp.isfinite(st1.positions).all())
    d = float(jnp.abs(st0.positions - st1.positions).max())
    assert d <= 1e-5, d


def test_displacement_rebuilds_inside_scan(small_system):
    """With the cadence pushed out of reach, rebuilds must still happen via
    the in-scan displacement cond — and match the step loop's host-side
    rebuilds on the same criterion."""
    system, pos, nn_idx, model, params = small_system
    runs = {}
    for mode in ["scan", "step"]:
        # the fastest atoms move ~5e-3 nm in these 10 steps at 200 K: a
        # 2e-3 nm skin makes the displacement criterion fire several times
        cfg = EngineConfig(loop_mode=mode, rebuild_every=1000, skin=0.002,
                           **_CFG)
        eng = MDEngine(system, cfg, special_force=_provider(small_system))
        runs[mode] = (eng.run(eng.init_state(pos, 200.0), 10), eng)
    st_s, eng_s = runs["scan"]
    st_p, eng_p = runs["step"]
    assert eng_s.diagnostics["displacement_rebuilds"] > 0
    assert (eng_s.diagnostics["displacement_rebuilds"]
            == eng_p.diagnostics["displacement_rebuilds"])
    assert float(jnp.abs(st_s.positions - st_p.positions).max()) <= 1e-6


def test_displacement_rebuild_in_window_uses_rebuilt_pair_table(
        small_system):
    """A list the displacement check rejects is rebuilt inside the window,
    pair table and all: the step's forces are those of a fresh list.  The
    stale list's table drops every pair, so forces from it would differ."""
    system, pos = small_system[0], small_system[1]
    cfg = EngineConfig(rebuild_every=1000, **_CFG)
    eng = MDEngine(system, cfg)
    state = eng.init_state(pos, 200.0)
    nl = eng.build_nlist(pos)
    stale = dataclasses.replace(
        nl, ref_positions=pos + cfg.skin,
        pairs=dataclasses.replace(nl.pairs,
                                  mask=jnp.zeros_like(nl.pairs.mask)))
    (new, nl_out, _, flags, e_cl, _), _ = eng._window_fn(1)(state, stale,
                                                            None)
    assert int(flags["rebuilds"]) == 1
    assert float(nl_out.pairs.mask.sum()) > 0
    fresh = build_neighbor_list(pos, system.box, cfg.cutoff,
                                cfg.neighbor_capacity, half=True,
                                skin=cfg.skin)
    e_ref, f_ref = classical_forces(pos, system, fresh, cfg.ff)
    assert abs(float(e_cl - e_ref)) <= 1e-6 * abs(float(e_ref)) + 1e-4
    np.testing.assert_allclose(np.asarray(new.forces), np.asarray(f_ref),
                               rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(f_ref).max()))


def test_step_mode_writes_all_timers(small_system):
    """Satellite: "special" and "integrate" were declared but never written;
    the Fig.-9 decomposition needs all four stages populated."""
    eng = MDEngine(small_system[0], EngineConfig(loop_mode="step", **_CFG),
                   special_force=_provider(small_system))
    eng.run(eng.init_state(small_system[1], 200.0), 3)
    for key in ["neighbor", "classical", "special", "integrate"]:
        assert eng.timings[key] > 0.0, (key, eng.timings)


def test_capacity_overflow_grows_instead_of_raising(small_system):
    """Satellite: undersized neighbor capacity must not kill the trajectory;
    the engine doubles capacity (re-jit) and surfaces it in diagnostics."""
    system, pos = small_system[0], small_system[1]
    eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=2,
                                        dt=0.0005, thermostat_t=200.0))
    st = eng.run(eng.init_state(pos, 200.0), 4)
    assert bool(jnp.isfinite(st.positions).all())
    assert eng.diagnostics["capacity_growths"], eng.diagnostics
    assert eng.config.neighbor_capacity > 2


def test_clustered_system_starts_without_growth():
    """The protein chain packs its cells far above the box's mean density;
    the engine sizes the cells from the first frame instead of doubling."""
    system, pos, _ = build_solvated_protein(96)
    eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                        dt=0.0005, thermostat_t=200.0))
    st = eng.run(eng.init_state(pos, 200.0), 2)
    assert bool(jnp.isfinite(st.positions).all())
    assert eng.diagnostics["capacity_growths"] == []
    assert eng.config.neighbor_capacity == 96


def test_observe_and_checkpoint_cadence(small_system, tmp_path):
    """Seed-compatible cadence: observation after steps 1, 1+k, 1+2k, ...;
    checkpoints at absolute-step multiples; no redundant step-0 rebuild."""
    system, pos = small_system[0], small_system[1]
    path = str(tmp_path / "ck")
    eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                        dt=0.0005, checkpoint_every=4,
                                        checkpoint_path=path))
    seen = []
    st = eng.run(eng.init_state(pos, 150.0), 12,
                 observe=lambda s, o: seen.append(o["step"]), observe_every=5)
    assert seen == [1, 6, 11]
    assert int(MDEngine.restore(path).step) % 4 == 0
    assert int(st.step) == 12
    # pre-loop build + cadence rebuilds at i=10 only (not at i=0)
    assert eng.diagnostics["cadence_rebuilds"] == 1
