"""The layers the program names for the benchmark: the name scopes of the
window program against the stage patterns of the trace metrics, and the
readers of the engine's per-run host totals."""
import json
import re

import numpy as np
import pytest
from perfbench_tiny import ROOT

from perfbench import harness
from perfbench.trace_reduce import op_names, reduce_events

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "protein_hybrid.skin_reuse"
STAGES = ("stage_ms.classical", "stage_ms.inference", "stage_ms.assembly")
NEW_SCOPES = ("md.rebuild_check", "md.neighbor", "md.classical",
              "md.integrate", "dp.nbr_gather", "dp.env_mat", "dp.embedding",
              "dp.attention", "dp.descriptor_reduce", "dp.fitting")


def _reader(metric):
    return harness.load_cell(CELL).reader(metric)


def _scopes():
    """Every metric's scope patterns, merged in BENCHMARK.json order as the
    harness merges them (an op counts for the first pattern it matches)."""
    out = {}
    for m in BENCH["per_layer"]:
        out.update(getattr(_reader(m["name"]), "SCOPES", {}))
    return out


@pytest.fixture(scope="module")
def window_stacks():
    """Name stacks of a small engine's window program (one-rank DD, DPA-1),
    compiled for the CPU through ``MDEngine.lower_window``."""
    import jax
    from repro.core import DeepmdForceProvider, suggest_config
    from repro.dp import DPModel, paper_dpa1_config
    from repro.launch.mesh import make_dd_mesh
    from repro.md import (EngineConfig, MDEngine, build_solvated_protein,
                          mark_nn_group)
    system, pos, nn_idx = build_solvated_protein(5,
                                                 water_per_protein_atom=1.5)
    system = mark_nn_group(system, nn_idx)
    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    nn = np.asarray(nn_idx)
    dd = suggest_config(len(nn), np.asarray(system.box), 1, 0.6,
                        nbr_capacity=48, skin=0.08,
                        force_mode="ghost_reduce",
                        coords=np.asarray(pos)[nn])
    prov = DeepmdForceProvider(model, model.init_params(jax.random.PRNGKey(0)),
                               nn_idx, system.types, system.box,
                               system.n_atoms, dd_config=dd,
                               mesh=make_dd_mesh(1))
    eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                        dt=0.0005, rebuild_every=5),
                   special_force=prov)
    state = eng.init_state(pos, 200.0)
    lowered = eng.lower_window(state, eng.build_nlist(pos),
                               prov.assemble(pos))
    return list(op_names(lowered.compile().as_text()).values())


def test_window_program_names_every_new_scope(window_stacks):
    for scope in NEW_SCOPES:
        assert any(scope in s for s in window_stacks), scope
    # the DP model's scopes reach the force backward pass too
    for scope in NEW_SCOPES[4:]:
        assert any(re.search(rf"transpose\([^/]*{re.escape(scope)}", s)
                   for s in window_stacks), scope


def test_dp_scopes_lie_inside_the_inference_stage(window_stacks):
    inference = _reader("stage_ms.inference").SCOPES["inference"]
    dp = [s for s in window_stacks if re.search(r"dp\.", s)]
    assert dp and all(re.search(inference, s) for s in dp)


def test_classical_stage_still_matches_window_ops(window_stacks):
    classical = _reader("stage_ms.classical").SCOPES["classical"]
    assert sum(bool(re.search(classical, s)) for s in window_stacks) > 10


def test_engine_scopes_match_no_claimed_stage(window_stacks):
    engine = _reader("stage_ms.engine").SCOPES["engine"]
    ops = [s for s in window_stacks if re.search(engine, s)]
    assert ops
    for metric in STAGES:
        for pat in _reader(metric).SCOPES.values():
            assert not any(re.search(pat, s) for s in ops), (metric, pat)


@pytest.mark.parametrize("stack,label", [
    ("jit_run_window/jit(run_window)/while/body/closed_call/md.integrate/"
     "jit(<lambda>)/add", "engine"),
    ("jit_run_window/jit(run_window)/while/body/closed_call/md.rebuild_check"
     "/sub", "engine"),
    ("jit_run_window/jit(run_window)/while/body/closed_call/jit(evaluate)/"
     "obs.inference/transpose(jvp(dp.embedding))/dot_general", "inference"),
    ("jit_run_window/jit(run_window)/while/body/closed_call/md.classical/"
     "jit(_classical_one)/mul", "classical"),
    ("jit_run_window/jit(run_window)/while/body/closed_call/md.neighbor/"
     "cond/branch_1_fun/jit(cell_list_neighbor_list)/sort", "classical"),
    ("jit_run_window/jit(run_window)/while/body/closed_call/md.neighbor/"
     "cond/branch_1_fun/select_n", "other"),
])
def test_each_op_is_credited_to_one_stage(stack, label):
    """The harness's scope merge, in BENCHMARK.json order, credits the new
    scopes' operations where the metrics expect them."""
    scopes = _scopes()
    events = {"host": [], "devices": {"/device:TPU:0": [
        ["fusion.1", 0.0, 1000.0, stack]]}}
    r = reduce_events(events, scopes, ())
    credited = {k for k, v in r["scope_s"].items() if v}
    assert credited == ({label} if label != "other" else set())


def _registry(**gauges):
    from repro.obs import Registry
    reg = Registry()
    for name, value in gauges.items():
        reg.gauge(name).set(value)
    return reg


RUN = {"md.run.steps": 150, "md.run.windows": 15, "md.run.host_reads": 120,
       "md.run.span_s.md.window": 30.0, "md.run.span_s.md.verdict": 0.3,
       "md.run.span_s.md.rebuild": 0.6}


@pytest.mark.parametrize("metric,want", [
    ("host_ms.verdict", 2.0), ("host_ms.rebuild", 4.0),
    ("host_syncs_per_window", 8.0)])
def test_host_readers(metric, want):
    read = _reader(metric).read
    reg = _registry(**RUN)
    assert read({"steps": 150, "registry": reg}) == pytest.approx(want)
    # the totals of another run (the warm-up) are not this run's
    assert read({"steps": 30, "registry": reg}) is None
    # a program that publishes nothing: no reading, no error
    assert read({"steps": 150, "registry": _registry()}) is None


def test_engine_stage_reader():
    read = _reader("stage_ms.engine").read
    trace = {"scope_s": {"engine": 0.015}}
    assert read({"trace": trace, "steps": 150}) == pytest.approx(0.1)
    assert read({"trace": {"scope_s": {"engine": 0.0}}, "steps": 150}) is None
    assert read({"trace": None, "steps": 150}) is None
