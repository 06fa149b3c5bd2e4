"""Observability subsystem: registry/histogram correctness, trace schema
round-trips, zero-overhead-when-disabled guarantees, tracer parity with
uninstrumented runs, and per-step dd counters under scan windows (8-rank
subprocess) and the replica-batched ensemble driver."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.core import DeepmdForceProvider
from repro.dp import DPModel, paper_dpa1_config
from repro.md import (EngineConfig, MDEngine, build_solvated_protein,
                      mark_nn_group)
from repro.obs import (Counter, Gauge, Histogram, ObsConfig, Registry,
                       Tracer, export, report)
from repro.obs.trace import _NULL_SPAN

# -- registry ---------------------------------------------------------------


def test_histogram_quantiles_match_numpy(rng):
    """Log-binned quantiles must track exact quantiles within the bin
    width (8 bins/octave => ~4.4% relative error; allow 2 bins)."""
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    h = Histogram(lo=1e-6)
    for s in samples:
        h.observe(s)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        approx = h.quantile(q)
        assert abs(approx - exact) / exact < 0.20, (q, approx, exact)
    assert h.count == len(samples)
    assert np.isclose(h.sum, samples.sum())
    assert np.isclose(h.mean(), samples.mean())


def test_histogram_degenerate_and_clamped():
    h = Histogram()
    assert h.quantile(0.5) == 0.0 and h.snapshot()["count"] == 0
    h.observe(3.0)
    # single observation: every quantile is the exact value (min/max clamp)
    assert h.quantile(0.0) == h.quantile(0.5) == h.quantile(0.99) == 3.0


def test_registry_create_on_use_and_reset():
    r = Registry()
    r.counter("steps").inc()
    r.counter("steps").inc(4)
    r.gauge("depth").set(3)
    r.gauge("depth").set(1)
    r.histogram("lat").observe(0.5)
    snap = r.snapshot()
    assert snap["counters"]["steps"] == 5
    assert snap["gauges"]["depth"] == {"value": 1, "peak": 3}
    assert snap["histograms"]["lat"]["count"] == 1
    assert isinstance(r.counter("steps"), Counter)
    assert isinstance(r.gauge("depth"), Gauge)
    r.reset()
    assert r.snapshot()["counters"] == {}


# -- export schema ----------------------------------------------------------


def test_jsonl_round_trip(tmp_path):
    events = [
        {"type": "meta", "kind": "run", "n_steps": 4},
        {"type": "span", "name": "scan_window", "ts": 0.1, "dur": 0.05,
         "phase": "scan", "steps": 4},
        {"type": "instant", "name": "xla_capture_start", "ts": 0.2},
        {"type": "step", "step": 0, "rank_cost": [3, 4], "cost_ratio": 1.1,
         "rebuild": False},
    ]
    path = str(tmp_path / "events.jsonl")
    export.write_jsonl(events, path)
    back = export.read_jsonl(path)
    assert back == events
    export.validate_events(back)


def test_jsonl_rejects_bad_events(tmp_path):
    for bad in [{"name": "no type"},
                {"type": "span", "name": "x"},          # missing ts/dur
                {"type": "step"},                        # missing step
                {"type": "wat", "name": "x"}]:
        with pytest.raises(ValueError):
            export.write_jsonl([bad], str(tmp_path / "bad.jsonl"))


def test_chrome_trace_schema(tmp_path):
    events = [
        {"type": "meta", "engine": "MDEngine"},
        {"type": "span", "name": "scan_window", "ts": 0.1, "dur": 0.05,
         "phase": "scan", "tid": 0},
        {"type": "instant", "name": "mark", "ts": 0.11},
    ]
    path = str(tmp_path / "trace.json")
    export.write_chrome_trace(events, path)
    with open(path) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "scan_window"
    assert xs[0]["dur"] == pytest.approx(0.05 * 1e6)  # microseconds
    assert any(e["ph"] == "i" for e in evs)
    assert all({"ph", "pid", "ts"} <= set(e) for e in evs
               if e["ph"] != "M")


# -- disabled mode: hard no-op ----------------------------------------------


def test_disabled_tracer_is_noop():
    tr = Tracer(None)
    assert not tr.enabled and not tr.wants_counters
    assert tr.span("anything", phase="x") is _NULL_SPAN  # shared object
    with tr.span("anything"):
        pass
    tr.meta(kind="run")
    tr.instant("mark")
    tr.count("host_reads")
    tr.record_window(0, 4, {"c": jnp.zeros(4)})
    tr.record_step(0, {"c": 1})
    assert tr.events == []
    assert tr.run_totals() == ({}, {})
    assert tr.flush() is None
    assert not tr.start_capture()


def test_ensure_coercion():
    cfg = ObsConfig(enabled=True)
    tr = Tracer(cfg)
    assert Tracer.ensure(tr) is tr
    assert Tracer.ensure(cfg).enabled
    assert not Tracer.ensure(None).enabled


# -- engine integration (single device) -------------------------------------


@pytest.fixture(scope="module")
def small_md():
    system, pos, nn_idx = build_solvated_protein(5, water_per_protein_atom=1.5)
    system = mark_nn_group(system, nn_idx)
    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    params = model.init_params(jax.random.PRNGKey(0))

    def provider():
        return DeepmdForceProvider(model, params, nn_idx, system.types,
                                   system.box, system.n_atoms,
                                   nbr_capacity=48, skin=0.08)
    return system, pos, provider


_CFG = dict(cutoff=0.9, neighbor_capacity=96, dt=0.0005, thermostat_t=200.0)


def test_instrumented_run_bitwise_equals_uninstrumented(small_md):
    """Core guarantee: turning the tracer on must not change the physics.
    With counters threaded through the scan the trajectory must stay
    bitwise identical — the counters are outputs, never inputs."""
    system, pos, provider = small_md
    runs = {}
    for tag, obs in [("off", None), ("on", ObsConfig(enabled=True))]:
        eng = MDEngine(system, EngineConfig(**_CFG),
                       special_force=provider(), obs=obs)
        runs[tag] = (eng.run(eng.init_state(pos, 200.0), 10), eng)
    st_off, _ = runs["off"]
    st_on, eng_on = runs["on"]
    assert (np.asarray(st_off.positions) == np.asarray(st_on.positions)).all()
    assert (np.asarray(st_off.velocities)
            == np.asarray(st_on.velocities)).all()
    steps = [e for e in eng_on.tracer.events if e["type"] == "step"]
    assert [e["step"] for e in steps] == list(range(10))
    spans = [e for e in eng_on.tracer.events if e["type"] == "span"]
    assert {e["name"] for e in spans} == {"md.window", "md.verdict",
                                          "md.rebuild"}
    assert [e["why"] for e in spans if e["name"] == "md.rebuild"] == [
        "build"]


def test_step_mode_spans_and_records(small_md, tmp_path):
    system, pos, provider = small_md
    trace_dir = str(tmp_path / "trace")
    eng = MDEngine(system, EngineConfig(loop_mode="step", **_CFG),
                   special_force=provider(),
                   obs=ObsConfig(enabled=True, trace_dir=trace_dir))
    eng.run(eng.init_state(pos, 200.0), 6)
    phases = {e.get("phase") for e in eng.tracer.events
              if e["type"] == "span"}
    assert {"neighbor", "classical", "inference", "integrate"} <= phases
    steps = [e for e in eng.tracer.events if e["type"] == "step"]
    assert len(steps) == 6
    # run() auto-flushed into trace_dir; the log must be loadable
    events = report.load(trace_dir + "/events.jsonl")
    assert report.counter_summary(events)["n_steps"] == 6
    assert report.phase_table(events)  # non-empty


def test_timings_reset_per_run_and_reset_api(small_md):
    """Satellite: repeated run() calls must not silently accumulate."""
    system, pos, provider = small_md
    eng = MDEngine(system, EngineConfig(**_CFG), special_force=provider())
    st = eng.run(eng.init_state(pos, 200.0), 6)
    t1 = dict(eng.timings)
    assert t1["scan"] > 0
    eng.run(st, 6)
    # second run rewrites, not adds: the warm run must come in *below* the
    # cold run's scan bucket (which paid compilation), not above it
    assert eng.timings["scan"] < t1["scan"]
    eng.reset()
    assert all(v == 0.0 for v in eng.timings.values())
    assert eng.diagnostics["displacement_rebuilds"] == 0
    assert eng.tracer.events == []


def test_step_counters_cleared_between_runs(small_md):
    """Regression (satellite): back-to-back run() calls must not leak the
    first run's per-step device-counter records into the second trace.
    Restarting from a fresh state would otherwise duplicate absolute step
    numbers; continuing the same trajectory would mix two runs' counters."""
    system, pos, provider = small_md
    eng = MDEngine(system, EngineConfig(**_CFG),
                   special_force=provider(), obs=ObsConfig(enabled=True))
    eng.run(eng.init_state(pos, 200.0), 6)
    assert len([e for e in eng.tracer.events if e["type"] == "step"]) == 6
    # restart from step 0: without clearing, steps 0..5 would appear twice
    eng.run(eng.init_state(pos, 200.0), 4)
    steps = [e["step"] for e in eng.tracer.events if e["type"] == "step"]
    assert steps == list(range(4))
    # spans/meta survive the per-run clear (two run meta events recorded)
    metas = [e for e in eng.tracer.events
             if e["type"] == "meta" and e.get("kind") == "run"]
    assert len(metas) == 2


# -- run-loop host spans, blocking reads and per-run gauges ----------------


@pytest.fixture(scope="module")
def traced_engine(small_md):
    """One engine, tracing on as the benchmark's traced runs set it (no
    device counters), reporting into a registry of its own."""
    system, pos, provider = small_md
    registry = Registry()
    tracer = Tracer(ObsConfig(enabled=True, counters=False,
                              calibrate=False), registry=registry)
    eng = MDEngine(system, EngineConfig(**_CFG), special_force=provider(),
                   obs=tracer)
    assert eng._stateful and eng.config.rebuild_every == 10
    return eng, eng.init_state(pos, 200.0), registry


@pytest.mark.parametrize("windows", [1, 2, 3])
def test_traced_scan_run_publishes_its_totals(traced_engine, windows):
    """Each run overwrites the ``md.run.*`` gauges with its own totals; a
    window of ``rebuild_every`` steps makes 8 blocking reads: the step
    reads of the run loop and of the segment, two verdict flags, two
    diagnostics sums and the overflow checks of the list build and the
    DD assembly at the window's start."""
    eng, state, registry = traced_engine
    eng.run(state, 10 * windows)
    g = {k: v["value"] for k, v in registry.snapshot()["gauges"].items()}
    assert g["md.run.steps"] == 10 * windows
    assert g["md.run.windows"] == windows
    assert g["md.run.host_reads"] == 8 * windows
    for name in ("md.window", "md.verdict", "md.rebuild"):
        assert g[f"md.run.span_s.{name}"] > 0
    assert g["md.run.span_s.md.window"] > g["md.run.span_s.md.verdict"]
    span_s, counts = eng.tracer.run_totals()
    assert counts == {"host_reads": 8 * windows}
    assert span_s["md.verdict"] == g["md.run.span_s.md.verdict"]


def test_disabled_engine_records_counts_and_publishes_nothing(small_md):
    system, pos, provider = small_md
    registry = Registry()
    tracer = Tracer(None, registry=registry)
    eng = MDEngine(system, EngineConfig(**_CFG), special_force=provider(),
                   obs=tracer)
    eng.run(eng.init_state(pos, 200.0), 10)
    assert tracer.events == []
    assert tracer.run_totals() == ({}, {})
    assert registry.snapshot() == {"counters": {}, "gauges": {},
                                   "histograms": {}}


def test_step_mode_publishes_its_totals(small_md):
    """The per-step host loop publishes the same gauges: its reads are
    counted through the same door (one rebuild check and one needs-rebuild
    flag per step, and the per-step counter records)."""
    system, pos, provider = small_md
    registry = Registry()
    eng = MDEngine(system, EngineConfig(loop_mode="step", **_CFG),
                   special_force=provider(),
                   obs=Tracer(ObsConfig(enabled=True), registry=registry))
    eng.run(eng.init_state(pos, 200.0), 4)
    g = {k: v["value"] for k, v in registry.snapshot()["gauges"].items()}
    assert g["md.run.steps"] == 4 and g["md.run.windows"] == 1
    # 2 build overflow checks, 3 step reads (run loop, segment, attempt),
    # then per step: rebuild check, needs-rebuild flag, overflow flag and
    # the counter record
    assert g["md.run.host_reads"] == 2 + 3 + 4 * 4
    assert g["md.run.span_s.md.window"] == 0.0


# -- dd counters under scan windows and the ensemble driver (8 ranks) -------

_DD_OBS_CODE = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import DeepmdForceProvider, suggest_config
from repro.dp import DPModel, paper_dpa1_config
from repro.launch.mesh import make_dd_mesh
from repro.md import (EngineConfig, MDEngine, build_solvated_protein,
                      mark_nn_group)
from repro.obs import ObsConfig, Tracer

system, pos, nn_idx = build_solvated_protein(6, water_per_protein_atom=1.5)
system = mark_nn_group(system, nn_idx)
model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
params = model.init_params(jax.random.PRNGKey(0))
mesh = make_dd_mesh(8)
dd = suggest_config(len(nn_idx), np.asarray(system.box), 8, 0.6,
                    nbr_capacity=48, slack=2.5, skin=0.04,
                    force_mode="ghost_reduce",
                    coords=np.asarray(pos)[np.asarray(nn_idx)])
prov = DeepmdForceProvider(model, params, nn_idx, system.types,
                           system.box, system.n_atoms, dd_config=dd,
                           mesh=mesh)
tracer = Tracer(ObsConfig(enabled=True))
eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                    dt=0.0005, thermostat_t=200.0),
               special_force=prov, obs=tracer)
state = eng.run(eng.init_state(pos, 200.0), 6)

# ground truth: the provider's own evaluation diag at the final positions
e, f, fl = prov.evaluate(state.positions, prov.assemble(state.positions))
truth = {k: np.asarray(v).tolist() for k, v in fl["counters"].items()}

steps = [e for e in tracer.events if e["type"] == "step"]
out = {
    "n_steps": len(steps),
    "step_ids": [e["step"] for e in steps],
    "keys": sorted(steps[-1].keys()),
    "rank_cost_last": steps[-1]["rank_cost"],
    "cost_max_last": steps[-1]["cost_max"],
    "local_last": steps[-1]["local_count"],
    "ghost_last": steps[-1]["ghost_count"],
    "occupancy": [e["nbr_occupancy"] for e in steps],
    "truth_local": truth["local_count"],
    "truth_rank_cost": truth["rank_cost"],
}
print("JSON" + json.dumps(out))
"""


def test_dd_counters_through_scan_windows():
    """Per-step dd counters recorded out of fused scan windows must be
    internally consistent and match the provider's direct diag."""
    stdout = run_in_subprocess(_DD_OBS_CODE, n_devices=8)
    out = json.loads([l for l in stdout.splitlines()
                      if l.startswith("JSON")][0][4:])
    assert out["n_steps"] == 6
    assert out["step_ids"] == list(range(6))
    for key in ("rank_cost", "cost_max", "cost_ratio", "nbr_occupancy",
                "local_count", "ghost_count", "rebuild", "sp_rebuild"):
        assert key in out["keys"], (key, out["keys"])
    rc = np.asarray(out["rank_cost_last"])
    assert rc.shape == (8,)
    assert rc.sum() == out["local_last"] + out["ghost_last"]
    assert rc.max() == out["cost_max_last"]
    assert all(0 < o <= 1 for o in out["occupancy"])
    # dt is tiny and the skin absorbed all motion: the decomposition at the
    # final state matches the recorded final-step counters
    assert out["truth_local"] == out["local_last"]
    assert out["truth_rank_cost"] == out["rank_cost_last"]


_ENSEMBLE_OBS_CODE = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import suggest_config
from repro.dp import DPModel, paper_dpa1_config
from repro.ensemble import (BatchedDeepmdProvider, EnsembleConfig,
                            EnsembleEngine)
from repro.md import EngineConfig, build_solvated_protein, mark_nn_group
from repro.obs import ObsConfig, Tracer

R, P = 2, 4
system, pos, nn_idx = build_solvated_protein(6, water_per_protein_atom=1.5)
system = mark_nn_group(system, nn_idx)
model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
params = model.init_params(jax.random.PRNGKey(0))
mesh = Mesh(np.array(jax.devices()[:R * P]).reshape(R, P),
            ("replica", "dd"))
dd = suggest_config(len(nn_idx), np.asarray(system.box), P, 0.6,
                    nbr_capacity=48, slack=2.5, skin=0.04,
                    force_mode="ghost_reduce",
                    coords=np.asarray(pos)[np.asarray(nn_idx)])
prov = BatchedDeepmdProvider(model, params, nn_idx, system.types,
                             system.box, system.n_atoms, n_replicas=R,
                             dd_config=dd, mesh=mesh)
tracer = Tracer(ObsConfig(enabled=True))
eng = EnsembleEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                          dt=0.0005),
                     EnsembleConfig(n_replicas=R, temps=(200.0, 230.0)),
                     special_force=prov, obs=tracer)
eng.run(eng.init_state(pos), 4)
steps = [e for e in tracer.events if e["type"] == "step"]
out = {
    "n_steps": len(steps),
    "rank_cost_shape": np.asarray(steps[-1]["rank_cost"]).shape,
    "local_last": steps[-1]["local_count"],
    "rank_cost_last": steps[-1]["rank_cost"],
}
print("JSON" + json.dumps(out))
"""


@pytest.mark.slow
def test_ensemble_dd_counters_on_replica_mesh():
    """(replica x dd) mesh: step records carry (R, P) rank_cost and (R,)
    per-replica counters."""
    stdout = run_in_subprocess(_ENSEMBLE_OBS_CODE, n_devices=8)
    out = json.loads([l for l in stdout.splitlines()
                      if l.startswith("JSON")][0][4:])
    assert out["n_steps"] == 4
    assert tuple(out["rank_cost_shape"]) == (2, 4)
    rc = np.asarray(out["rank_cost_last"])
    loc = np.asarray(out["local_last"])
    assert loc.shape == (2,)
    # every (replica, step) sample: rank costs sum to local+ghost atoms
    imb = report.imbalance_table(
        [{"type": "step", "step": 0, "rank_cost": rc.tolist()}])
    assert imb["n_samples"] == 2 and len(imb["ranks"]) == 4


# -- serve metrics on the shared registry -----------------------------------


def test_tenant_metrics_latency_quantiles():
    from repro.serve.metrics import MetricsRegistry
    obs = Registry()
    mr = MetricsRegistry(window_s=5.0, obs_registry=obs)
    for lat in (0.001, 0.002, 0.004, 0.100):
        mr.update("sim0", "submit")
    for lat in (0.001, 0.002, 0.004, 0.100):
        mr.update("sim0", "complete", lat)
    s = mr.snapshot()["sim0"]
    assert s["completed"] == 4 and s["queue_depth"] == 0
    assert s["mean_latency_s"] == pytest.approx(0.02675, rel=1e-6)
    assert s["p50_latency_s"] == pytest.approx(0.002, rel=0.10)
    assert s["p99_latency_s"] == pytest.approx(0.100, rel=0.10)
    assert s["max_latency_s"] == 0.100
    # the same histogram is visible in the shared obs registry
    snap = obs.snapshot()
    assert snap["histograms"]["serve.latency_s.sim0"]["count"] == 4
    assert snap["gauges"]["serve.queue_depth"]["peak"] == 4
    assert snap["gauges"]["serve.queue_depth"]["value"] == 0
