"""One run of one benchmark cell: set-up, the measured window, the check.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or metric is
a file of its own under this directory, found by name:

* ``workloads/<cell>.json``: the configuration, the traffic mix, the chips
  and the limits of the comparison that decides ``correct``;
* ``configs/<config>.json``: the model widths and precision, the system
  builder and its sizes, the force field, the decomposition;
* ``systems/<builder>.py`` and ``references/<reference>.py``: the
  configuration's inputs and its plain reference;
* ``traffic/<mix>.json``: time step, skins, list cadence, initial
  temperature (NVE: no thermostat);
* ``metrics/<metric>.py``: the reader of one per-layer metric;
* ``costs/<kernel>.py``: operations and bytes from shapes;
* ``peaks.json``: the chip's peaks by ``device_kind``.

The timed entry is ``MDEngine.run`` in scan mode with a
``DeepmdForceProvider`` as the special force: ``ForcePipeline`` over a
one-rank owner_full decomposition, DPA-1, the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import logging
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from .check import (compare, judge, near, previous_positions,
                    reference_forces)
from .trace_reduce import WINDOW_SPAN, extract, op_names, reduce_events

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".perfbench_cache" / "jax"


# --------------------------------------------------------------------------
# discovery by name
# --------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path

    def module(self, kind: str, name: str):
        return load_module(self.bench_dir / kind / f"{name}.py")

    def reader(self, metric: str):
        """The reader of a per-layer metric: ``metrics/<name>.py``."""
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_dir: Path = BENCH,
              benchmark: dict | None = None) -> Cell:
    """The cell's files, checked against its entry in ``BENCHMARK.json``."""
    if benchmark is None:
        benchmark = load_json(bench_dir.parent / "BENCHMARK.json")
    wl_path = bench_dir / "workloads" / f"{name}.json"
    if not wl_path.exists():
        raise SystemExit(f"perfbench: no workload file {wl_path}")
    wl = load_json(wl_path)
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None or any(entry[k] != wl[k]
                            for k in ("config", "traffic", "chips")):
        raise SystemExit(f"perfbench: {name} in BENCHMARK.json does not "
                         f"match {wl_path}")
    return Cell(name, wl,
                load_json(bench_dir / "configs" / f"{wl['config']}.json"),
                load_json(bench_dir / "traffic" / f"{wl['traffic']}.json"),
                [m for m in benchmark["end_to_end"] if _applies(m, name)],
                [m for m in benchmark["per_layer"] if _applies(m, name)],
                bench_dir)


# --------------------------------------------------------------------------
# seeds and compile accounting
# --------------------------------------------------------------------------

def seed31(seed: int) -> int:
    """A 31-bit PRNG seed from any whole number (seeds may exceed 32
    bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) \
        & 0x7FFFFFFF


class CompileLog(logging.Handler):
    """Names of the programs JAX compiles (or loads) while attached."""

    def __init__(self):
        super().__init__()
        self.names: list = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ", 2)[1])


def enable_cache(jax) -> None:
    """Persistent compilation cache at a fixed path inside the checkout, so
    that only a cell's first run there compiles."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    spec: dict
    params: object
    provider: object
    engine: object
    state0: object


def make_params(cell: Cell, ref_mod):
    """The DPA-1 weights of the configuration (its ``weights_seed``), drawn
    on the device in one jitted call.  They are the trained model of an MD
    run: fixed, while the run's seed draws the initial velocities.  (The
    engine compiles the weights into its window program as constants, so
    weights that changed with the seed would recompile it in every run.)"""
    import jax
    m = cell.config["model"]
    key = jax.random.PRNGKey(seed31(cell.config["weights_seed"]))
    return jax.jit(lambda k: ref_mod.init_params(k, m))(key)


def build_program(cell: Cell, seed: int, ref_mod,
                  traced: bool = False) -> Program:
    import jax
    import jax.numpy as jnp
    from repro.core import DeepmdForceProvider, suggest_config
    from repro.dp import DescriptorConfig, DPConfig, DPModel
    from repro.launch.mesh import make_dd_mesh
    from repro.md import EngineConfig, MDEngine
    from repro.md.forcefield import ForceFieldConfig
    from repro.md.system import (System, Topology, build_exclusions,
                                 mark_nn_group)
    from repro.obs import ObsConfig

    cfg, tr = cell.config, cell.traffic
    sysc = dict(cfg["system"])
    spec = cell.module("systems", sysc.pop("builder")).build(**sysc)
    nn = spec["nn_idx"]
    in_group = np.zeros(len(spec["types"]), bool)
    in_group[nn] = True
    for key in ("bonds", "angles"):
        if len(spec[key]) and not in_group[spec[key]].all():
            raise ValueError(f"{key} outside the DP group: the reference "
                             "has no bonded terms")
    n = len(spec["types"])

    def terms(idx, width, params):
        if len(idx):
            return (jnp.asarray(idx), jnp.asarray(np.tile(params, (len(idx), 1)),
                                                  jnp.float32),
                    jnp.ones(len(idx), jnp.float32))
        return (jnp.zeros((1, width), jnp.int32),
                jnp.zeros((1, len(params)), jnp.float32),
                jnp.zeros(1, jnp.float32))

    b, bp, bm = terms(spec["bonds"], 2, [0.15, 25000.0])
    a, ap, am = terms(spec["angles"], 3, [1.91, 300.0])
    topo = Topology(bonds=b, bond_params=bp, bond_mask=bm, angles=a,
                    angle_params=ap, angle_mask=am,
                    dihedrals=jnp.zeros((1, 4), jnp.int32),
                    dihedral_params=jnp.zeros((1, 3), jnp.float32),
                    dihedral_mask=jnp.zeros(1, jnp.float32),
                    exclusions=jnp.asarray(build_exclusions(
                        n, spec["bonds"], spec["angles"])))
    system = mark_nn_group(System(
        box=jnp.asarray(spec["box"]), types=jnp.asarray(spec["types"]),
        masses=jnp.asarray(spec["masses"]),
        charges=jnp.asarray(spec["charges"]),
        lj_sigma=jnp.asarray(spec["lj_sigma"]),
        lj_epsilon=jnp.asarray(spec["lj_epsilon"]), topology=topo,
        nn_mask=jnp.zeros(n, jnp.float32)), nn)

    m = cfg["model"]
    desc = DescriptorConfig(
        kind=m["kind"], rcut=m["rcut"], rcut_smth=m["rcut_smth"],
        sel=m["sel"], ntypes=m["ntypes"], neuron=tuple(m["neuron"]),
        axis_neuron=m["axis_neuron"], type_embed_dim=m["type_embed_dim"],
        attn_layers=m["attn_layers"], attn_hidden=m["attn_hidden"],
        attn_heads=m["attn_heads"], use_pallas=m["use_pallas"])
    model = DPModel(DPConfig(descriptor=desc,
                             fitting_neuron=tuple(m["fitting_neuron"]),
                             dtype=m["dtype"]))
    params = make_params(cell, ref_mod)
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            w.shape != g.shape for w, g in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not match the layout "
                         "of DPModel.init_params")

    dd = cfg["decomposition"]
    ddc = suggest_config(len(nn), spec["box"], dd["ranks"], m["rcut"],
                         nbr_capacity=m["sel"], force_mode=dd["force_mode"],
                         nbr_method=dd["nbr_method"],
                         use_pallas=m["use_pallas"],
                         coords=spec["positions"][nn], skin=tr["dd_skin"])
    provider = DeepmdForceProvider(model, params, nn, system.types,
                                   system.box, n, dd_config=ddc,
                                   mesh=make_dd_mesh(dd["ranks"]))
    ff = cfg["forcefield"]
    ecfg = EngineConfig(
        dt=tr["dt_ps"], cutoff=ff["cutoff"], skin=tr["classical_skin"],
        neighbor_capacity=ff["neighbor_capacity"],
        rebuild_every=tr["rebuild_every"], thermostat_t=None,
        loop_mode="scan",
        ff=ForceFieldConfig(cutoff=ff["cutoff"],
                            use_reaction_field=ff["reaction_field"],
                            eps_rf=ff["eps_rf"], use_pme=False))

    class Engine(MDEngine):
        """Keeps each window's end time and last-step energies (the host
        boundary hook; the compiled windows are the engine's own)."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.window_ends = []
            self.last_energies = None

        def _post_segment(self, state, e_cl, e_sp, i):
            self.window_ends.append(time.perf_counter())
            self.last_energies = (e_cl, e_sp)
            return super()._post_segment(state, e_cl, e_sp, i)

    # traced runs add host spans to the profile; the compiled windows are
    # the same (no device counters)
    obs = ObsConfig(enabled=True, counters=False, calibrate=False) \
        if traced else None
    engine = Engine(system, ecfg, special_force=provider, obs=obs)
    state0 = engine.init_state(jnp.asarray(spec["positions"]),
                               temperature=tr["temperature"],
                               seed=seed31(seed))
    return Program(spec, params, provider, engine, state0)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def _device_info(jax, devices) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _counts(program: Program, positions) -> tuple[dict, object]:
    """The decomposition's counts at ``positions``, and its state there."""
    st = program.provider.assemble(positions)
    c = program.provider.dd_config
    return {"local": int(st.local_count), "ghost": int(st.ghost_count),
            "overflow": int(st.overflow),
            "local_capacity": c.local_capacity * c.n_ranks,
            "ghost_capacity": c.ghost_capacity * c.n_ranks,
            "n_subcells": int(np.prod(c.subcell_dims)) * c.n_ranks}, st


def _window_stacks(program: Program, state, sp_state) -> dict:
    """Name stacks of the scan window's operations, from the compiled text
    of the window program that just ran (served from the compile cache)."""
    eng = program.engine
    fn = eng._window_fn(eng.config.rebuild_every)
    compiled = fn.lower(state, eng.build_nlist(state.positions),
                        sp_state if eng._stateful else None).compile()
    return {"jit_run_window": op_names(compiled.as_text())}


def prepare(name: str, bench_dir: Path = BENCH, benchmark: dict | None = None,
            require_tpu: bool = True):
    """The cell, its devices, the chip's peaks and the reference module.
    Exits when JAX finds no TPU, too few chips, or a chip without peaks."""
    import jax
    cell = load_cell(name, bench_dir, benchmark)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"perfbench: no TPU (JAX sees {devices[0].platform})"
                         "; nothing measured")
    if len(devices) < cell.workload["chips"]:
        raise SystemExit(f"perfbench: {name} needs {cell.workload['chips']} "
                         f"chips, JAX sees {len(devices)}")
    devices = devices[:cell.workload["chips"]]
    peaks = load_json(bench_dir / "peaks.json")
    if require_tpu and devices[0].device_kind not in peaks:
        raise SystemExit(f"perfbench: no peaks for {devices[0].device_kind}")
    enable_cache(jax)
    return (cell, devices, peaks.get(devices[0].device_kind),
            cell.module("references", cell.config["reference"]))


def measure(cell: Cell, devices, ref_mod, seed: int, seconds: float,
            trace: bool, t_start: float) -> dict:
    """Set-up, warm-up and the measured ``MDEngine.run`` call.  The result
    holds the final state on the host and what the check needs; the
    program's device state is released before it returns."""
    import jax
    tr = cell.traffic
    re_every = tr["rebuild_every"]
    with jax.default_matmul_precision(cell.config["precision"]):
        prog = build_program(cell, seed, ref_mod, traced=trace)
        t_built = time.perf_counter()
        eng = prog.engine
        state = eng.run(prog.state0, tr["warmup_windows"] * re_every)
        jax.block_until_ready(state.positions)
        warm = dict(eng.diagnostics)
        t_win = eng.window_ends[-1] - eng.window_ends[-2]
        n_windows = max(1, round(seconds / t_win))
        n_steps = n_windows * re_every
        step0 = int(state.step)
        setup_s = time.perf_counter() - t_start

        eng.reset()
        # what set-up left behind is collected now and kept out of the
        # collector's later passes, so that a collection inside the window
        # walks only what the window itself allocates
        gc.collect()
        gc.freeze()
        compiles = CompileLog()
        jax_log = logging.getLogger("jax")
        jax_log.addHandler(compiles)
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace \
            else None
        try:
            with jax.log_compiles(True):
                if trace:
                    jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    t1 = time.perf_counter()
                    state = eng.run(state, n_steps)
                    jax.block_until_ready(state.positions)
                    wall = time.perf_counter() - t1
                if trace:
                    jax.profiler.stop_trace()
        finally:
            jax_log.removeHandler(compiles)
            gc.unfreeze()
        diag = dict(eng.diagnostics)
        device = _device_info(jax, devices)
        counts, sp_state = _counts(prog, state.positions)
        stacks = _window_stacks(prog, state, sp_state) if trace else {}
    out = {"setup_s": setup_s, "build_s": t_built - t_start,
           "n_steps": n_steps, "n_windows": n_windows, "wall": wall, "t_win": t_win, "warm": warm, "diag": diag,
           "device": device, "counts": counts,
           "energies": tuple(float(e) for e in eng.last_energies),
           "fin": {k: np.asarray(getattr(state, k))
                   for k in ("positions", "velocities", "forces")},
           "steps_done": int(state.step) - step0, "spec": prog.spec,
           "params": prog.params, "compiles": compiles.names,
           "trace_dir": trace_dir, "stacks": stacks}
    del prog, eng, state, sp_state
    gc.collect()
    return out


LIMITS_EXACT = {"not_finite": 0, "dd_overflow": 0, "capacity_growths": 0,
                "compiles_in_window": 0, "steps_missing": 0}


def check(cell: Cell, ref_mod, m: dict) -> dict:
    """The numbers compared against the reference, and the reference."""
    tr = cell.traffic
    fin, spec = m["fin"], m["spec"]
    x_prev, unresolved, ambiguous = previous_positions(
        fin["positions"], fin["velocities"], spec["box"], tr["dt_ps"])
    # atoms whose forces depend on an ambiguous position: DP atoms within
    # 2 r_c of an ambiguous DP atom, any atom within the classical cutoff
    dp = np.zeros(len(x_prev), bool)
    dp[spec["nn_idx"]] = True
    skip = (near(x_prev, ambiguous & dp, spec["box"],
                 2 * cell.config["model"]["rcut"])
            | near(x_prev, ambiguous, spec["box"],
                   cell.config["forcefield"]["cutoff"]))
    ref = reference_forces(ref_mod, m["params"], cell.config, spec, x_prev)
    e_cl, e_dp = m["energies"]
    numbers = compare(fin["forces"], e_dp, e_cl, ref, spec["nn_idx"], skip)
    growths = sum(len(d["capacity_growths"]) + d["special_growths"]
                  + d["window_reruns"] for d in (m["warm"], m["diag"]))
    numbers.update({
        "not_finite": int(not all(np.isfinite(v).all()
                                  for v in fin.values())),
        "dd_overflow": m["counts"]["overflow"],
        "capacity_growths": growths,
        "compiles_in_window": len(m["compiles"]),
        "steps_missing": m["n_steps"] - m["steps_done"]})
    return {"numbers": numbers, "ref": ref, "x_prev": x_prev,
            "unresolved": unresolved, "skip": skip}


def layer_metrics(cell: Cell, m: dict, peak: dict) -> tuple[dict, dict]:
    """Per-layer metrics from the trace, counters and costs, and the trace's
    busy and window times with its breakdown."""
    readers = {x["name"]: cell.reader(x["name"]) for x in cell.per_layer}
    scopes, kernels = {}, []
    for r in readers.values():
        scopes.update(getattr(r, "SCOPES", {}))
        kernels += [k for k in getattr(r, "KERNELS", ()) if k not in kernels]
    reduced = reduce_events(extract(m["trace_dir"], m["stacks"]), scopes,
                            tuple(kernels))
    shutil.rmtree(m["trace_dir"], ignore_errors=True)
    ctx = {"trace": reduced, "steps": m["n_steps"], "wall_s": m["wall"],
           "counts": m["counts"], "model": cell.config["model"],
           "peak": peak, "dp_atoms": len(m["spec"]["nn_idx"]),
           "cost": lambda k: cell.module("costs", k)}
    metrics = {}
    for x in cell.per_layer:
        v = readers[x["name"]].read(ctx)
        if v is not None:
            metrics[x["name"]] = {"value": v, "unit": x["unit"]}
    trace = {"busy_s": reduced["busy_s"] if reduced else 0.0,
             "window_s": reduced["window_s"] if reduced else m["wall"]}
    if reduced:
        trace["breakdown"] = {"device_ops": reduced["device_ops"],
                              "idle_gaps": reduced["idle_gaps"]}
    return metrics, trace


def run(name: str, seed: int, seconds: float, trace: bool, *,
        bench_dir: Path = BENCH, benchmark: dict | None = None,
        require_tpu: bool = True, t_start: float | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell, devices, peak, ref_mod = prepare(name, bench_dir, benchmark,
                                           require_tpu)
    m = measure(cell, devices, ref_mod, seed, seconds, trace, t_start)
    c = check(cell, ref_mod, m)
    correct, table = judge(c["numbers"],
                           {**cell.workload["limits"], **LIMITS_EXACT})
    device = m["device"]
    if trace:
        metrics, tr = layer_metrics(cell, m, peak)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
    else:
        ns = (m["n_steps"] * cell.traffic["dt_ps"] * 1e-3 / m["wall"]
              * 86400.0)
        values = {"ns_per_day": ns, "setup_s": m["setup_s"]}
        metrics = {x["name"]: {"value": values[x["name"]], "unit": x["unit"]}
                   for x in cell.end_to_end}
    e_cl, e_dp = m["energies"]
    info = {"cell": name, "seed": seed, "steps": m["n_steps"],
            "windows": m["n_windows"], "window_wall_s": m["wall"],
            "warmup_window_s": m["t_win"], "setup_s": m["setup_s"],
            "build_s": m["build_s"],
            "counts": m["counts"], "unresolved_coordinates": c["unresolved"],
            "atoms_skipped": int(c["skip"].sum()),
            "energies": {"dp": e_dp, "dp_ref": c["ref"]["e_dp"],
                         "classical": e_cl,
                         "classical_ref": c["ref"]["e_cl"]},
            "compiled_in_window": m["compiles"]}
    print("perfbench: " + json.dumps(info), file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": m["n_steps"],
              "failed": 0 if correct else m["n_steps"], "metrics": metrics,
              "device": device}
    if trace and "breakdown" in tr:
        result["breakdown"] = tr["breakdown"]
    result["checks"] = table
    for k, v in table.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def _finite_json(obj):
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite_json(v) for v in obj]
    return obj


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_start=t_start)
    print(json.dumps(_finite_json(result)), flush=True)
    return 0
