#!/usr/bin/env python3
"""Smoke run of the DP-MD main path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: the 4-rank path only

One process drives everything through the entry points a user calls:
``md.system.build_solvated_protein`` (1,024 residues, a 4,096-atom DP
group), DPA-1 at the paper's widths (``configs.dpa1_md.paper_config``:
r_c = 0.8 nm, sel = 64, embedding (32, 64, 128), 3 x 256 attention, 3 x 256
fitting net, fp32 policy, Pallas kernels) with random weights from a seed,
``core.DeepmdForceProvider`` over a virtual domain decomposition, and
``md.MDEngine``.

* Phase A (parity): energy and forces of the kernel path against the plain
  ``jnp`` single-domain reference, both under matmul precision "highest".
* Phase B (MD): ``MDEngine`` in scan mode with a Verlet skin, at least three
  windows and one decomposition rebuild, no capacity growth and no replay.
* Phase C (no interpreter): the compiled force step holds Mosaic custom
  calls for the env-matrix and attention-stack kernels.

With ``--chips 4`` only the 4-rank ``ForcePipeline`` runs: parity against
the one-device reference on the same frame, then a few MD steps.

Times printed here come from a smoke run and are not benchmark results.
The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.backend import ForceRequest  # noqa: E402
from repro.configs.dpa1_md import paper_config  # noqa: E402
from repro.core import DeepmdForceProvider, suggest_config  # noqa: E402
from repro.dp import DPModel  # noqa: E402
from repro.launch.mesh import make_dd_mesh  # noqa: E402
from repro.launch.runtime import enable_compile_cache  # noqa: E402
from repro.md import (EngineConfig, MDEngine,  # noqa: E402
                      brute_force_neighbor_list, build_solvated_protein,
                      mark_nn_group)

# Force RMSE over force RMS.  A single bf16 pass of the 256-wide matmuls
# carries ~4e-3 relative error per product, far above this bound.
FORCE_RTOL = 1e-4
# |E - E_ref| over sum_i |e_i| (the per-atom energies of the reference):
# fp32 sums of 4,096 terms in two orders differ by ~1e-6 of that scale.
ENERGY_RTOL = 1e-5
SEED = 0
N_RESIDUES = 1024
# kernels whose Mosaic custom calls must appear in the compiled force step
KERNELS = ("env_mat_fwd", "env_mat_bwd", "nbr_attn_stack_fwd",
           "nbr_attn_stack_bwd")


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Setup:
    """One solvated protein, its DP model and the provider over a mesh."""

    system: object
    positions: jax.Array
    nn_idx: np.ndarray
    model: DPModel
    params: dict
    provider: DeepmdForceProvider
    engine_cfg: EngineConfig


def build(n_residues: int, dp_cfg, n_ranks: int = 1,
          skin: float = 0.1) -> Setup:
    """The hybrid system, random-init DP parameters from ``SEED``, and a
    ``DeepmdForceProvider`` with an ``n_ranks`` owner_full decomposition
    (the paper's: a 2 r_c halo, no ghost-force reduction), skin-widened so
    the engine drives the assemble/evaluate split."""
    system, positions, nn_idx = build_solvated_protein(n_residues, seed=SEED)
    system = mark_nn_group(system, nn_idx)
    model = DPModel(dp_cfg)
    params = model.init_params(jax.random.PRNGKey(SEED))
    rcut = dp_cfg.descriptor.rcut
    dd = suggest_config(len(nn_idx), np.asarray(system.box), n_ranks, rcut,
                        nbr_capacity=dp_cfg.descriptor.sel,
                        force_mode="owner_full", nbr_method="cells",
                        use_pallas=dp_cfg.descriptor.use_pallas,
                        coords=np.asarray(positions)[nn_idx], skin=skin)
    mesh = make_dd_mesh(n_ranks)
    provider = DeepmdForceProvider(model, params, nn_idx, system.types,
                                   system.box, system.n_atoms,
                                   dd_config=dd, mesh=mesh)
    engine_cfg = EngineConfig(dt=0.0005, cutoff=1.2, skin=0.1,
                              rebuild_every=10, thermostat_t=300.0,
                              loop_mode="scan")
    return Setup(system, positions, nn_idx, model, params, provider,
                 engine_cfg)


def describe(setup: Setup) -> dict:
    dd = setup.provider.dd_config
    state = setup.provider.assemble(setup.positions)
    out = {"dp_atoms": len(setup.nn_idx),
           "all_atoms": int(setup.system.n_atoms),
           "ranks": dd.n_ranks, "force_mode": dd.force_mode,
           "local_count": int(state.local_count),
           "ghost_count": int(state.ghost_count),
           "local_capacity": dd.local_capacity,
           "ghost_capacity": dd.ghost_capacity,
           "subcell_capacity": dd.subcell_capacity,
           "k_eval": dd.k_eval, "skin": dd.skin}
    log(f"decomposition: {out}")
    return out


def nn_positions(setup: Setup) -> jax.Array:
    """DP-group positions wrapped into the box (identity unit conversion)."""
    return jnp.mod(setup.positions[setup.nn_idx], setup.provider.box_model)


def reference(setup: Setup):
    """Plain ``jnp`` single-domain reference (``use_pallas=False``):
    brute-force minimum-image list over the DP group, autodiff forces.
    Returns (energy, forces (N_dp, 3), sum_i |e_i|)."""
    cfg = setup.model.cfg
    ref_model = DPModel(dataclasses.replace(
        cfg, descriptor=dataclasses.replace(cfg.descriptor,
                                            use_pallas=False)))
    box = setup.provider.box_model
    nn_pos = nn_positions(setup)
    types = setup.provider.nn_types
    nl = brute_force_neighbor_list(nn_pos, box, cfg.descriptor.rcut,
                                   cfg.descriptor.sel)
    if bool(nl.overflow):
        raise RuntimeError("reference neighbour list overflowed sel")
    ones = jnp.ones(nn_pos.shape[0], nn_pos.dtype)
    e, f = ref_model.energy_and_forces(setup.params, nn_pos, types, nl.idx,
                                       nl.mask, ones, box=box)
    e_rows = ref_model._atomic_e(setup.params, nn_pos, types, nl.idx,
                                 nl.mask, box)
    return e, f, float(jnp.abs(e_rows).sum())


def _compare(e, f, e_ref, f_ref, e_scale) -> dict:
    f = np.asarray(f, np.float64)
    f_ref = np.asarray(f_ref, np.float64)
    rms = float(np.sqrt((f_ref ** 2).mean()))
    rmse = float(np.sqrt(((f - f_ref) ** 2).mean()))
    return {"force_rms": rms, "force_rmse": rmse,
            "force_rel": rmse / rms,
            "energy": float(e), "energy_ref": float(e_ref),
            "energy_rel": abs(float(e) - float(e_ref)) / e_scale,
            "finite": bool(np.isfinite(f).all() and np.isfinite(float(e)))}


def _check_parity(cmp: dict, what: str) -> None:
    ok = (cmp["finite"] and cmp["force_rel"] <= FORCE_RTOL
          and cmp["energy_rel"] <= ENERGY_RTOL)
    if not ok:
        raise AssertionError(
            f"{what}: force RMSE/RMS {cmp['force_rel']:.3e} (bound "
            f"{FORCE_RTOL:g}), |dE|/sum|e_i| {cmp['energy_rel']:.3e} (bound "
            f"{ENERGY_RTOL:g}), finite {cmp['finite']}")


def provider_forces(setup: Setup):
    """Energy and DP-group forces through ``DeepmdForceProvider.compute``,
    plus the decomposition diagnostics of that evaluation."""
    res = setup.provider.compute(ForceRequest(positions=setup.positions,
                                              box=setup.system.box))
    return res.energy, res.forces[setup.nn_idx], res.diagnostics


def phase_parity(setup: Setup) -> dict:
    """Phase A: the kernel path against the jnp reference on one frame,
    both under matmul precision "highest"."""
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        e, f, diag = provider_forces(setup)
        jax.block_until_ready(f)
        t_kernel = time.perf_counter() - t0
        e_ref, f_ref, e_scale = reference(setup)
    if diag.get("overflow"):
        raise AssertionError(f"decomposition overflowed: {diag}")
    cmp = _compare(e, f, e_ref, f_ref, e_scale)
    # information only (ROADMAP R1/S7): the production call at the
    # backend's default matmul precision against the same reference
    e_d, f_d, _ = provider_forces(setup)
    info = _compare(e_d, f_d, e_ref, f_ref, e_scale)
    log(f"phase A info: default-precision call, force RMSE/RMS "
        f"{info['force_rel']:.3e}, |dE|/sum|e_i| {info['energy_rel']:.3e}")
    log(f"phase A: first kernel-path call {t_kernel:.1f} s (compile "
        f"included); force RMSE/RMS {cmp['force_rel']:.3e} (bound "
        f"{FORCE_RTOL:g}), |dE|/sum|e_i| {cmp['energy_rel']:.3e} (bound "
        f"{ENERGY_RTOL:g}), E {cmp['energy']:.6f} vs {cmp['energy_ref']:.6f}")
    _check_parity(cmp, "phase A")
    return {**cmp, "default_precision": info, "first_call_s": t_kernel}


class _CompileLog(logging.Handler):
    """Collects the names of the programs JAX compiles while attached."""

    def __init__(self):
        super().__init__()
        self.names: list = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ", 2)[1])


def phase_md(setup: Setup, steps_per_window: int = 10,
             windows: int = 3) -> dict:
    """Phase B: ``MDEngine`` scan windows with DP forces from the provider.

    A warm-up run of two windows compiles the window program for both of
    its input layouts (fresh state, then a window's output) and sizes the
    classical neighbour list; the checked run that follows takes
    ``windows`` windows from the warm-up's end state."""
    cfg = dataclasses.replace(setup.engine_cfg,
                              rebuild_every=steps_per_window)
    eng = MDEngine(setup.system, cfg, special_force=setup.provider)
    state = eng.init_state(setup.positions, temperature=300.0)
    t0 = time.perf_counter()
    state = eng.run(state, 2 * steps_per_window)
    jax.block_until_ready(state.positions)
    t_warm = time.perf_counter() - t0
    warm = dict(eng.diagnostics)
    log(f"phase B warm-up, two windows: {t_warm:.1f} s (compile included), "
        f"diagnostics {warm}")
    eng.reset()
    n = steps_per_window * windows
    compiles = _CompileLog()
    jax_log = logging.getLogger("jax")
    jax_log.addHandler(compiles)
    t0 = time.perf_counter()
    try:
        with jax.log_compiles(True):
            state = eng.run(state, n)
            jax.block_until_ready(state.positions)
    finally:
        jax_log.removeHandler(compiles)
    wall = time.perf_counter() - t0
    diag = eng.diagnostics
    overflow = int(setup.provider.assemble(state.positions).overflow)
    finite = bool(jnp.isfinite(state.positions).all())
    dd_rebuilds = diag["cadence_rebuilds"] + diag["special_rebuilds"]
    out = {"steps": n, "windows": windows, "finite": finite,
           "overflow": overflow, "dd_rebuilds": dd_rebuilds,
           "wall_s": wall, "window_s": eng.timings["scan"] / windows,
           "warmup_s": t_warm, "compiles": compiles.names,
           "diagnostics": diag, "warmup": warm}
    log(f"phase B: {n} steps in {windows} scan windows, "
        f"{out['window_s'] * 1e3:.1f} ms per window (smoke run, not a "
        f"benchmark); finite {finite}, overflow {overflow}, DD rebuilds "
        f"{dd_rebuilds}, diagnostics {diag}")
    log(f"phase B: {wall:.1f} s wall for the run, {len(compiles.names)} "
        f"programs compiled during it: {compiles.names}")
    grew = any(d[k] for d in (diag, warm) for k in
               ("capacity_growths", "special_growths", "window_reruns"))
    if not finite or overflow or grew or dd_rebuilds < 1:
        raise AssertionError(f"phase B failed: {out}")
    return out


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def kernels_in(text: str) -> set:
    """Names of the Mosaic kernels among the HLO's ``tpu_custom_call``s."""
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {k for k in KERNELS + ("cell_filter",)
            if any(k in ln for ln in calls)}


def phase_kernels(setup: Setup) -> dict:
    """Phase C: compile the provider's force (evaluation) step and its
    assembly step; both must hold the kernels as Mosaic custom calls."""
    p = setup.provider
    nn_pos = nn_positions(setup)
    state = p.assemble(setup.positions)
    t0 = time.perf_counter()
    ev = p.pipeline.build_evaluation_fn().lower(p.params, nn_pos,
                                                state).compile()
    t_eval = time.perf_counter() - t0
    asm = p.pipeline.build_assembly_fn().lower(nn_pos, p.nn_types).compile()
    found = kernels_in(ev.as_text())
    found_asm = kernels_in(asm.as_text())
    out = {"eval_kernels": sorted(found),
           "assembly_kernels": sorted(found_asm), "eval_compile_s": t_eval,
           "eval_memory": _memory(ev), "assembly_memory": _memory(asm)}
    log(f"phase C: force step compiled in {t_eval:.1f} s; Mosaic kernels "
        f"{sorted(found)}; assembly kernels {sorted(found_asm)}")
    log(f"force step memory_analysis(): {out['eval_memory']}")
    log(f"assembly step memory_analysis(): {out['assembly_memory']}")
    missing = set(KERNELS) - found
    if missing:
        raise AssertionError(f"phase C: no tpu_custom_call for {missing}")
    return out


def phase_four_chips(n_residues: int, dp_cfg, n_ranks: int = 4) -> dict:
    """The ``n_ranks`` pipeline on ``make_dd_mesh(n_ranks)``: parity with
    the one-device jnp reference on the same frame, then MD steps."""
    setup = build(n_residues, dp_cfg, n_ranks=n_ranks)
    describe(setup)
    state = setup.provider.assemble(setup.positions)
    shards = {}
    for s in state.nbr_idx.addressable_shards:
        shards[str(s.device)] = shards.get(str(s.device), 0) + 1
    log(f"DDState.nbr_idx shards per device: {shards}")
    if len(shards) != n_ranks:
        raise AssertionError(f"state spans {len(shards)} devices")
    with jax.default_matmul_precision("highest"):
        e, f, diag = provider_forces(setup)
        e_ref, f_ref, e_scale = reference(setup)
    cmp = _compare(e, f, e_ref, f_ref, e_scale)
    log(f"4-rank parity vs one-device reference: force RMSE/RMS "
        f"{cmp['force_rel']:.3e}, |dE|/sum|e_i| {cmp['energy_rel']:.3e}")
    if diag.get("overflow"):
        raise AssertionError(f"decomposition overflowed: {diag}")
    _check_parity(cmp, "4-rank parity")
    md = phase_md(setup, steps_per_window=5, windows=2)
    return {"shards": shards, **cmp, "md": md}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing ran",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) seen", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    dp_cfg = paper_config(ntypes=4, sel=64, dtype="float32", use_pallas=True)
    if args.chips == 4:
        phase_four_chips(N_RESIDUES, dp_cfg)
    else:
        setup = build(N_RESIDUES, dp_cfg)
        describe(setup)
        phase_parity(setup)
        phase_md(setup)
        phase_kernels(setup)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
