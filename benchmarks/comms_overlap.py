"""Communication/computation overlap benchmark: sequential vs overlapped
evaluation of the distributed DP force path (8-rank mesh, 4096 atoms).

The overlapped evaluation (``DDConfig.overlap``) splits DP inference into
an interior pass issued *before* the halo all-gather — rows whose stale
neighbor lists reference only local atoms — and a boundary pass behind it,
then merges the two so the result stays bitwise-equal to the sequential
evaluation (the parity gate asserted here and in CI).  The benchmark
reports:

  seq        amortized sequential schedule (assemble once with skin, then
             per step: gather -> partition -> evaluate)
  overlap    same schedule with the interior pass scheduled against the
             all-gather

plus the measured interior fraction from the evaluation diagnostics
against the uniform-density prediction of
``repro.core.interior_fraction_estimate`` for a sweep of rank grids — the
planning number that says whether a given decomposition leaves enough
interior work to hide the gather (``DDConfig.overlap_min_interior``).

On the host-device CPU backend the collectives are memcpys, so the wall
clock mostly documents that the overlapped program costs no extra compute;
the interior-fraction sweep and the bitwise gate are the portable results.

Writes ``BENCH_comms_overlap.json``.

Usage:
  python -m benchmarks.comms_overlap              # full point (4096 atoms)
  python -m benchmarks.comms_overlap --smoke      # tiny point (CI)
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from .common import save_json, time_fn

DENSITY = 3.7          # atoms / nm^3 (water-ish NN-group density)
RCUT = 0.6
SKIN = 0.06
N_RANKS = 8
STEPS = 8              # steps per timed window


def _drift_sequence(coords: np.ndarray, box: np.ndarray, rng,
                    steps: int) -> np.ndarray:
    """Random walk keeping every atom inside the skin/2 reuse bound."""
    per_step = 0.35 * (SKIN / 2) / steps
    seq = []
    pos = coords.copy()
    for _ in range(steps):
        step = rng.normal(0, per_step, coords.shape)
        norm = np.linalg.norm(step, axis=1, keepdims=True)
        step *= np.minimum(1.0, per_step / np.maximum(norm, 1e-12))
        pos = np.mod(pos + step, box)
        seq.append(pos.copy())
    return np.stack(seq)


def run(smoke: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.core import (ForcePipeline, factor_grid,
                            interior_fraction_estimate, suggest_config)
    from repro.dp.descriptors import DescriptorConfig
    from repro.dp.model import DPConfig, DPModel
    from repro.launch.mesh import make_dd_mesh
    from repro.launch.runtime import require_devices

    require_devices(N_RANKS, "benchmarks.comms_overlap")

    n = 512 if smoke else 4096
    boxl = float((n / DENSITY) ** (1.0 / 3.0))
    box = np.array([boxl] * 3, np.float32)
    rng = np.random.default_rng(0)
    coords_h = rng.uniform(0, boxl, (n, 3)).astype(np.float32)
    coords = jnp.asarray(coords_h)
    types = jnp.asarray(rng.integers(0, 4, n), jnp.int32)

    model = DPModel(DPConfig(
        descriptor=DescriptorConfig(kind="dpse", rcut=RCUT,
                                    rcut_smth=RCUT - 0.3, sel=48, ntypes=4,
                                    neuron=(8, 16), axis_neuron=4),
        fitting_neuron=(32, 32)))
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = make_dd_mesh(N_RANKS)

    cfg = suggest_config(n, box, N_RANKS, RCUT, nbr_capacity=48, slack=2.0,
                         nbr_method="cells", coords=coords_h, skin=SKIN)
    pipe = ForcePipeline(model, cfg, mesh, box, n)
    asm = pipe.build_assembly_fn()
    ev_seq = pipe.build_evaluation_fn()
    cfg_ov = dataclasses.replace(cfg, overlap=True)
    ev_ov = ForcePipeline(model, cfg_ov, mesh, box, n).build_evaluation_fn()

    seq_h = _drift_sequence(coords_h, box, rng, STEPS)
    drift = jnp.asarray(seq_h)
    state0 = asm(coords, types)
    assert int(state0.overflow) == 0, "assembly overflow — raise slack"

    def window(ev):
        def win():
            f_last = None
            for t in range(STEPS):
                _, f_last, _ = ev(params, drift[t], state0)
            jax.block_until_ready(f_last)
        return win

    iters = 2 if smoke else 3
    t_seq = time_fn(window(ev_seq), warmup=1, iters=iters) / STEPS
    t_ov = time_fn(window(ev_ov), warmup=1, iters=iters) / STEPS

    # -- parity gate: bitwise energy AND forces, build + drifted positions --
    e0, f0, _ = ev_seq(params, coords, state0)
    e1, f1, d1 = ev_ov(params, coords, state0)
    bw_build = bool((f0 == f1).all()) and float(e0) == float(e1)
    e2, f2, _ = ev_seq(params, drift[-1], state0)
    e3, f3, _ = ev_ov(params, drift[-1], state0)
    bw_drift = bool((f2 == f3).all()) and float(e2) == float(e3)
    overflow = int(np.asarray(d1["overflow"]))
    interior_meas = float(np.asarray(d1["interior_frac"]))

    # -- interior-fraction sweep: uniform-density estimate per rank grid.
    # A row is gather-free when its whole r_list = rcut + skin shell is
    # locally resident — one list cutoff from the subdomain face, not the
    # (2-hop) halo_eff the ghost import uses.
    margin = cfg.halo_eff / cfg.halo_hops
    sweep = []
    for ranks in (1, 2, 4, 8, 16, 32, 64):
        dims = factor_grid(ranks, box)
        est = interior_fraction_estimate(box, dims, margin)
        sweep.append({"n_ranks": ranks, "grid_dims": list(dims),
                      "interior_frac_est": est})
    est_here = interior_fraction_estimate(box, cfg.grid_dims, margin)

    payload = {
        "n_atoms": n, "n_ranks": N_RANKS, "rcut": RCUT, "skin": SKIN,
        "steps_per_window": STEPS, "density": DENSITY,
        "model": "dpse(8,16)x(32,32)",
        "seq_eval_us": t_seq,
        "overlap_eval_us": t_ov,
        "overlap_vs_seq": t_seq / t_ov,
        "overflow": overflow,
        "bitwise_build": bw_build,
        "bitwise_drift": bw_drift,
        "interior_frac_measured": interior_meas,
        "interior_frac_estimate": est_here,
        "interior_sweep": sweep,
    }
    save_json("BENCH_comms_overlap", payload)
    assert overflow == 0, "overlap evaluation overflowed"
    assert bw_build and bw_drift, "overlap parity gate failed"
    return [
        ("comms_overlap_seq", t_seq, "baseline"),
        ("comms_overlap_on", t_ov,
         f"x{payload['overlap_vs_seq']:.2f} bitwise={bw_build and bw_drift}"),
        ("comms_overlap_interior", interior_meas * 1e6,
         f"measured={interior_meas:.3f} est={est_here:.3f}"),
    ]


if __name__ == "__main__":
    from repro.launch.runtime import use_cpu_devices
    use_cpu_devices(N_RANKS)
    for name, us, derived in run(smoke="--smoke" in sys.argv[1:]):
        print(f"{name},{us:.1f},{derived}")
