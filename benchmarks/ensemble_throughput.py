"""Ensemble throughput: steps*replica/s vs replica count at fixed devices.

The paper caps strong scaling at ~40% on 32 devices (load imbalance + the
Eq.-8 ghost floor), so past ~16 devices extra hardware buys more from more
*trajectories* than from more ranks per trajectory.  This benchmark
measures that trade on a fixed 8-device set, comparing three schedules for
stepping R replicas through the distributed DP force path:

  looped        the pre-ensemble baseline: R sequential dispatches of the
                unbatched dd-8 driver (R all-gathers + R reductions/step)
  batched_vmap  one jitted call on a (replica=1, dd=8) mesh: identical
                per-replica decomposition, but all R replicas ride ONE
                batched all-gather + ONE batched reduction
  batched_mesh  a (replica=R, dd=8/R) mesh: replicas run concurrently on
                device groups with fewer dd ranks each — less ghost
                overhead per replica (Eq. 8), full device utilization

Writes ``BENCH_ensemble.json`` with per-R step times and steps*replica/s;
the acceptance figure is ``speedup_batched_r4`` (best batched vs looped at
R=4) >= 1.5.

Usage:
  python -m benchmarks.ensemble_throughput              # full (4096 atoms)
  python -m benchmarks.ensemble_throughput --smoke      # tiny point (CI)
"""
from __future__ import annotations

import sys

import numpy as np

from .common import save_json, time_fn

DENSITY = 3.7
RCUT = 0.6
N_DEV = 8
R_VALUES = (2, 4, 8)


def run(smoke: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.core import ForcePipeline, suggest_config
    from repro.dp.descriptors import DescriptorConfig
    from repro.dp.model import DPConfig, DPModel
    from repro.ensemble import make_ensemble_mesh
    from repro.launch.mesh import make_dd_mesh
    from repro.launch.runtime import require_devices

    require_devices(N_DEV, "benchmarks.ensemble_throughput")

    n = 512 if smoke else 4096
    r_values = (2, 4) if smoke else R_VALUES
    boxl = float((n / DENSITY) ** (1.0 / 3.0))
    box = np.array([boxl] * 3, np.float32)
    rng = np.random.default_rng(0)
    coords_h = rng.uniform(0, boxl, (max(r_values), n, 3)).astype(np.float32)
    types = jnp.asarray(rng.integers(0, 4, n), jnp.int32)

    model = DPModel(DPConfig(
        descriptor=DescriptorConfig(kind="dpse", rcut=RCUT,
                                    rcut_smth=RCUT - 0.3, sel=48, ntypes=4,
                                    neuron=(8, 16), axis_neuron=4),
        fitting_neuron=(32, 32)))
    params = model.init_params(jax.random.PRNGKey(0))

    def cfg_for(p):
        return suggest_config(n, box, p, RCUT, nbr_capacity=48, slack=2.0,
                              nbr_method="cells", coords=coords_h[0])

    cfg8 = cfg_for(N_DEV)
    fused8 = ForcePipeline(model, cfg8, make_dd_mesh(N_DEV), box,
                           n).build_force_fn()
    iters = 2 if smoke else 3
    rows, points = [], []
    for r in r_values:
        coords = jnp.asarray(coords_h[:r])

        def looped(coords=coords, r=r):
            f = None
            for k in range(r):
                _, f, _ = fused8(params, coords[k], types)
            jax.block_until_ready(f)

        bf_vmap = ForcePipeline(model, cfg8, make_ensemble_mesh(1, N_DEV),
                                box, n, n_replicas=r).build_force_fn()

        def batched_vmap(coords=coords, bf=bf_vmap):
            jax.block_until_ready(bf(params, coords, types)[1])

        dd_per = N_DEV // r
        bf_mesh = ForcePipeline(model, cfg_for(dd_per),
                                make_ensemble_mesh(r, dd_per),
                                box, n, n_replicas=r).build_force_fn()

        def batched_mesh(coords=coords, bf=bf_mesh):
            jax.block_until_ready(bf(params, coords, types)[1])

        # a timed configuration that overflows its static capacities would
        # silently truncate neighbor/ghost sets — refuse to record it
        overflow = int(np.asarray(
            fused8(params, coords[0], types)[2]["overflow"]).max())
        for bf in (bf_vmap, bf_mesh):
            overflow = max(overflow, int(np.asarray(
                bf(params, coords, types)[2]["overflow"]).max()))
        assert overflow == 0, f"capacity overflow at R={r}"

        t_loop = time_fn(looped, warmup=1, iters=iters)
        t_vmap = time_fn(batched_vmap, warmup=1, iters=iters)
        t_mesh = time_fn(batched_mesh, warmup=1, iters=iters)
        t_best = min(t_vmap, t_mesh)
        point = {
            "replicas": r, "dd_per_replica_mesh": dd_per, "overflow": overflow,
            "looped_us": t_loop, "batched_vmap_us": t_vmap,
            "batched_mesh_us": t_mesh,
            "looped_steps_replica_per_s": r / (t_loop * 1e-6),
            "batched_steps_replica_per_s": r / (t_best * 1e-6),
            "speedup_batched": t_loop / t_best,
        }
        points.append(point)
        rows.append((f"ensemble_r{r}_looped", t_loop / r, "baseline"))
        rows.append((f"ensemble_r{r}_batched", t_best / r,
                     f"x{point['speedup_batched']:.2f}"))

    at4 = [p for p in points if p["replicas"] == 4]
    payload = {
        "n_atoms": n, "n_devices": N_DEV, "rcut": RCUT, "density": DENSITY,
        "model": "dpse(8,16)x(32,32)", "points": points,
        "speedup_batched_r4": at4[0]["speedup_batched"] if at4 else None,
    }
    save_json("BENCH_ensemble", payload)
    return rows


if __name__ == "__main__":
    from repro.launch.runtime import use_cpu_devices
    use_cpu_devices(N_DEV)
    for name, us, derived in run(smoke="--smoke" in sys.argv[1:]):
        print(f"{name},{us:.1f},{derived}")
