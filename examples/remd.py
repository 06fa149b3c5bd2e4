"""Replica-exchange MD (parallel tempering) over the ensemble subsystem.

R replicas of a solvated protein run as ONE jitted batched program —
classical forces, DP inference and the integrator all carry a leading
replica axis — with a temperature-ladder Metropolis exchange move at
window boundaries.  With ``--ranks`` > 1 the DP force path additionally
distributes over a 2-D (replica x dd) mesh of replicas * ranks devices.

  python examples/remd.py --replicas 4 --steps 40 --exchange-interval 5
  JAX_PLATFORMS=cpu python examples/remd.py --replicas 2 --ranks 4 \
      --temp-ladder 280,340
(run from the repo root; on the CPU the mesh's host devices are created
here, on a TPU the mesh may not exceed the chips this process sees)
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--replicas", type=int, default=4,
                help="replica count R (the new scaling dimension)")
ap.add_argument("--exchange-interval", type=int, default=5,
                help="steps between exchange attempts; 0 disables REMD")
ap.add_argument("--temp-ladder", default=None,
                help="comma-separated ladder (len R), e.g. 300,330,365,400; "
                     "default: geometric between --tmin and --tmax")
ap.add_argument("--tmin", type=float, default=300.0)
ap.add_argument("--tmax", type=float, default=420.0)
ap.add_argument("--ranks", type=int, default=1,
                help="dd ranks per replica (devices = replicas * ranks when "
                     "> 1; 1 = vmapped single-domain DP)")
ap.add_argument("--steps", type=int, default=40)
ap.add_argument("--residues", type=int, default=12)
args = ap.parse_args()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import suggest_config  # noqa: E402
from repro.dp import DPModel, paper_dpa1_config  # noqa: E402
from repro.ensemble import (BatchedDeepmdProvider, EnsembleConfig,  # noqa: E402
                            EnsembleEngine, geometric_ladder,
                            make_ensemble_mesh)
from repro.launch.runtime import (enable_compile_cache,  # noqa: E402
                                  use_cpu_devices)
from repro.md import (EngineConfig, build_solvated_protein,  # noqa: E402
                      mark_nn_group)


def main():
    r = args.replicas
    if args.ranks > 1:
        use_cpu_devices(r * args.ranks)
    enable_compile_cache()
    temps = (tuple(float(t) for t in args.temp_ladder.split(","))
             if args.temp_ladder else geometric_ladder(args.tmin, args.tmax, r))
    if len(temps) != r:
        raise SystemExit(f"--temp-ladder has {len(temps)} rungs for "
                         f"{r} replicas")
    system, positions, nn_idx = build_solvated_protein(args.residues)
    system = mark_nn_group(system, nn_idx)
    print(f"{system.n_atoms} atoms, DP group {len(nn_idx)}, R={r} replicas, "
          f"ladder {tuple(round(t, 1) for t in temps)} K, "
          f"exchange every {args.exchange_interval or 'never'} steps")

    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    params = model.init_params(jax.random.PRNGKey(0))

    dd = mesh = None
    if args.ranks > 1:
        mesh = make_ensemble_mesh(r, args.ranks)
        dd = suggest_config(len(nn_idx), np.asarray(system.box), args.ranks,
                            0.6, nbr_capacity=48, slack=2.5,
                            force_mode="ghost_reduce",
                            coords=np.asarray(positions)[np.asarray(nn_idx)])
        print(f"2-D mesh (replica={r}, dd={args.ranks}), "
              f"virtual grid {dd.grid_dims}")
    provider = BatchedDeepmdProvider(model, params, nn_idx, system.types,
                                     system.box, system.n_atoms,
                                     n_replicas=r, dd_config=dd, mesh=mesh,
                                     nbr_capacity=48,
                                     skin=0.0 if dd is not None else 0.08)
    ens = EnsembleConfig(n_replicas=r, temps=temps,
                         exchange_interval=args.exchange_interval)
    eng = EnsembleEngine(system,
                         EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                      dt=0.0005, thermostat_t=temps[0]),
                         ens, special_force=provider)

    def observe(s, obs):
        t = ", ".join(f"{x:5.1f}" for x in obs["temperature"])
        print(f"  step {obs['step']:4d} ladder {obs['ladder'].tolist()} "
              f"T [{t}] K  E_dp {np.round(obs['e_special'], 2).tolist()}")

    state = eng.run(eng.init_state(positions), args.steps, observe=observe,
                    observe_every=args.exchange_interval or 10)
    d = eng.diagnostics
    if args.exchange_interval:
        rate = d["exchange_accepts"] / max(d["exchange_attempts"], 1)
        print(f"exchange: {d['exchange_accepts']}/{d['exchange_attempts']} "
              f"accepted ({100 * rate:.0f}%), per-pair "
              f"{d['pair_accepts'].tolist()}/{d['pair_attempts'].tolist()}")
    print("final ladder:", np.asarray(state.ladder).tolist(),
          "finite:", bool(jnp.isfinite(state.positions).all()))


if __name__ == "__main__":
    main()
