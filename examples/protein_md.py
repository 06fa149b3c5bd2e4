"""End-to-end driver (the paper's production scenario): domain-decomposed,
multi-device DP-aided MD of a solvated protein with checkpoint/restart.

This is the serving workload of the paper — every MD step performs batched
distributed DP inference (two collectives: coordinate all-gather + force
reduction) through the virtual-DD layer on a ``--ranks`` mesh.

  python examples/protein_md.py --steps 30              # every device seen
  JAX_PLATFORMS=cpu python examples/protein_md.py --ranks 8 --steps 30
(run from the repo root; on the CPU ``--ranks`` sets the host device
count, on a TPU it may not exceed the chips this process sees)
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--ranks", type=int, default=0,
                help="dd ranks (0 = every device this process sees)")
ap.add_argument("--steps", type=int, default=30)
ap.add_argument("--residues", type=int, default=16)
ap.add_argument("--force-mode", default="owner_full",
                choices=["owner_full", "ghost_reduce"])
ap.add_argument("--nbr-method", default="cells", choices=["cells", "dense"],
                help="subdomain assembly: cell list (linear) or dense oracle")
ap.add_argument("--balanced", action="store_true")
ap.add_argument("--ckpt-dir", default=None)
args = ap.parse_args()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import DDConfig, DeepmdForceProvider, suggest_config  # noqa: E402
from repro.dp import DPModel, paper_dpa1_config  # noqa: E402
from repro.launch.mesh import make_dd_mesh  # noqa: E402
from repro.launch.runtime import (enable_compile_cache,  # noqa: E402
                                  use_cpu_devices)
from repro.md import (EngineConfig, MDEngine, build_solvated_protein,  # noqa: E402
                      mark_nn_group)
from repro.md.observables import gyration_radii_axes  # noqa: E402


def main():
    use_cpu_devices(args.ranks)
    enable_compile_cache()
    args.ranks = args.ranks or len(jax.devices())
    mesh = make_dd_mesh(args.ranks)   # fails if --ranks exceeds the devices
    system, positions, nn_idx = build_solvated_protein(args.residues)
    system = mark_nn_group(system, nn_idx)
    print(f"{system.n_atoms} atoms, DP group {len(nn_idx)}, "
          f"{args.ranks} ranks, force_mode={args.force_mode}")

    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    params = model.init_params(jax.random.PRNGKey(0))

    dd = suggest_config(len(nn_idx), np.asarray(system.box), args.ranks,
                        0.6, nbr_capacity=48, slack=2.5,
                        balanced=args.balanced, force_mode=args.force_mode,
                        nbr_method=args.nbr_method,
                        coords=np.asarray(positions)[np.asarray(nn_idx)])
    print(f"virtual DD grid {dd.grid_dims}, halo {dd.halo:.2f} nm, "
          f"capacities local={dd.local_capacity} ghost={dd.ghost_capacity}, "
          f"assembly={dd.nbr_method}")

    provider = DeepmdForceProvider(model, params, nn_idx, system.types,
                                   system.box, system.n_atoms,
                                   dd_config=dd, mesh=mesh)
    eng = MDEngine(system,
                   EngineConfig(cutoff=0.9, neighbor_capacity=96, dt=0.0005,
                                thermostat_t=200.0,
                                checkpoint_every=10 if args.ckpt_dir else 0,
                                checkpoint_path=args.ckpt_dir),
                   special_force=provider)
    state = eng.init_state(positions, 200.0)
    sel = jnp.asarray(np.asarray(system.nn_mask))

    def observe(s, obs):
        rg = np.asarray(gyration_radii_axes(s.positions, system.masses, sel))
        diag = provider.last_diag
        extra = ""
        if diag is not None:
            extra = (f" ghosts={int(diag['ghost_count'])}"
                     f" overflow={int(diag['overflow'])}")
        print(f"  step {obs['step']:4d} E_dp {obs['e_special']:9.3f} "
              f"T {obs['temperature']:5.1f}K Rg {rg.round(3)}{extra}")

    state = eng.run(state, args.steps, observe=observe, observe_every=5)
    print("final positions finite:", bool(jnp.isfinite(state.positions).all()))


if __name__ == "__main__":
    main()
