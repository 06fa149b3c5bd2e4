#!/usr/bin/env python
"""End-to-end observability smoke: a short instrumented 8-rank MD run.

Forces 8 host devices, runs a solvated-protein MD trajectory with the
distributed Deep-Potential provider under ``ObsConfig(enabled=True)``
(fused-scan windows, so per-step dd counters come out of ``lax.scan``),
then:

* writes + re-reads the JSONL event log (schema-validated both ways),
* writes the Chrome-trace (Perfetto) view,
* prints the ``trace_report`` rendering (phase table, per-rank
  imbalance, step counters) and the run's ``md.run.*`` registry gauges.

The committed ``experiments/traces/example_8rank_trace.jsonl`` is this
script's output; CI runs it fresh on every push and uploads the artifact.
"""
from __future__ import annotations

import argparse
import os
import sys

# 8 simulated dd ranks — must be set before jax initializes
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

N_RANKS = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--outdir", default=os.path.join("experiments", "traces"))
    ap.add_argument("--name", default="example_8rank_trace")
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.core import DeepmdForceProvider, suggest_config
    from repro.dp import DPModel, paper_dpa1_config
    from repro.launch.mesh import make_dd_mesh
    from repro.md import (EngineConfig, MDEngine, build_solvated_protein,
                          mark_nn_group)
    from repro.obs import ObsConfig, Tracer, report

    assert len(jax.devices()) >= N_RANKS, (
        f"need {N_RANKS} devices, got {len(jax.devices())} — XLA_FLAGS was "
        "set after jax initialized?")

    system, pos, nn_idx = build_solvated_protein(6, water_per_protein_atom=1.5)
    system = mark_nn_group(system, nn_idx)
    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=32))
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = make_dd_mesh(N_RANKS)
    # ghost_reduce: the protein box is too small for the owner_full halo
    dd = suggest_config(len(nn_idx), np.asarray(system.box), N_RANKS, 0.6,
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
    print(f"running {args.steps} instrumented steps on {N_RANKS} ranks ...")
    eng.run(eng.init_state(pos, 200.0), args.steps)
    gauges = tracer.registry.snapshot()["gauges"]
    print("run totals:", {k: g["value"] for k, g in sorted(gauges.items())
                          if k.startswith("md.run.")})

    os.makedirs(args.outdir, exist_ok=True)
    jsonl = os.path.join(args.outdir, args.name + ".jsonl")
    chrome = os.path.join(args.outdir, args.name + ".chrome.json")
    tracer.flush(jsonl)          # schema-validated on write
    tracer.chrome_trace(chrome)

    events = report.load(jsonl)  # re-read + re-validate
    n_steps = sum(1 for e in events if e.get("type") == "step")
    assert n_steps == args.steps, (n_steps, args.steps)
    assert any("rank_cost" in e for e in events
               if e.get("type") == "step"), "dd counters missing"
    print(f"\nwrote {jsonl} ({len(events)} events) and {chrome}\n")
    print(report.render(events))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
