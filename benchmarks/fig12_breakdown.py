"""Paper Fig. 12: per-step phase breakdown of the distributed DP path.

The paper's ROCm trace shows >90% inference, <=10% force collective, ~0
coordinate broadcast.  Earlier versions of this benchmark timed a
hand-rolled single-rank pipeline with a ``f.sum(0)`` stand-in for the
force reduction; now the breakdown comes from the observability layer's
nested prefix probes (``ForcePipeline.build_phase_probes`` +
:func:`repro.obs.timed_prefix_phases`): each probe runs the *real* fused
the fused force pipeline truncated after one more phase
(gather ⊂ assembly ⊂ inference ⊂ force-reduction) on an 8-rank mesh,
and successive differences attribute the step time.  The last probe is the
fused production force step itself — measured, not modeled.  Runs in the calling
process, which must see 8 devices (on the CPU:
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

from .common import save_json

N_RANKS = 8


def run():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ForcePipeline, suggest_config
    from repro.dp import DPModel, paper_dpa1_config
    from repro.launch.mesh import make_dd_mesh
    from repro.obs import ObsConfig, Tracer, timed_prefix_phases

    rng = np.random.default_rng(0)
    n = 512
    box = np.array([5.0, 5.0, 5.0], np.float32)
    coords_h = rng.uniform(0, 5, (n, 3)).astype(np.float32)
    coords = jnp.asarray(coords_h)
    types = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    model = DPModel(paper_dpa1_config(ntypes=4, rcut=0.6, sel=48))
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = make_dd_mesh(N_RANKS)
    cfg = suggest_config(n, box, N_RANKS, 0.6, nbr_capacity=64, slack=2.5,
                         nbr_method="cells", coords=coords_h)

    tracer = Tracer(ObsConfig(enabled=True))
    probes = ForcePipeline(model, cfg, mesh, box, n).build_phase_probes()
    thunks = {k: (lambda fn=fn: fn(params, coords, types))
              for k, fn in probes.items()}
    phases = timed_prefix_phases(tracer, thunks, iters=3, warmup=1)

    # per-rank balance of the same fused step, from its own diag (the last
    # probe IS the fused force step — already compiled, reuse it)
    _, _, diag = probes["force_reduce"](params, coords, types)
    rank_cost = np.asarray(diag["rank_cost"], np.float64)

    tot = sum(phases.values())
    out = {
        "gather_s": phases["gather"],
        "assemble_s": phases["assembly"],
        "inference_s": phases["inference"],
        "reduce_s": phases["force_reduce"],
        "inference_share": phases["inference"] / tot,
        "rank_cost": rank_cost.tolist(),
        "cost_ratio": float(rank_cost.max() / max(rank_cost.mean(), 1e-12)),
    }
    save_json("fig12_breakdown", out)
    share = out["inference_share"]
    ratio = out["cost_ratio"]
    return [("fig12_inference_phase", out["inference_s"] * 1e6,
             f"inference share {share:.2%} (paper: ~90%)"),
            ("fig12_assemble_phase", out["assemble_s"] * 1e6,
             "coord gather + DD assembly"),
            ("fig12_reduce_phase", out["reduce_s"] * 1e6,
             f"force reduce; rank cost_ratio {ratio:.2f}")]
