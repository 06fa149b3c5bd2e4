"""Amortized decomposition benchmark: per-step rebuild vs skin-reuse vs
scan-fused evaluation of the distributed DP force path (8-rank mesh).

Three schedules over the same drifting-positions sequence (a bounded random
walk staying inside the skin/2 reuse bound):

  per_step    the paper's schedule — full assembly pipeline (binning,
              ghost/local selection, subdomain neighbor list) every call,
              one host round-trip per step
  reuse       assemble once with ``DDConfig.skin``, then per step: psum'd
              displacement check + evaluation phase only (host loop)
  scan_fused  same reuse split, but the whole step window runs as one
              jitted ``lax.scan`` (displacement check + ``lax.cond``
              rebuild + evaluation fused; single host sync per window)

Writes ``BENCH_dd_reuse.json`` with per-mode step times, the speedup of
each amortized mode over per-step rebuild, and a bitwise reuse-parity
record (stale-state evaluation vs fresh assembly at drifted positions).

The DP model is a small DP-SE config: the quantity under test is assembly
amortization, which is model-independent; a small fitting stack keeps the
assembly:inference ratio near what large-scale runs see after the paper's
own inference-side optimizations.

Usage:
  python -m benchmarks.dd_reuse              # full point (4096 atoms)
  python -m benchmarks.dd_reuse --smoke      # tiny point (CI)
"""
from __future__ import annotations

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
    """Random walk with every atom's total displacement < skin/2."""
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


def _parity_drift(coords: np.ndarray, box: np.ndarray, halo_eff: float,
                  rng, amp: float = 1e-4, margin: float = 1e-3) -> np.ndarray:
    """Bounded drift that freezes atoms near selection-critical boundaries.

    Reuse is bitwise-equal to fresh assembly exactly when the local/ghost
    *sets* are unchanged (the within-cutoff pair set is handled by the
    evaluation-phase compaction).  Atoms whose coordinates sit within
    ``margin`` of a subdomain plane or a halo face (planes +- halo_eff,
    periodic) could flip set membership under any drift, so they stay put —
    everything else moves by up to ``amp`` (well inside the skin bound).
    """
    crit = []
    for L in box:
        planes = np.array([0.0, L / 2])          # uniform 2-per-axis grid
        crit.append(np.concatenate([planes, planes - halo_eff,
                                    planes + halo_eff]) % L)
    frozen = np.zeros(len(coords), bool)
    for a in range(3):
        d = np.abs(coords[:, a][:, None] - crit[a][None, :])
        d = np.minimum(d, box[a] - d)            # periodic distance
        frozen |= (d < margin).any(1)
    step = rng.uniform(-amp, amp, coords.shape)
    step[frozen] = 0.0
    return np.mod(coords + step, box).astype(np.float32)


def run(smoke: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.core import ForcePipeline, suggest_config
    from repro.dp.descriptors import DescriptorConfig
    from repro.dp.model import DPConfig, DPModel
    from repro.launch.mesh import make_dd_mesh
    from repro.launch.runtime import require_devices

    require_devices(N_RANKS, "benchmarks.dd_reuse")

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

    cfg0 = suggest_config(n, box, N_RANKS, RCUT, nbr_capacity=48, slack=2.0,
                          nbr_method="cells", coords=coords_h)
    cfgS = suggest_config(n, box, N_RANKS, RCUT, nbr_capacity=48, slack=2.0,
                          nbr_method="cells", coords=coords_h, skin=SKIN)

    fused = ForcePipeline(model, cfg0, mesh, box, n).build_force_fn()
    pipeS = ForcePipeline(model, cfgS, mesh, box, n)
    asm = pipeS.build_assembly_fn()
    ev = pipeS.build_evaluation_fn()

    seq_h = _drift_sequence(coords_h, box, rng, STEPS)
    seq = jnp.asarray(seq_h)
    state0 = asm(coords, types)
    assert int(state0.overflow) == 0, "assembly overflow — raise slack"

    # -- mode 1: per-step full rebuild (the paper's schedule) --------------
    def per_step():
        f_last = None
        for t in range(STEPS):
            _, f_last, _ = fused(params, seq[t], types)
        jax.block_until_ready(f_last)

    # -- mode 2: skin-reuse, host loop (one dispatch per no-rebuild step:
    # the displacement check rides along in the evaluation diagnostics;
    # when it fires the stale result is discarded and recomputed fresh)
    @jax.jit
    def reuse_step(st, pos):
        e, f, diag = ev(params, pos, st)

        def rebuilt(p, s):
            s2 = asm(p, types)
            e2, f2, _ = ev(params, p, s2)
            return s2, e2, f2

        return jax.lax.cond(diag["needs_rebuild"], rebuilt,
                            lambda p, s: (s, e, f), pos, st)

    def reuse():
        st = state0
        f_last = None
        for t in range(STEPS):
            st, _, f_last = reuse_step(st, seq[t])
        jax.block_until_ready(f_last)

    # -- mode 3: skin-reuse, window fused into one lax.scan ----------------
    @jax.jit
    def scan_window(st, positions):
        def body(carry, pos):
            st, acc = carry
            st, e, f = reuse_step(st, pos)
            return (st, acc + f), e

        (st, acc), es = jax.lax.scan(body, (st, jnp.zeros_like(coords)),
                                     positions)
        return acc, es

    def scan_fused():
        acc, es = scan_window(state0, seq)
        jax.block_until_ready(acc)

    # -- observability overhead: the same fused window with the tracer's
    # per-step counter record threaded out of the scan.  ``want=False``
    # threads an empty dict — the traced program must be identical to the
    # uninstrumented window (the <2%-overhead acceptance bar); ``want=True``
    # carries the dd counters and pays one device_get per window.
    from repro.obs import ObsConfig, Tracer
    OBS_COUNTERS = ("local_count", "ghost_count", "cost_max", "cost_ratio",
                    "rank_cost", "nbr_occupancy")

    def make_obs_window(want: bool):
        @jax.jit
        def win(st, positions):
            def body(carry, pos):
                st, acc = carry
                e, f, diag = ev(params, pos, st)

                def rebuilt(p, s):
                    s2 = asm(p, types)
                    e2, f2, d2 = ev(params, p, s2)
                    return s2, e2, f2, d2

                st, e, f, diag = jax.lax.cond(
                    diag["needs_rebuild"], rebuilt,
                    lambda p, s: (s, e, f, diag), pos, st)
                rec = {k: diag[k] for k in OBS_COUNTERS} if want else {}
                return (st, acc + f), (e, rec)

            (st, acc), (es, recs) = jax.lax.scan(
                body, (st, jnp.zeros_like(coords)), positions)
            return acc, es, recs
        return win

    tracer = Tracer(ObsConfig(enabled=True))
    win_off = make_obs_window(False)
    win_on = make_obs_window(True)

    def obs_off():
        acc, es, _ = win_off(state0, seq)
        jax.block_until_ready(acc)

    def obs_on():
        acc, es, recs = win_on(state0, seq)
        jax.block_until_ready(acc)
        tracer.record_window(0, STEPS, recs)   # the host transfer is part
        #   of the measured cost: one device_get per window, never per step

    # -- guard-seam overhead: the same fused window with the in-scan health
    # check (nonfinite forces/positions, the GuardConfig.enabled seam)
    # OR-reduced into a single window flag fetched at the boundary — the
    # <2%-overhead acceptance bar for guarded execution
    @jax.jit
    def guard_window(st, positions):
        def body(carry, pos):
            st, acc, tripped = carry
            st, e, f = reuse_step(st, pos)
            trip = ~(jnp.isfinite(f).all() & jnp.isfinite(pos).all())
            return (st, acc + f, tripped | trip), e

        (st, acc, tripped), es = jax.lax.scan(
            body, (st, jnp.zeros_like(coords), jnp.zeros((), bool)),
            positions)
        return acc, es, tripped

    def scan_guard():
        acc, es, tripped = guard_window(state0, seq)
        jax.block_until_ready(acc)
        assert not bool(tripped)

    iters = 2 if smoke else 3
    t_per_step = time_fn(per_step, warmup=1, iters=iters) / STEPS
    t_reuse = time_fn(reuse, warmup=1, iters=iters) / STEPS
    t_scan = time_fn(scan_fused, warmup=1, iters=iters) / STEPS
    t_obs_off = time_fn(obs_off, warmup=1, iters=iters) / STEPS
    t_obs_on = time_fn(obs_on, warmup=1, iters=iters) / STEPS
    t_guard = time_fn(scan_guard, warmup=1, iters=iters) / STEPS

    # -- reuse parity: stale state vs fresh assembly at drifted positions --
    c1 = jnp.asarray(_parity_drift(coords_h, box, cfgS.halo_eff, rng))
    _, f_stale, diag = ev(params, c1, state0)
    _, f_fresh, _ = ev(params, c1, asm(c1, types))
    bitwise = bool((f_stale == f_fresh).all())
    max_df = float(jnp.abs(f_stale - f_fresh).max())

    payload = {
        "n_atoms": n, "n_ranks": N_RANKS, "rcut": RCUT, "skin": SKIN,
        "steps_per_window": STEPS, "density": DENSITY,
        "model": "dpse(8,16)x(32,32)",
        "per_step_rebuild_us": t_per_step,
        "skin_reuse_us": t_reuse,
        "scan_fused_us": t_scan,
        "speedup_reuse": t_per_step / t_reuse,
        "speedup_scan_fused": t_per_step / t_scan,
        "scan_obs_off_us": t_obs_off,
        "scan_obs_on_us": t_obs_on,
        "obs_off_overhead_pct": 100.0 * (t_obs_off - t_scan) / t_scan,
        "obs_on_overhead_pct": 100.0 * (t_obs_on - t_scan) / t_scan,
        "scan_guard_us": t_guard,
        "guard_overhead_pct": 100.0 * (t_guard - t_scan) / t_scan,
        "obs_steps_recorded": sum(1 for e in tracer.events
                                  if e["type"] == "step"),
        "reuse_bitwise_equal_fresh": bitwise,
        "reuse_max_abs_df": max_df,
        "max_disp2": float(diag["max_disp2"]),
        "rebuild_triggered": bool(diag["needs_rebuild"]),
    }
    save_json("BENCH_dd_reuse", payload)
    return [
        ("dd_reuse_per_step", t_per_step, "baseline"),
        ("dd_reuse_skin", t_reuse, f"x{payload['speedup_reuse']:.2f}"),
        ("dd_reuse_scan", t_scan,
         f"x{payload['speedup_scan_fused']:.2f} bitwise={bitwise}"),
        ("dd_reuse_obs_off", t_obs_off,
         f"{payload['obs_off_overhead_pct']:+.2f}% vs scan (<2% target)"),
        ("dd_reuse_obs_on", t_obs_on,
         f"{payload['obs_on_overhead_pct']:+.2f}% with counters+transfer"),
        ("dd_reuse_guard", t_guard,
         f"{payload['guard_overhead_pct']:+.2f}% vs scan (<2% target)"),
    ]


if __name__ == "__main__":
    from repro.launch.runtime import use_cpu_devices
    use_cpu_devices(N_RANKS)
    for name, us, derived in run(smoke="--smoke" in sys.argv[1:]):
        print(f"{name},{us:.1f},{derived}")
