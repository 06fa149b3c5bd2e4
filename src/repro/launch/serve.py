"""Serving driver: one entry point, two backend kinds.

Dispatches on what is being served (``--backend auto`` resolves from
``--arch``):

* ``lm`` — the LM token-serving loop (batched prefill + decode), for any
  architecture in the :mod:`repro.configs` registry;
* ``force`` — the DP force-inference server (:mod:`repro.serve`): stands an
  in-process :class:`~repro.serve.ForceServer` in front of the paper's
  DPA-1 model and drives it with N concurrent MD-simulation clients
  (:class:`~repro.serve.RemoteForceProvider` tenants), then prints the
  per-tenant serving metrics.

Usage:
  python -m repro.launch.serve --arch gemma2-2b --reduced --batch 4 --new 16
  python -m repro.launch.serve --backend force --clients 4 --steps 10
"""
from __future__ import annotations

import argparse
import time

# DP/force presets the auto dispatcher recognizes (everything else resolves
# through the LM arch registry)
FORCE_ARCHS = ("dpa1", "dpa1-md", "dp")


def main_lm(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..configs import get_arch
    from ..lm import model as M
    from ..lm.serve_lib import make_prefill, make_serve_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.new
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch,
                                                     args.prompt_len)))
    ctx = None
    if cfg.enc_dec:
        ctx = jnp.asarray(rng.normal(0, 1, (args.batch, cfg.n_audio_frames,
                                            cfg.d_model)), jnp.float32)
    elif cfg.cross_attn_every and cfg.family == "vlm":
        ctx = jnp.asarray(rng.normal(0, 1, (args.batch, cfg.n_image_tokens,
                                            cfg.d_model)), jnp.float32)

    prefill = jax.jit(make_prefill(cfg, max_len=max_len, remat="none"))
    serve = jax.jit(make_serve_step(cfg))
    t0 = time.time()
    logits, cache = (prefill(params, tokens, ctx) if ctx is not None
                     else prefill(params, tokens))
    print(f"prefill {args.batch}x{args.prompt_len} in {time.time()-t0:.2f}s")
    out = [int(x) for x in jnp.argmax(logits[:, -1], -1)]
    t0 = time.time()
    tok = jnp.argmax(logits[:, -1:], -1)
    for i in range(args.new - 1):
        logits, cache = serve(params, cache, tok, args.prompt_len + i)
        tok = jnp.argmax(logits[:, :, :], -1)
        out.append(int(tok[0, 0]))
    dt = time.time() - t0
    print(f"decoded {args.new - 1} steps in {dt:.2f}s "
          f"({(args.new - 1) * args.batch / max(dt, 1e-9):.1f} tok/s)")
    print("greedy tokens (batch 0):", out[:16])


def main_force(args):
    import threading

    import jax
    from ..dp import DPModel, paper_dpa1_config
    from ..md import (EngineConfig, MDEngine, build_solvated_protein,
                      mark_nn_group)
    from ..serve import ForceServer, RemoteForceProvider, ServeConfig

    # the served evaluator: paper DPA-1 (reduced shrinks cutoff/sel so the
    # CPU demo stays interactive)
    cfg = (paper_dpa1_config(ntypes=4, rcut=0.6, sel=32) if args.reduced
           else paper_dpa1_config(ntypes=4))
    model = DPModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    system, pos, nn_idx = build_solvated_protein(
        args.protein_atoms, water_per_protein_atom=2.0)
    system = mark_nn_group(system, nn_idx)

    serve_cfg = ServeConfig(queue_bound=args.queue_bound,
                            batch_window_s=args.batch_window_ms * 1e-3,
                            default_timeout_s=args.timeout_s,
                            nbr_capacity=48)
    server = ForceServer(model, params, serve_cfg)
    print(f"force server up: atom buckets {serve_cfg.atom_buckets}, "
          f"batch buckets {serve_cfg.batch_buckets}, "
          f"queue bound {serve_cfg.queue_bound}")

    def run_client(tid: int):
        provider = RemoteForceProvider(
            server, nn_idx, system.types, system.box, system.n_atoms,
            tenant=f"sim{tid}", timeout_s=args.timeout_s)
        eng = MDEngine(system, EngineConfig(cutoff=0.9, neighbor_capacity=96,
                                            dt=0.0005, thermostat_t=300.0),
                       special_force=provider)
        st = eng.init_state(pos, 300.0, seed=tid)
        eng.run(st, args.steps)

    t0 = time.time()
    threads = [threading.Thread(target=run_client, args=(i,), daemon=True)
               for i in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0
    snap = server.metrics.snapshot()
    totals = server.metrics.totals()
    server.stop()

    print(f"\n{args.clients} MD clients x {args.steps} steps "
          f"in {dt:.2f}s ({totals['completed'] / max(dt, 1e-9):.1f} req/s)")
    hdr = ("tenant", "submitted", "completed", "timeouts", "errors",
           "rejected", "max_depth", "mean_lat_ms", "p50_ms", "p99_ms", "rps")
    print(("{:>10}" * len(hdr)).format(*hdr))
    for tenant in sorted(snap):
        s = snap[tenant]
        print("{:>10}{:>10}{:>10}{:>10}{:>10}{:>10}{:>10}"
              "{:>10.1f}{:>10.1f}{:>10.1f}{:>10.2f}"
              .format(tenant, s["submitted"], s["completed"], s["timeouts"],
                      s["errors"], s["rejected"], s["max_queue_depth"],
                      1e3 * s["mean_latency_s"], 1e3 * s["p50_latency_s"],
                      1e3 * s["p99_latency_s"], s["rps"]))
    print("totals:", totals)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "lm", "force"),
                    help="what to serve: LM tokens or DP forces "
                    "(auto resolves from --arch)")
    ap.add_argument("--arch", default="gemma2-2b",
                    help="LM arch id, or a DP preset "
                    f"({'/'.join(FORCE_ARCHS)}) for force serving")
    ap.add_argument("--reduced", action="store_true")
    # LM knobs
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    # force-serving knobs
    ap.add_argument("--clients", type=int, default=4,
                    help="concurrent MD-simulation tenants")
    ap.add_argument("--steps", type=int, default=10,
                    help="MD steps per client")
    ap.add_argument("--protein-atoms", type=int, default=6)
    ap.add_argument("--queue-bound", type=int, default=64)
    ap.add_argument("--batch-window-ms", type=float, default=2.0)
    ap.add_argument("--timeout-s", type=float, default=60.0)
    args = ap.parse_args()
    from .runtime import enable_compile_cache
    enable_compile_cache()

    backend = args.backend
    if backend == "auto":
        backend = "force" if args.arch in FORCE_ARCHS else "lm"
    if backend == "force":
        main_force(args)
    else:
        main_lm(args)


if __name__ == "__main__":
    main()
