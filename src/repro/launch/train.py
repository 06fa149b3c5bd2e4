"""End-to-end LM training driver (CPU-runnable with reduced configs).

Fault tolerance: async checkpoints every K steps, deterministic data order
keyed to the global step (restart-safe), automatic restore from the latest
checkpoint at startup.  ``--simulate-failure N`` kills the process at step N
to exercise the restart path (see launch/elastic.py for the supervisor).

Usage:
  python -m repro.launch.train --arch qwen2-1.5b --reduced --steps 50
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--simulate-failure", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--n-layers", type=int, default=4)
    args = ap.parse_args()
    from .runtime import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from ..ckpt import AsyncCheckpointer
    from ..configs import get_arch
    from ..data.loader import synthetic_token_batch
    from ..lm import model as M
    from ..lm.train_lib import TrainHParams, make_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=args.n_layers, d_model=args.d_model,
                          d_ff=2 * args.d_model, vocab=512)
    hp = TrainHParams(lr=args.lr, optimizer=args.optimizer, remat="none")

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    step_fn, opt = make_train_step(cfg, hp)
    step_fn = jax.jit(step_fn)
    opt_state = opt.init(params)

    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt is not None:
        restored, s = ckpt.restore_latest({"params": params, "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = s + 1
            print(f"[restore] resumed from step {s}")

    rng_ctx = np.random.default_rng
    t0 = time.time()
    for step in range(start, args.steps):
        rng = rng_ctx((1234, step))  # deterministic per-step batch
        batch = synthetic_token_batch(rng, args.batch, args.seq, cfg.vocab)
        if cfg.enc_dec or cfg.cross_attn_every:
            t = cfg.n_audio_frames if cfg.enc_dec else cfg.n_image_tokens
            batch["context"] = jnp.asarray(
                rng.normal(0, 1, (args.batch, t, cfg.d_model)), jnp.float32)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if ckpt is not None and step and step % args.ckpt_every == 0:
            ckpt.save({"params": params, "opt": opt_state}, step)
        if args.simulate_failure and step == args.simulate_failure:
            print(f"[failure-injection] dying at step {step}", flush=True)
            raise SystemExit(42)
    if ckpt is not None:
        ckpt.save({"params": params, "opt": opt_state}, args.steps - 1)
        ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
