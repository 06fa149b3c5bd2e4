import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST be first — before ANY other import — because jax
# locks the device count at first init.  512 placeholder host devices back
# both production meshes (single-pod 16x16=256, multi-pod 2x16x16=512).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell this script:
  1. builds abstract (ShapeDtypeStruct, zero-allocation) params / optimizer
     state / batch / cache with production shardings;
  2. ``jit(step).lower(...).compile()`` — success proves the sharding config
     is coherent (no sharding mismatch, no unsupported collective);
  3. records ``memory_analysis()`` (fits/doesn't-fit evidence) and
     ``cost_analysis()``;
  4. re-lowers two reduced-depth variants to fit FLOPs/bytes linearly in
     depth (scan bodies are not multiplied by cost_analysis — see
     launch/roofline.py);
  5. parses optimized HLO for the collective schedule and emits the
     three-term roofline to JSON.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

INFER_FSDP = True  # --no-infer-fsdp switches inference params to TP-only


def _build_step_and_args(arch_cfg, shape_cfg, mesh, hp, with_mesh=True):
    """Returns (fn, args tuple of ShapeDtypeStructs, donate_argnums).

    ``with_mesh=False`` builds the step WITHOUT sharding constraints (the
    unsharded depth-fit path)."""
    from ..lm import serve_lib, train_lib
    from ..lm.sharding import cache_shardings, params_shardings
    step_mesh = mesh if with_mesh else None

    if shape_cfg.kind == "train":
        params, opt_state = train_lib.abstract_train_state(arch_cfg, hp, mesh)
        batch = train_lib.batch_specs(arch_cfg, shape_cfg.seq_len,
                                      shape_cfg.global_batch, mesh)
        step, _ = train_lib.make_train_step(arch_cfg, hp, step_mesh)
        # donate params+opt: the update is in-place on real hardware
        return step, (params, opt_state, batch), (0, 1)

    # inference paths: params only (no optimizer).  INFER_FSDP=False shards
    # params over "model" only — inference has no optimizer state, so ZeRO
    # gathers per step are pure overhead (§Perf).
    p_shapes = train_lib.abstract_params(arch_cfg)
    p_shard = params_shardings(p_shapes, mesh, fsdp=INFER_FSDP)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        p_shapes, p_shard)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..lm.sharding import batch_spec
    dp = batch_spec(mesh)
    b = shape_cfg.global_batch
    axes = dp[0] if len(dp) else None
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    dp_size = 1
    for a in axes:
        dp_size *= mesh.shape[a]
    divisible = b >= dp_size and b % dp_size == 0
    tok_spec = P(axes) if (axes and divisible) else P()

    ctx = train_lib.context_spec(arch_cfg, b, mesh)

    if shape_cfg.kind == "prefill":
        tokens = jax.ShapeDtypeStruct(
            (b, shape_cfg.seq_len), jnp.int32,
            sharding=NamedSharding(mesh, P(tok_spec[0] if len(tok_spec) else None, None)))
        prefill = serve_lib.make_prefill(arch_cfg, max_len=shape_cfg.seq_len,
                                         mesh=step_mesh)
        if ctx is not None:
            return prefill, (params, tokens, ctx), ()
        return prefill, (params, tokens), ()

    # decode: one new token against a seq_len cache
    tokens = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32,
        sharding=NamedSharding(mesh, P(tok_spec[0] if len(tok_spec) else None, None)))
    cache_shapes = serve_lib.abstract_cache(arch_cfg, b, shape_cfg.seq_len)
    c_shard = cache_shardings(cache_shapes, mesh,
                              long_context=shape_cfg.seq_len > 100_000)
    cache = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        cache_shapes, c_shard)
    serve = serve_lib.make_serve_step(arch_cfg, step_mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return serve, (params, cache, tokens, pos), (1,)  # donate the cache


def _cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` returns one dict on modern jax but a
    list of per-device dicts on 0.4.x — normalize to a single dict."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             hp_overrides: dict | None = None, fit_depth: bool = True) -> dict:
    from ..configs import ARCHS, SHAPES, param_count
    from ..lm.train_lib import TrainHParams
    from . import roofline as R
    from .mesh import make_production_mesh

    arch = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    hp = TrainHParams(**(hp_overrides or {}))

    result = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
              "chips": int(chips), "ok": False}
    t0 = time.time()
    try:
        with mesh:
            fn, args, donate = _build_step_and_args(arch, shape, mesh, hp)
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            ma = compiled.memory_analysis()
            ca = _cost_analysis_dict(compiled)
            hlo = compiled.as_text()
            records = R.parse_hlo_collectives(hlo)
            colls = R.collective_summary(records)
            hbm_traffic = R.parse_hlo_memory_traffic(hlo)

            flops = float(ca.get("flops", 0.0))
            bytes_acc = float(ca.get("bytes accessed", 0.0))

            if fit_depth:
                flops, bytes_acc, fit = _depth_fit(arch, shape, mesh, hp,
                                                   flops, bytes_acc)
                result["depth_fit"] = fit

            terms = R.roofline_terms(flops, hbm_traffic,
                                     colls["total_wire_bytes"])
            result["hlo_bytes_naive_per_chip"] = bytes_acc
            total, active = param_count(arch)
            mf = R.model_flops_per_step(arch, shape, chips, total, active)
            result.update({
                "ok": True,
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "memory": {
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    # donated args alias outputs, so peak ~ args + temp
                    "peak_bytes_est": (
                        ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        + (0 if donate else ma.output_size_in_bytes)),
                },
                "hlo_flops_per_chip": flops,
                "hlo_bytes_per_chip": hbm_traffic,
                "collectives": colls,
                "roofline": terms,
                "model_flops_per_chip": mf,
                "useful_flops_ratio": (mf / flops) if flops else None,
                "params_total": total, "params_active": active,
            })
    except Exception as e:  # noqa: BLE001 — report the failure as data
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    result["wall_s"] = round(time.time() - t0, 2)
    return result


def _depth_fit(arch, shape, mesh, hp, flops_full, bytes_full):
    """Compile *unrolled* prefix+1 and prefix+2 period variants; extrapolate.

    cost_analysis counts a while body once regardless of trip count, so the
    fit compiles two small straight-line (scan-unrolled) depths — the delta
    is exactly one period's cost — and extends linearly to full depth.
    """
    from ..lm import model as M
    prefix, steps, pattern = arch.scan_pattern()
    period = len(pattern)
    if steps <= 1 or period == 0:
        return flops_full, bytes_full, {"note": "no scan; raw cost_analysis"}
    chips = mesh.devices.size
    vals = {}
    M.set_scan_unroll(True)
    try:
        for k in (1, 2):
            small = dataclasses.replace(arch, n_layers=prefix + k * period)
            fn, args, donate = _build_step_and_args(small, shape, mesh, hp,
                                                    with_mesh=False)
            # strip shardings: the fit only needs GLOBAL flops/bytes, and
            # skipping the SPMD partitioner makes unrolled compiles ~10x
            # faster (rwkv/mamba chunk scans unroll to hundreds of bodies).
            args = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), args)
            ca = _cost_analysis_dict(
                jax.jit(fn, donate_argnums=donate).lower(*args).compile())
            vals[k] = (float(ca.get("flops", 0.0)) / chips,
                       float(ca.get("bytes accessed", 0.0)) / chips)
    finally:
        M.set_scan_unroll(False)
    df = vals[2][0] - vals[1][0]
    db = vals[2][1] - vals[1][1]
    flops = vals[1][0] + df * (steps - 1)
    bytes_ = vals[1][1] + db * (steps - 1)
    fit = {"flops_1": vals[1][0], "flops_2": vals[2][0],
           "per_period_flops": df, "per_period_bytes": db,
           "raw_full_flops": flops_full, "fit_mode": "unsharded/chips"}
    return flops, bytes_, fit


def refit(path: str, hp_overrides: dict) -> None:
    """Recompute the depth-fit + roofline of an existing cell JSON (cheap:
    two small unsharded compiles; the full-compile artifacts are kept)."""
    from ..configs import ARCHS, SHAPES
    from ..lm.train_lib import TrainHParams
    from . import roofline as R
    from .mesh import make_production_mesh

    with open(path) as f:
        res = json.load(f)
    if not res.get("ok"):
        return
    arch = ARCHS[res["arch"]]
    shape = SHAPES[res["shape"]]
    mesh = make_production_mesh(multi_pod=(res["mesh"] == "multi"))
    hp = TrainHParams(**hp_overrides)
    flops, bytes_acc, fit = _depth_fit(arch, shape, mesh, hp, 0.0, 0.0)
    res["depth_fit"] = fit
    res["hlo_flops_per_chip"] = flops
    res["roofline"] = R.roofline_terms(
        flops, res["hlo_bytes_per_chip"],
        res["collectives"]["total_wire_bytes"])
    res["useful_flops_ratio"] = (res["model_flops_per_chip"] / flops
                                 if flops else None)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    r = res["roofline"]
    print(f"[refit] {os.path.basename(path)} dom={r['dominant']} "
          f"useful={res['useful_flops_ratio']:.2f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--optimizer", default="adam8bit")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--no-fit", action="store_true")
    ap.add_argument("--refit", action="store_true",
                    help="recompute depth-fit/roofline of cached cells")
    # §Perf optimization knobs (off = paper-faithful/naive baseline)
    ap.add_argument("--gqa-repeat", action="store_true")
    ap.add_argument("--no-infer-fsdp", action="store_true")
    ap.add_argument("--expert-2d", action="store_true")
    ap.add_argument("--flash-decode", action="store_true")
    args = ap.parse_args()
    from .runtime import enable_compile_cache
    enable_compile_cache()

    if args.flash_decode:
        from ..lm.layers import set_flash_decode
        set_flash_decode(True)
    if args.gqa_repeat:
        from ..lm.layers import set_gqa_repeat
        set_gqa_repeat(True)
    if args.no_infer_fsdp:
        global INFER_FSDP
        INFER_FSDP = False
    if args.expert_2d:
        from ..lm.sharding import set_expert_2d
        set_expert_2d(True)

    if args.refit:
        import glob as _glob
        hp = {"optimizer": args.optimizer, "remat": args.remat}
        for path in sorted(_glob.glob(os.path.join(args.out, "*.json"))):
            try:
                refit(path, hp)
            except Exception as e:  # noqa: BLE001
                print(f"[refit] FAIL {path}: {e}", flush=True)
        return

    from ..configs import ARCHS, applicable_shapes

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for name, cfg in ARCHS.items():
            for shp in applicable_shapes(cfg):
                cells.append((name, shp))
    else:
        cells.append((args.arch, args.shape))
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    hp = {"optimizer": args.optimizer, "remat": args.remat}
    for arch, shp in cells:
        for mk in meshes:
            tag = f"{arch}__{shp}__{mk}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (cached)")
                continue
            print(f"[run ] {tag}", flush=True)
            res = run_cell(arch, shp, mk, hp, fit_depth=not args.no_fit)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            status = "OK" if res["ok"] else "FAIL " + res.get("error", "")[:120]
            if res["ok"]:
                r = res["roofline"]
                mem_gb = res["memory"]["peak_bytes_est"] / 1e9
                print(f"       {status}  compile={res.get('compile_s')}s "
                      f"mem={mem_gb:.1f}GB dom={r['dominant']} "
                      f"t=(c{r['compute_s']:.4f} m{r['memory_s']:.4f} "
                      f"x{r['collective_s']:.4f})s", flush=True)
            else:
                print(f"       {status}", flush=True)


if __name__ == "__main__":
    main()
