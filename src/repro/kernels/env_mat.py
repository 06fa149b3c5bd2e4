"""Pallas TPU kernel: fused environment-matrix construction + analytic VJP.

This is the TPU adaptation of DeePMD-kit's custom ``prod_env_mat`` CUDA op —
the first compute hot-spot of every DP inference step.  The GPU version
gathers neighbors and computes (s, s*x/r, s*y/r, s*z/r) in one kernel to
avoid materializing intermediates in HBM; on TPU we do the same with a
VMEM-tiled elementwise fusion.

TPU-native layout decisions (DESIGN.md Hardware adaptation):
  * SoA planes: neighbor displacement components arrive as three (N, K)
    planes instead of an (N, K, 3) array, so the lane dimension is the
    neighbor axis (pad K to a multiple of 128) and the sublane dimension is
    the atom axis (block of 8) — native (8, 128) VREG tiling, no relayouts.
  * One grid step processes a (BLOCK_N, K) tile; all four outputs are
    written from registers, so HBM traffic is exactly inputs + outputs.

Autodiff: the op carries a ``jax.custom_vjp`` whose backward pass is a
second fused elementwise kernel in the *same* SoA plane layout.  Forces go
through ``jax.value_and_grad`` of the total energy, so without a VJP rule
the forward kernel would be unreachable from the MD hot path.  The backward
is analytic: with h(r) the [0, 1] switch polynomial, s = h/r and
q = s/r = h/r^2,

    d s / d x  = s'(r) x / r                    s'  = h'/r   - h/r^2
    d sx / d x = q + x^2/r * q'(r)              q'  = h'/r^2 - 2 h/r^3
    d sx / d y = x y / r * q'(r)                (and cyclic)

so the cotangents (gs, gsx, gsy, gsz) contract to

    dx_ct = x/r * (gs * s' + A * q') + q * gsx,   A = gsx*x + gsy*y + gsz*z

— eight input planes in, three planes out, all elementwise in VREGs.

Zero-distance guard: r^2 is clamped to 1e-12 for *valid* pairs (matching
``dp.common.switch_fn``'s 1/max(r, 1e-6)), and gradients below the clamp
are zeroed — the same semantics the jnp double-where guard produces, so a
coincident-atom frame yields huge-but-finite energies and finite forces on
both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# canonical zero-distance clamp (matches switch_fn's r >= 1e-6): every
# descriptor path — this kernel, the jnp oracle in ref.py, and
# dp.common._guarded_env — must share it or the jnp/pallas parity breaks
R2_MIN = 1e-12
_R2_MIN = R2_MIN


def _switch_parts(r, rcut_smth: float, rcut: float):
    """h(r) (the [0,1] polynomial envelope) and h'(r), branch-free."""
    u = (r - rcut_smth) / (rcut - rcut_smth)
    uu = jnp.clip(u, 0.0, 1.0)
    poly = uu * uu * uu * (-6.0 * uu * uu + 15.0 * uu - 10.0) + 1.0
    h = jnp.where(r < rcut, jnp.where(r < rcut_smth, 1.0, poly), 0.0)
    dpoly = -30.0 * uu * uu * (uu - 1.0) * (uu - 1.0) / (rcut - rcut_smth)
    hp = jnp.where((r >= rcut_smth) & (r < rcut), dpoly, 0.0)
    return h, hp


def _env_mat_kernel(dx_ref, dy_ref, dz_ref, mask_ref,
                    s_ref, sx_ref, sy_ref, sz_ref,
                    *, rcut_smth: float, rcut: float):
    dx = dx_ref[...]
    dy = dy_ref[...]
    dz = dz_ref[...]
    mask = mask_ref[...]

    d2 = dx * dx + dy * dy + dz * dz
    # padded entries -> safe r; valid coincident pairs -> clamped r = 1e-6
    d2 = jnp.where(mask > 0, jnp.maximum(d2, _R2_MIN), 1.0)
    inv_r = jax.lax.rsqrt(d2)
    r = d2 * inv_r                              # r = d2 / sqrt(d2)

    # smooth switch: 1/r below rcut_smth, 1/r * poly to 0 at rcut
    h, _ = _switch_parts(r, rcut_smth, rcut)
    sw = inv_r * h * mask

    s_ref[...] = sw
    sx_ref[...] = sw * dx * inv_r
    sy_ref[...] = sw * dy * inv_r
    sz_ref[...] = sw * dz * inv_r


def _env_mat_bwd_kernel(dx_ref, dy_ref, dz_ref, mask_ref,
                        gs_ref, gsx_ref, gsy_ref, gsz_ref,
                        ddx_ref, ddy_ref, ddz_ref,
                        *, rcut_smth: float, rcut: float):
    dx = dx_ref[...]
    dy = dy_ref[...]
    dz = dz_ref[...]
    mask = mask_ref[...]
    gs = gs_ref[...]
    gsx = gsx_ref[...]
    gsy = gsy_ref[...]
    gsz = gsz_ref[...]

    d2_raw = dx * dx + dy * dy + dz * dz
    valid = mask > 0
    d2 = jnp.where(valid, jnp.maximum(d2_raw, _R2_MIN), 1.0)
    inv_r = jax.lax.rsqrt(d2)
    r = d2 * inv_r
    inv_r2 = inv_r * inv_r

    h, hp = _switch_parts(r, rcut_smth, rcut)
    ds_dr = hp * inv_r - h * inv_r2                       # d(h/r)/dr
    dq_dr = hp * inv_r2 - 2.0 * h * inv_r2 * inv_r        # d(h/r^2)/dr
    q = h * inv_r2

    a = gsx * dx + gsy * dy + gsz * dz
    # below the clamp r is constant in d2 (max picks the constant branch):
    # the r-chain terms vanish there, but the direct q = h/r^2 coupling of
    # sx = q * x stays — huge-but-finite, exactly what the jnp double-where
    # oracle differentiates to
    live = valid & (d2_raw > _R2_MIN)
    chain = jnp.where(live, (gs * ds_dr + a * dq_dr) * inv_r,
                      jnp.zeros_like(dx))
    zero = jnp.zeros_like(dx)
    ddx_ref[...] = jnp.where(valid, chain * dx + q * gsx, zero)
    ddy_ref[...] = jnp.where(valid, chain * dy + q * gsy, zero)
    ddz_ref[...] = jnp.where(valid, chain * dz + q * gsz, zero)


def _pad_rows(arrays, block_n: int):
    n = arrays[0].shape[0]
    pad_n = (-n) % block_n
    if pad_n:
        arrays = [jnp.pad(a, ((0, pad_n), (0, 0))) for a in arrays]
    return arrays, n


def _env_mat_call(dx, dy, dz, mask, rcut_smth: float, rcut: float,
                  block_n: int, interpret: bool):
    (dx, dy, dz, mask), n = _pad_rows([dx, dy, dz, mask], block_n)
    np_, kp = dx.shape
    grid = (np_ // block_n,)
    spec = pl.BlockSpec((block_n, kp), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((np_, kp), dx.dtype)] * 4
    kernel = functools.partial(_env_mat_kernel, rcut_smth=rcut_smth,
                               rcut=rcut)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 4,
        out_shape=out_shape,
        interpret=interpret,
        name="env_mat_fwd",
    )(dx, dy, dz, mask)
    return tuple(o[:n] for o in outs) if np_ != n else tuple(outs)


def _env_mat_bwd_call(dx, dy, dz, mask, gs, gsx, gsy, gsz,
                      rcut_smth: float, rcut: float, block_n: int,
                      interpret: bool):
    arrays, n = _pad_rows([dx, dy, dz, mask, gs, gsx, gsy, gsz], block_n)
    np_, kp = arrays[0].shape
    grid = (np_ // block_n,)
    spec = pl.BlockSpec((block_n, kp), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((np_, kp), dx.dtype)] * 3
    kernel = functools.partial(_env_mat_bwd_kernel, rcut_smth=rcut_smth,
                               rcut=rcut)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * 8,
        out_specs=[spec] * 3,
        out_shape=out_shape,
        interpret=interpret,
        name="env_mat_bwd",
    )(*arrays)
    return tuple(o[:n] for o in outs) if np_ != n else tuple(outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _env_mat(dx, dy, dz, mask, rcut_smth, rcut, block_n, interpret):
    return _env_mat_call(dx, dy, dz, mask, rcut_smth, rcut, block_n,
                         interpret)


def _env_mat_fwd(dx, dy, dz, mask, rcut_smth, rcut, block_n, interpret):
    out = _env_mat_call(dx, dy, dz, mask, rcut_smth, rcut, block_n, interpret)
    return out, (dx, dy, dz, mask)


def _env_mat_bwd(rcut_smth, rcut, block_n, interpret, res, cts):
    dx, dy, dz, mask = res
    gs, gsx, gsy, gsz = cts
    ddx, ddy, ddz = _env_mat_bwd_call(dx, dy, dz, mask, gs, gsx, gsy, gsz,
                                      rcut_smth, rcut, block_n, interpret)
    return ddx, ddy, ddz, jnp.zeros_like(mask)


_env_mat.defvjp(_env_mat_fwd, _env_mat_bwd)


@functools.partial(jax.jit, static_argnames=("rcut_smth", "rcut", "block_n",
                                             "interpret"))
def env_mat(dx: jax.Array, dy: jax.Array, dz: jax.Array, mask: jax.Array,
            rcut_smth: float, rcut: float, block_n: int = 8,
            interpret: bool = False):
    """Fused env-matrix planes from displacement planes (differentiable).

    Args: dx/dy/dz/mask (N, K) — displacement components center->neighbor and
    validity mask.  K should be a multiple of 128 on real TPUs (the ops.py
    wrapper pads); N is padded to ``block_n`` here.
    Returns: (s, sx, sy, sz), each (N, K).  Reverse-mode differentiable in
    dx/dy/dz via the fused analytic backward kernel; the mask cotangent is
    zero (it is a selector, not a coordinate function).
    """
    return _env_mat(dx, dy, dz, mask, rcut_smth, rcut, block_n, interpret)
