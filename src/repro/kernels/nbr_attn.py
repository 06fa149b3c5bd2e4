"""Pallas TPU kernel: DPA-1 gated neighbor self-attention (se_attention_v2).

The second DP hot-spot: for every center atom, l_a attention layers over its
K neighbors.  The GPU implementation launches one fused attention kernel per
layer; the TPU adaptation goes further and fuses the *whole l_a-layer stack*
into a single kernel: one grid step processes a block of atoms and keeps the
(K x M) activations plus each head's (K, K) score matrix resident in VMEM
across all layers, so G enters and leaves HBM exactly once per stack — not
once per layer.  The angular gate is computed in-kernel from the r_hat
planes; it never touches HBM.

Layout: G tiles are (BLOCK_N, K, M) with M = 128 in lanes (MXU-aligned).
Mosaic's matmul takes at most one batch dim, so the projections run as 2-D
matmuls over the flattened (BLOCK_N * K) rows and the score/value
contractions batch over the atom block only.  Multi-head attention splits
the hidden width H into ``heads`` contiguous H/heads slices sharing the
angular gate; the wrapper hands the kernels per-head weight stacks and the
heads loop statically inside.

Autodiff: the stack carries a ``jax.custom_vjp``.  The forward kernel
stashes each layer's *input* activations (L, N, K, M) — everything else
(projections, scores, softmax) is cheaper to recompute than to spill, the
flash-attention trade.  The backward kernel sweeps the layers in reverse in
one pallas_call: per block it rebuilds the score matrix in VMEM, backprops
layer norm -> output projection -> gated softmax -> QKV, accumulates the
angular-gate/envelope cotangents across layers, and reduces parameter
gradients into accumulator blocks that stay resident across the grid
(initialized at block 0 — TPU grids execute sequentially, and vmapped grid
dims are hidden from ``pl.program_id``, so the pattern survives the batched
ensemble drivers).

Mixed precision: ``compute_dtype`` casts matmul *operands* (bf16 on the MXU)
while every accumulation, the softmax, the gate, residual adds and layer
norm stay fp32 — the policy `DPConfig.dtype` selects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The fused layer keeps q/k/v, the (K x K) score planes and the recompute
# intermediates of a whole atom block in VMEM; the backward of one block at
# K = 128 needs just over the 16 MiB default scoped limit.  A v5e core has
# 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)

_MM = (((1,), (0,)), ((), ()))     # (R, X) @ (X, Y)
_MT = (((1,), (1,)), ((), ()))     # (R, X) @ (Y, X)^T
_TM = (((0,), (0,)), ((), ()))     # (R, X)^T @ (R, Y)
# batched over the atom block: the only batch dim Mosaic's matmul takes
_BNT = (((2,), (2,)), ((0,), (0,)))  # (B, K, D) x (B, L, D) -> (B, K, L)
_BNN = (((2,), (1,)), ((0,), (0,)))  # (B, K, L) x (B, L, D) -> (B, K, D)
_BTN = (((1,), (1,)), ((0,), (0,)))  # (B, L, K) x (B, L, D) -> (B, K, D)


def _cast(x, dtype):
    return x if x.dtype == dtype else x.astype(dtype)


def _dot(a, b, dims, cd=None):
    """fp32-accumulating contraction; ``cd`` casts the operands first."""
    if cd is not None:
        a, b = _cast(a, cd), _cast(b, cd)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _gate_mul(rx, ry, rz, sw, mask):
    """Combined score multiplier: angular gate x smooth envelope x mask."""
    gate = (rx[:, :, None] * rx[:, None, :] + ry[:, :, None] * ry[:, None, :]
            + rz[:, :, None] * rz[:, None, :])
    gmul = gate * (sw[:, :, None] * sw[:, None, :])
    return gate, gmul * (mask[:, :, None] * mask[:, None, :])


def _layer_core(g, gmul, mask, wq, wk, wv, wo, cd):
    """Forward intermediates for one layer (fwd kernel + bwd recompute).

    ``wq``/``wk``/``wv`` are (heads, M, hd) and ``wo`` is (heads, hd, M):
    the heads loop statically, so every projection is a 2-D matmul over the
    flattened (B*K) rows and every score/value contraction has the atom
    block as its one batch dim."""
    b, k, m = g.shape
    heads, _, hd = wq.shape
    f32 = jnp.float32
    g2 = g.reshape(b * k, m)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, f32))
    neg = jnp.finfo(f32).min
    valid = mask[:, None, :] > 0
    out = jnp.zeros((b * k, m), f32)
    per_head = []
    for h in range(heads):
        q = _dot(g2, wq[h], _MM, cd).reshape(b, k, hd)
        kk = _dot(g2, wk[h], _MM, cd).reshape(b, k, hd)
        v = _dot(g2, wv[h], _MM, cd).reshape(b, k, hd)
        scores = jnp.where(valid, _dot(q, kk, _BNT, cd) * scale, neg)
        p = jax.nn.softmax(scores, axis=-1)              # (B, K, K)
        w = p * gmul
        o = _dot(w, v, _BNN, cd).reshape(b * k, hd)
        out = out + _dot(o, wo[h], _MM, cd)
        per_head.append(dict(q=q, kk=kk, v=v, p=p, w=w, o=o))
    g1 = g + out.reshape(b, k, m)
    mu = g1.mean(-1, keepdims=True)
    var = ((g1 - mu) ** 2).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(var + 1e-5)
    xhat = (g1 - mu) * inv
    return dict(heads=per_head, inv=inv, xhat=xhat, scale=scale)


def _layer_bwd(g_in, dg, gmul, mask, wq, wk, wv, wo, gamma, cd):
    """Analytic backward of one layer; recomputes the forward in VMEM.

    Backward contractions run fp32 (the stored intermediates are fp32
    accumulations) — for cd = fp32 this matches the jnp autodiff bitwise up
    to reassociation; for bf16 the forward already quantized the operands.
    Returns per-head parameter gradients in the kernel's (heads, ...) layout.
    """
    c = _layer_core(g_in, gmul, mask, wq, wk, wv, wo, cd)
    b, k, m = g_in.shape
    hd = wq.shape[-1]
    # out = layer_norm(g1) * mask
    dln = dg * mask[..., None]
    dgamma = (dln * c["xhat"]).sum((0, 1))
    dbeta = dln.sum((0, 1))
    dxhat = dln * gamma
    dg1 = c["inv"] * (dxhat - dxhat.mean(-1, keepdims=True)
                      - c["xhat"] * (dxhat * c["xhat"]).mean(-1, keepdims=True))
    dg1_2 = dg1.reshape(b * k, m)
    g2 = g_in.reshape(b * k, m)
    dgin = dg1_2
    dgmul = jnp.zeros(gmul.shape, jnp.float32)
    dwq, dwk, dwv, dwo = [], [], [], []
    for h, ch in enumerate(c["heads"]):
        # out-projection: o (BK, hd) @ wo_h (hd, M)
        dwo.append(_dot(ch["o"], dg1_2, _TM))
        do = _dot(dg1_2, wo[h], _MT).reshape(b, k, hd)
        # o = W @ v
        dw = _dot(do, ch["v"], _BNT)                     # (B, K, K)
        dv = _dot(ch["w"], do, _BTN).reshape(b * k, hd)
        # W = P * gmul  (gmul shared across heads)
        dp = dw * gmul
        dgmul = dgmul + dw * ch["p"]
        ds = ch["p"] * (dp - (dp * ch["p"]).sum(-1, keepdims=True)) \
            * c["scale"]
        # scores = q k^T
        dq = _dot(ds, ch["kk"], _BNN).reshape(b * k, hd)
        dk = _dot(ds, ch["q"], _BTN).reshape(b * k, hd)
        dwq.append(_dot(g2, dq, _TM))
        dwk.append(_dot(g2, dk, _TM))
        dwv.append(_dot(g2, dv, _TM))
        dgin = (dgin + _dot(dq, wq[h], _MT) + _dot(dk, wk[h], _MT)
                + _dot(dv, wv[h], _MT))
    return (dgin.reshape(b, k, m), dgmul, jnp.stack(dwq), jnp.stack(dwk),
            jnp.stack(dwv), jnp.stack(dwo), dgamma, dbeta)


# ---------------------------------------------------------------------------
# Fused stack kernels
# ---------------------------------------------------------------------------

def _stack_fwd_kernel(g_ref, rx_ref, ry_ref, rz_ref, sw_ref, mask_ref,
                      wq_ref, wk_ref, wv_ref, wo_ref, gamma_ref, beta_ref,
                      out_ref, *res_ref, layers: int, cd):
    """``res_ref`` is present only on the VJP-forward variant — the primal
    (no-grad) path skips the residual stash entirely, so G really does
    enter and leave HBM exactly once per stack."""
    mask = mask_ref[...]
    _, gmul = _gate_mul(rx_ref[...], ry_ref[...], rz_ref[...], sw_ref[...],
                        mask)
    g = g_ref[...]
    for l in range(layers):
        if res_ref:
            res_ref[0][l] = g               # layer-input residual stash
        c = _layer_core(g, gmul, mask, wq_ref[l], wk_ref[l], wv_ref[l],
                        wo_ref[l], cd)
        g = (c["xhat"] * gamma_ref[l] + beta_ref[l]) * mask[..., None]
    out_ref[...] = g


def _stack_bwd_kernel(res_ref, rx_ref, ry_ref, rz_ref, sw_ref, mask_ref,
                      wq_ref, wk_ref, wv_ref, wo_ref, gamma_ref, beta_ref,
                      dout_ref,
                      dg_ref, drx_ref, dry_ref, drz_ref, dsw_ref,
                      dwq_ref, dwk_ref, dwv_ref, dwo_ref, dgamma_ref,
                      dbeta_ref, *, layers: int, cd):
    # parameter-grad accumulators live across the (sequential) grid; vmapped
    # batch dims are hidden from program_id, so block 0 is per-batch-element
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for r in (dwq_ref, dwk_ref, dwv_ref, dwo_ref, dgamma_ref, dbeta_ref):
            r[...] = jnp.zeros_like(r)

    mask = mask_ref[...]
    rx = rx_ref[...]
    ry = ry_ref[...]
    rz = rz_ref[...]
    sw = sw_ref[...]
    gate, gmul = _gate_mul(rx, ry, rz, sw, mask)

    dg = dout_ref[...]
    dgmul_acc = jnp.zeros(gmul.shape, gmul.dtype)
    for l in reversed(range(layers)):
        dg, dgmul, dwq, dwk, dwv, dwo, dgam, dbet = _layer_bwd(
            res_ref[l], dg, gmul, mask, wq_ref[l], wk_ref[l], wv_ref[l],
            wo_ref[l], gamma_ref[l], cd)
        dgmul_acc += dgmul
        dwq_ref[l] += dwq
        dwk_ref[l] += dwk
        dwv_ref[l] += dwv
        dwo_ref[l] += dwo
        dgamma_ref[l] += dgam
        dbeta_ref[l] += dbet

    # gmul = gate * (sw x sw) * (mask x mask): expand the accumulated
    # cotangent onto the direction planes and the envelope
    mm = mask[:, :, None] * mask[:, None, :]
    swsw = sw[:, :, None] * sw[:, None, :]
    dgate = dgmul_acc * swsw * mm
    hsw = dgmul_acc * gate * mm
    dsw_ref[...] = ((hsw * sw[:, None, :]).sum(2)
                    + (hsw * sw[:, :, None]).sum(1))
    sym = dgate + dgate.transpose(0, 2, 1)
    drx_ref[...] = (sym * rx[:, None, :]).sum(2)
    dry_ref[...] = (sym * ry[:, None, :]).sum(2)
    drz_ref[...] = (sym * rz[:, None, :]).sum(2)
    dg_ref[...] = dg


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom VJP
# ---------------------------------------------------------------------------

def _specs(block_n: int, k: int, m: int, wq, wo):
    """Block specs: atom tiles, per-atom planes, layer residuals, and the
    whole (per-head) weight stacks resident for every grid step."""
    layers = wq.shape[0]
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return dict(
        tile3=pl.BlockSpec((block_n, k, m), lambda i: (i, 0, 0)),
        tile2=pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        res=pl.BlockSpec((layers, block_n, k, m), lambda i: (0, i, 0, 0)),
        weights=[full(wq.shape)] * 3 + [full(wo.shape)]
                + [full((layers, m))] * 2)


def _stack_fwd_call(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                    compute_dtype: str, block_n: int, interpret: bool,
                    stash: bool):
    n, k, m = g.shape
    layers = wq.shape[0]
    sp = _specs(block_n, k, m, wq, wo)
    kernel = functools.partial(_stack_fwd_kernel, layers=layers,
                               cd=jnp.dtype(compute_dtype))
    outs = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[sp["tile3"]] + [sp["tile2"]] * 5 + sp["weights"],
        out_specs=[sp["tile3"]] + ([sp["res"]] if stash else []),
        out_shape=[jax.ShapeDtypeStruct((n, k, m), g.dtype)]
                  + ([jax.ShapeDtypeStruct((layers, n, k, m), g.dtype)]
                     if stash else []),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        name="nbr_attn_stack_fwd",
    )(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta)
    return (outs[0], outs[1]) if stash else (outs[0], None)


def _stack_bwd_call(res, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                    dout, compute_dtype: str, block_n: int, interpret: bool):
    layers, n, k, m = res.shape
    sp = _specs(block_n, k, m, wq, wo)
    kernel = functools.partial(_stack_bwd_kernel, layers=layers,
                               cd=jnp.dtype(compute_dtype))
    f32 = jnp.float32
    plane = jax.ShapeDtypeStruct((n, k), f32)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[sp["res"]] + [sp["tile2"]] * 5 + sp["weights"]
                 + [sp["tile3"]],
        out_specs=[sp["tile3"]] + [sp["tile2"]] * 4 + sp["weights"],
        out_shape=[jax.ShapeDtypeStruct((n, k, m), f32)] + [plane] * 4
                  + [jax.ShapeDtypeStruct(a.shape, f32)
                     for a in (wq, wk, wv, wo, gamma, beta)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        name="nbr_attn_stack_bwd",
    )(res, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(12, 13, 14))
def _stack(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
           compute_dtype, block_n, interpret):
    out, _ = _stack_fwd_call(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma,
                             beta, compute_dtype, block_n, interpret,
                             stash=False)
    return out


def _stack_vjp_fwd(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                   compute_dtype, block_n, interpret):
    out, res = _stack_fwd_call(g, rx, ry, rz, sw, mask, wq, wk, wv, wo,
                               gamma, beta, compute_dtype, block_n,
                               interpret, stash=True)
    return out, (res, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta)


def _stack_vjp_bwd(compute_dtype, block_n, interpret, saved, dout):
    res, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta = saved
    (dg, drx, dry, drz, dsw, dwq, dwk, dwv, dwo, dgamma, dbeta) = \
        _stack_bwd_call(res, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma,
                        beta, dout, compute_dtype, block_n, interpret)
    return (dg, drx, dry, drz, dsw, jnp.zeros_like(mask),
            dwq, dwk, dwv, dwo, dgamma, dbeta)


_stack.defvjp(_stack_vjp_fwd, _stack_vjp_bwd)


def _split_heads(wq, wk, wv, wo, heads: int):
    """(L, M, H) projections -> (L, heads, M, H/heads); (L, H, M) output
    projection -> (L, heads, H/heads, M).  Differentiable plain reshapes,
    so the kernels' per-head gradients flow back to the stacked layout."""
    layers, m, h = wq.shape
    hd = h // heads
    split = lambda w: w.reshape(layers, m, heads, hd).transpose(0, 2, 1, 3)
    return split(wq), split(wk), split(wv), wo.reshape(layers, heads, hd, m)


def _pad_n(a, pad: int):
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


@functools.partial(jax.jit, static_argnames=("heads", "compute_dtype",
                                             "block_n", "interpret"))
def nbr_attention_stack(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                        heads: int = 1, compute_dtype: str = "float32",
                        block_n: int = 8, interpret: bool = False):
    """l_a fused gated self-attention layers over the neighbor axis.

    g (N, K, M); rx/ry/rz/sw/mask (N, K); stacked per-layer params
    wq/wk/wv (L, M, H), wo (L, H, M), gamma/beta (L, M).  Returns the
    updated (N, K, M).  Differentiable in everything except ``mask`` via
    the fused reverse-sweep backward kernel.
    """
    n = g.shape[0]
    if wq.shape[-1] % heads:
        raise ValueError(f"attn_hidden {wq.shape[-1]} not divisible by "
                         f"heads {heads}")
    pad = (-n) % block_n
    if pad:
        g, rx, ry, rz, sw, mask = (_pad_n(a, pad)
                                   for a in (g, rx, ry, rz, sw, mask))
    wq, wk, wv, wo = _split_heads(wq, wk, wv, wo, heads)
    out = _stack(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                 compute_dtype, block_n, interpret)
    return out[:n] if pad else out


def nbr_attention_layer(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                        block_n: int = 8, interpret: bool = False,
                        heads: int = 1):
    """One gated self-attention layer — the L=1 slice of the fused stack."""
    return nbr_attention_stack(g, rx, ry, rz, sw, mask, wq[None], wk[None],
                               wv[None], wo[None], gamma[None], beta[None],
                               heads=heads, block_n=block_n,
                               interpret=interpret)
