"""Jit'd public wrappers around the Pallas kernels.

``use_pallas`` selects the kernel path; the default jnp path is used by the
dry-run and as the autodiff-friendly fallback.  How a kernel runs is decided
when it is traced, from the backend JAX runs on (:func:`interpret_mode`):
compiled Mosaic on a TPU, the Pallas interpreter on the CPU (the tests), and
an error anywhere else.  ``interpret=`` overrides the decision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .cell_gather import cell_filter
from .env_mat import env_mat
from .flash_attn import flash_attention
from .nbr_attn import nbr_attention_layer, nbr_attention_stack



def interpret_mode() -> bool:
    """Trace-time choice between compiled Mosaic and the Pallas interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU or run interpreted on the CPU; "
        f"backend {backend!r} has neither (use use_pallas=False)")


def _interpret(interpret):
    return interpret_mode() if interpret is None else interpret


def _pad_lanes(x, mult: int = 128):
    k = x.shape[-1]
    pad = (-k) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, k


def env_mat_op(dx, dy, dz, mask, rcut_smth: float, rcut: float,
               use_pallas: bool = False, interpret=None):
    """Env-matrix planes; pads the neighbor axis to 128 lanes for TPU."""
    if not use_pallas:
        return ref.env_mat_ref(dx, dy, dz, mask, rcut_smth, rcut)
    (dxp, k0), (dyp, _), (dzp, _), (mp, _) = (
        _pad_lanes(dx), _pad_lanes(dy), _pad_lanes(dz), _pad_lanes(mask))
    s, sx, sy, sz = env_mat(dxp, dyp, dzp, mp, rcut_smth, rcut,
                            interpret=_interpret(interpret))
    cut = lambda a: a[..., :k0]
    return cut(s), cut(sx), cut(sy), cut(sz)


def cell_filter_op(dx, dy, dz, valid, rcut: float,
                   use_pallas: bool = False, interpret=None):
    """Within-cutoff flags for cell candidates; pads lanes to 128 for TPU."""
    if not use_pallas:
        return ref.cell_filter_ref(dx, dy, dz, valid, rcut)
    (dxp, m0), (dyp, _), (dzp, _), (vp, _) = (
        _pad_lanes(dx), _pad_lanes(dy), _pad_lanes(dz), _pad_lanes(valid))
    return cell_filter(dxp, dyp, dzp, vp, rcut,
                       interpret=_interpret(interpret))[..., :m0]


def nbr_attention_op(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma, beta,
                     heads: int = 1, use_pallas: bool = False,
                     interpret=None):
    if not use_pallas:
        return ref.nbr_attention_layer_ref(g, rx, ry, rz, sw, mask,
                                           wq, wk, wv, wo, gamma, beta,
                                           heads=heads)
    return nbr_attention_layer(g, rx, ry, rz, sw, mask, wq, wk, wv, wo,
                               gamma, beta, heads=heads,
                               interpret=_interpret(interpret))


def nbr_attention_stack_op(g, rx, ry, rz, sw, mask, wq, wk, wv, wo, gamma,
                           beta, heads: int = 1,
                           compute_dtype: str = "float32",
                           use_pallas: bool = False,
                           interpret=None):
    """The fused l_a-layer DPA-1 attention stack (differentiable both ways).

    The jnp path autodiffs through the reference; the Pallas path carries a
    custom VJP whose backward is a fused reverse-sweep kernel.  Params are
    stacked along a leading layer axis: wq/wk/wv (L, M, H), wo (L, H, M),
    gamma/beta (L, M).
    """
    if not use_pallas:
        return ref.nbr_attention_stack_ref(g, rx, ry, rz, sw, mask, wq, wk,
                                           wv, wo, gamma, beta, heads=heads,
                                           compute_dtype=compute_dtype)
    return nbr_attention_stack(g, rx, ry, rz, sw, mask, wq, wk, wv, wo,
                               gamma, beta, heads=heads,
                               compute_dtype=compute_dtype,
                               interpret=_interpret(interpret))


def attention_op(q, k, v, causal: bool = True, window: int = 0,
                 softcap: float = 0.0, q_offset: int = 0,
                 use_pallas: bool = False,
                 interpret=None):
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal, window, softcap, q_offset)
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, q_offset=q_offset,
                           interpret=_interpret(interpret))
