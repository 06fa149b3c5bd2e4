"""Pallas TPU kernel: blockwise (flash) attention for the LM substrate.

Memory-efficient attention with running-softmax accumulation over KV blocks:
never materializes the (S x S) score matrix in HBM.  Supports the attention
variants the assigned architecture pool needs:

  * causal masking (decoder LMs),
  * GQA (q_heads = g * kv_heads; the wrapper maps q-head -> kv-head),
  * sliding-window masking (gemma2 local layers — the sequence-space analogue
    of the paper's cutoff radius),
  * logit soft-capping (gemma2).

Grid: (batch*q_heads, q_blocks); the kernel loops over kv blocks with
``jax.lax.fori_loop`` keeping (m, l, acc) in VMEM registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_k: int,
                  causal: bool, window: int, softcap: float, q_offset: int):
    q = q_ref[...][0]                       # (block_q, d)
    block_q, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qi = pl.program_id(1) * block_q + q_offset  # absolute q position base

    acc = jnp.zeros((block_q, d), jnp.float32)
    m_i = jnp.full((block_q,), NEG_INF, jnp.float32)
    l_i = jnp.zeros((block_q,), jnp.float32)

    n_kv = seq_k // block_k

    def body(j, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]   # (block_k, d)
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = qi + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m_i, s.max(-1))
        # mask again post-exp: fully-masked rows have m_new == NEG_INF and
        # exp(NEG_INF - NEG_INF) == 1 would poison the accumulator
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_i - m_new)
        l_i = l_i * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + jnp.dot(p, v.astype(jnp.float32))
        return acc, m_new, l_i

    acc, m_i, l_i = jax.lax.fori_loop(0, n_kv, body, (acc, m_i, l_i))
    l_safe = jnp.where(l_i > 0, l_i, 1.0)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "q_offset",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    interpret: bool = False) -> jax.Array:
    """q (B, Hq, Sq, D); k/v (B, Hkv, Sk, D); Hq % Hkv == 0.

    Returns (B, Hq, Sq, D).  Sq/Sk padded to block sizes internally.
    ``q_offset`` positions queries within the kv sequence (prefill chunks).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k

    # flatten (B, H) into one grid axis; kv head broadcast for GQA
    qf = qp.reshape(b * hq, sqp, d)
    kv_head = (jnp.arange(b * hq) % hq) // group + (jnp.arange(b * hq) // hq) * hkv
    kf = kp.reshape(b * hkv, skp, d)[kv_head]
    vf = vp.reshape(b * hkv, skp, d)[kv_head]

    grid = (b * hq, sqp // block_q)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, seq_k=skp, causal=causal,
        window=window, softcap=softcap, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
                  pl.BlockSpec((1, skp, d), lambda h, i: (h, 0, 0)),
                  pl.BlockSpec((1, skp, d), lambda h, i: (h, 0, 0))],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sqp, d)[:, :, :sq, :]
