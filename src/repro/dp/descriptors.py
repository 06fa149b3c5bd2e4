"""DP-SE and DPA-1 descriptors (paper Fig. 3a/3b).

Both are *strictly local*: descriptor D^i depends only on atoms inside one
cutoff of atom i — the property that makes the paper's 2*r_c-halo virtual
domain decomposition exact.  Message-passing families (DPA-2/3) are out of
scope by the paper's own argument (Sec. IV-A) and are documented in DESIGN.md.

DP-SE   : D^i = (G^i)^T R~ (R~)^T G^i_r            (bilinear reduction)
DPA-1   : same reduction, but G^i is refined by l_a gated self-attention
          layers over the neighbor axis; the gate injects the angular
          correlation r_hat . r_hat^T (se_attention_v2).

Hot-path routing: ``DescriptorConfig.use_pallas`` sends the environment
matrix and the whole attention stack through the fused Pallas kernels in
``repro.kernels`` (differentiable — both carry custom VJPs with fused
backward kernels, so ``jax.value_and_grad`` forces run kernel-to-kernel);
the default jnp path autodiffs through the references.  ``DPConfig.dtype``
selects the mixed-precision policy (``repro.dp.precision``): matmul/attention
operands in bf16 with fp32 accumulation, env matrix / switch envelope /
bilinear reduction always fp32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import precision
from .common import EnvStats, _guarded_env, env_matrix_shifted
from .networks import layer_norm_init, mlp_apply, mlp_init
from ..kernels.ops import env_mat_op, nbr_attention_stack_op


@dataclasses.dataclass(frozen=True)
class DescriptorConfig:
    kind: str = "dpa1"            # "dpse" | "dpa1"
    rcut: float = 0.6             # nm (paper MD runs use r_c = 0.8/2 per-model; configurable)
    rcut_smth: float = 0.2
    sel: int = 64                 # neighbor capacity K
    ntypes: int = 4
    neuron: tuple = (32, 64, 128)  # embedding net widths (paper Sec. IV-B)
    axis_neuron: int = 16         # M2: columns of G kept for the right factor
    type_embed_dim: int = 8
    attn_layers: int = 3          # l_a (paper: three attention layers)
    attn_hidden: int = 256        # paper: hidden size 256
    attn_heads: int = 1           # multi-head split (attn_hidden % heads == 0)
    use_pallas: bool = False      # fused descriptor kernels vs jnp reference

    @property
    def m1(self) -> int:
        return self.neuron[-1]

    @property
    def out_dim(self) -> int:
        return self.m1 * self.axis_neuron

    def validate(self) -> None:
        if self.kind == "dpa1" and self.attn_hidden % self.attn_heads:
            raise ValueError(
                f"attn_hidden {self.attn_hidden} not divisible by "
                f"attn_heads {self.attn_heads}")


def init_descriptor(rng: jax.Array, cfg: DescriptorConfig) -> dict:
    cfg.validate()
    k_emb, k_type, k_attn = jax.random.split(rng, 3)
    params: dict = {}
    # type embedding table (+1 slot for padding type -1 -> clipped to 0 w/ mask)
    params["type_embed"] = 0.1 * jax.random.normal(
        k_type, (cfg.ntypes, cfg.type_embed_dim))
    # embedding net: input [s(r), type_emb_j] -> neuron widths
    in_dim = 1 + cfg.type_embed_dim
    params["embed"] = mlp_init(k_emb, (in_dim,) + tuple(cfg.neuron))
    if cfg.kind == "dpa1" and cfg.attn_layers > 0:
        layers = []
        for k in jax.random.split(k_attn, cfg.attn_layers):
            kq, kk, kv, ko = jax.random.split(k, 4)
            d, h = cfg.m1, cfg.attn_hidden
            layers.append({
                "wq": jax.random.normal(kq, (d, h)) / jnp.sqrt(d),
                "wk": jax.random.normal(kk, (d, h)) / jnp.sqrt(d),
                "wv": jax.random.normal(kv, (d, h)) / jnp.sqrt(d),
                "wo": jax.random.normal(ko, (h, d)) / jnp.sqrt(h),
                "ln": layer_norm_init(d),
            })
        params["attn"] = layers
    return params


def _stack_params(layers: list[dict]):
    """Per-layer param dicts -> the (L, ...) stacked layout the fused
    attention kernel consumes (a cheap concat; XLA folds it)."""
    get = lambda name: jnp.stack([l[name] for l in layers])
    return (get("wq"), get("wk"), get("wv"), get("wo"),
            jnp.stack([l["ln"]["gamma"] for l in layers]),
            jnp.stack([l["ln"]["beta"] for l in layers]))


def _env_planes_pallas(coords_center, coords_nbr, nbr_mask, cfg):
    """Env-matrix planes + gate inputs for the kernel path.

    The four (s, s*x/r, ...) planes come from the fused ``env_mat`` kernel
    (custom VJP); dist/r_hat for the angular gate come from the same
    ``_guarded_env`` helper as the jnp path (shared zero-distance clamp) —
    elementwise, not the dominant FLOPs, and autodiff-safe.  The helper's
    redundant switch value is dead code XLA eliminates.
    """
    dr = coords_nbr - coords_center[:, None, :]
    s, sx, sy, sz = env_mat_op(dr[..., 0], dr[..., 1], dr[..., 2], nbr_mask,
                               cfg.rcut_smth, cfg.rcut, use_pallas=True)
    R = jnp.stack([s, sx, sy, sz], axis=-1)
    dist, _, r_hat = _guarded_env(dr, nbr_mask, cfg.rcut_smth, cfg.rcut)
    return R, r_hat * nbr_mask[..., None], dist, s


def apply_descriptor(params: dict, cfg: DescriptorConfig, stats: EnvStats,
                     coords_center: jax.Array, coords_nbr: jax.Array,
                     types_center: jax.Array, types_nbr: jax.Array,
                     nbr_mask: jax.Array, dtype: str = "float32") -> jax.Array:
    """Compute D^i for every center atom.

    coords_center (N,3); coords_nbr (N,K,3) pre-gathered (PBC shifts applied);
    types_* int32 (-1 padding); nbr_mask (N,K).
    Returns descriptors (N, M1*M2), always fp32 — ``dtype`` only drops the
    matmul-operand precision inside (see ``repro.dp.precision``).
    """
    cfg.validate()
    cd = precision.compute_dtype(dtype)
    # dp.* name scopes: HLO metadata naming the descriptor's stages
    with jax.named_scope("dp.env_mat"):
        if cfg.use_pallas:
            R, r_hat, dist, sw = _env_planes_pallas(coords_center,
                                                    coords_nbr, nbr_mask, cfg)
        else:
            R, r_hat, dist, sw = env_matrix_shifted(coords_center,
                                                    coords_nbr, nbr_mask,
                                                    cfg.rcut_smth, cfg.rcut)
        R = stats.normalize(R, types_center) * nbr_mask[..., None]

    with jax.named_scope("dp.embedding"):
        t_emb = params["type_embed"][jnp.clip(types_nbr, 0)]
        feat = jnp.concatenate([sw[..., None], t_emb * nbr_mask[..., None]],
                               -1)
        g = mlp_apply(params["embed"], feat, compute_dtype=cd)  # (N, K, M1)
        g = g * nbr_mask[..., None]

    if cfg.kind == "dpa1" and cfg.attn_layers > 0:
        with jax.named_scope("dp.attention"):
            sw_env = sw * dist  # the [0,1] polynomial envelope from s(r)
            g = nbr_attention_stack_op(
                g, r_hat[..., 0], r_hat[..., 1], r_hat[..., 2], sw_env,
                nbr_mask, *_stack_params(params["attn"]),
                heads=cfg.attn_heads, compute_dtype=dtype,
                use_pallas=cfg.use_pallas)

    # bilinear G^T R R^T G reduction: always fp32 (force-critical)
    with jax.named_scope("dp.descriptor_reduce"):
        k_norm = 1.0 / cfg.sel
        g = g.astype(jnp.float32)
        R = R.astype(jnp.float32)
        gr = jnp.einsum("nkm,nka->nma", g, R) * k_norm     # (N, M1, 4)
        d = jnp.einsum("nma,npa->nmp", gr, gr[:, : cfg.axis_neuron, :])
        return d.reshape(d.shape[0], -1)                   # (N, M1*M2)
