"""Deep Potential model: descriptor + fitting net, autodiff forces, Eq. 7 masking.

The model maps (coords, types, neighbor list) -> per-atom energies e_i;
E = sum_i m_i e_i over *local* atoms only (ghost contributions masked,
paper Eq. 7), and F = -dE/dr via reverse-mode AD, so forces on ghost atoms
(-dE_local/dr_ghost) come out of the same gradient and are reduced onto the
owning rank by the distributed layer (repro.core).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import precision
from .common import EnvStats
from .descriptors import DescriptorConfig, apply_descriptor, init_descriptor
from .networks import count_params, mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class DPConfig:
    descriptor: DescriptorConfig = dataclasses.field(default_factory=DescriptorConfig)
    fitting_neuron: tuple = (256, 256, 256)  # paper: 3 x 256
    dtype: str = "float32"                   # "float32" (paper) | "bfloat16"
    #   mixed-precision policy (repro.dp.precision): bf16 matmul operands
    #   with fp32 accumulation; env matrix / reductions / forces stay fp32

    @property
    def ntypes(self) -> int:
        return self.descriptor.ntypes


def paper_dpa1_config(ntypes: int = 4, rcut: float = 0.6, sel: int = 64,
                      dtype: str = "float32",
                      use_pallas: bool = False) -> DPConfig:
    """The paper's in-house DPA-1: emb (32,64,128), 3 attn x 256, fit 3 x 256."""
    return DPConfig(descriptor=DescriptorConfig(
        kind="dpa1", rcut=rcut, rcut_smth=max(rcut - 0.3, 0.15), sel=sel,
        ntypes=ntypes, neuron=(32, 64, 128), axis_neuron=16,
        attn_layers=3, attn_hidden=256, use_pallas=use_pallas), dtype=dtype)


class DPModel:
    """Stateless apply-style model; params live in an external pytree."""

    def __init__(self, cfg: DPConfig, stats: Optional[EnvStats] = None):
        precision.validate_dtype(cfg.dtype)
        cfg.descriptor.validate()
        self.cfg = cfg
        self.stats = stats if stats is not None else EnvStats.identity(cfg.ntypes)

    # -- params -------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> dict:
        kd, kf, kb = jax.random.split(rng, 3)
        d = self.cfg.descriptor
        fit_sizes = (d.out_dim,) + tuple(self.cfg.fitting_neuron) + (1,)
        return {
            "descriptor": init_descriptor(kd, d),
            "fitting": mlp_init(kf, fit_sizes),
            "bias": jnp.zeros((d.ntypes,)),  # per-species energy bias
        }

    def n_params(self, params) -> int:
        return count_params(params)

    # -- core forward ---------------------------------------------------------

    def atomic_energies(self, params, coords_center, coords_nbr, types_center,
                        types_nbr, nbr_mask, atom_mask) -> jax.Array:
        """e_i for every center atom (padded atoms -> 0)."""
        desc = apply_descriptor(params["descriptor"], self.cfg.descriptor,
                                self.stats, coords_center, coords_nbr,
                                types_center, types_nbr, nbr_mask,
                                dtype=self.cfg.dtype)
        with jax.named_scope("dp.fitting"):
            e = mlp_apply(params["fitting"], desc,
                          compute_dtype=precision.compute_dtype(
                              self.cfg.dtype))[..., 0]
            e = e + params["bias"][jnp.clip(types_center, 0)]
            return e * atom_mask

    def _atomic_e(self, params, coords, types, nbr_idx, nbr_mask, box=None):
        """(C,) per-atom energies over a buffer; padded-neighbor safe."""
        with jax.named_scope("dp.nbr_gather"):
            safe = jnp.where(nbr_idx >= 0, nbr_idx, 0)
            coords_nbr = coords[safe]
            if box is not None:
                dr = coords_nbr - coords[:, None, :]
                dr = dr - box * jnp.round(dr / box)
                coords_nbr = coords[:, None, :] + dr
            types_nbr = types[safe]
        return self.atomic_energies(params, coords, coords_nbr, types,
                                    types_nbr, nbr_mask,
                                    jnp.ones(coords.shape[0], coords.dtype))

    def total_energy(self, params, coords, types, nbr_idx, nbr_mask,
                     local_mask, box=None) -> jax.Array:
        """E = sum_i m_i e_i  (Eq. 7 masking: m_i = 1 local, 0 ghost/pad).

        coords (C,3) local+ghost buffer; nbr_idx (C,K) indices *into coords*;
        PBC handled by minimum image when ``box`` is given (single-domain
        path) — the DD path pre-shifts ghost images so box=None there.
        """
        e = self._atomic_e(params, coords, types, nbr_idx, nbr_mask, box)
        return (e * local_mask).sum()

    def energy_and_forces(self, params, coords, types, nbr_idx, nbr_mask,
                          local_mask, box=None):
        """Forces on *all* atoms in the buffer, including ghosts (Eq. 7:
        ghost forces are -dE_local/dr_ghost and must be reduced by the DD
        layer onto the owners)."""
        e, g = jax.value_and_grad(self.total_energy, argnums=1)(
            params, coords, types, nbr_idx, nbr_mask, local_mask, box)
        return e, -g

    def energy_and_forces_dual(self, params, coords, types, nbr_idx, nbr_mask,
                               force_mask, report_mask, box=None):
        """Paper-faithful "owner computes full local forces" mode (Sec. IV-A):

        the force field differentiates sum(e * force_mask) (local + complete-
        descriptor ghosts — valid thanks to the 2*r_c halo), while the
        *reported* energy is sum(e * report_mask) (local only, so the psum
        over ranks counts every atom exactly once).
        """
        def fsum(c):
            e = self._atomic_e(params, c, types, nbr_idx, nbr_mask, box)
            return (e * force_mask).sum(), (e * report_mask).sum()

        (_, e_rep), g = jax.value_and_grad(fsum, has_aux=True)(coords)
        return e_rep, -g

    def energy_and_forces_batched(self, params, coords, types, nbr_idx,
                                  nbr_mask, local_mask, box=None):
        """Replica-batched :meth:`energy_and_forces`: every positional tensor
        carries a leading replica axis (coords (R, C, 3), nbr_idx (R, C, K),
        ...) except ``types``, which may be shared ((C,)) or per-replica
        ((R, C)).  Params and box are shared.  Returns (energy (R,), forces
        (R, C, 3)) from a single vmapped dispatch — the ensemble layer's
        amortization of R sequential model calls."""
        t_axis = 0 if jnp.ndim(types) == 2 else None
        fn = lambda c, t, i, m, lm: self.energy_and_forces(
            params, c, t, i, m, lm, box)
        return jax.vmap(fn, in_axes=(0, t_axis, 0, 0, 0))(
            coords, types, nbr_idx, nbr_mask, local_mask)

    def energy_forces_virial(self, params, coords, types, nbr_idx, nbr_mask,
                             local_mask, box=None):
        e, f = self.energy_and_forces(params, coords, types, nbr_idx,
                                      nbr_mask, local_mask, box)
        virial = -(coords[:, :, None] * f[:, None, :]).sum(0)
        return e, f, virial
