"""Classical force field: bonded terms, LJ, Coulomb (cutoff / reaction field).

This is the empirical-force-field baseline the paper compares the Deep
Potential against (Eq. 1): E = E_bonded + E_sr + E_lr.  ``classical_energy``
is the definition: a pure function of positions whose ``jax.grad`` gives the
conservative forces (Eq. 2).  ``classical_forces`` evaluates the short-range
pair terms analytically in one sweep over the list (one gather of each neighbour's
position, one scatter-add of its reaction force), from per-slot constants
built once per list (:class:`PairTable`); the bonded and PME reciprocal terms
stay on ``jax.grad``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .neighbors import NeighborList, minimum_image
from .system import COULOMB, System


@dataclasses.dataclass(frozen=True)
class ForceFieldConfig:
    cutoff: float = 1.2             # nm (paper Tab. II: r_c = 1.2 EM/NVT/NPT)
    use_reaction_field: bool = True  # RF correction for cutoff Coulomb
    eps_rf: float = 78.5            # solvent dielectric for RF
    use_pme: bool = False           # long-range via smooth PME (md/pme.py)
    pme_grid: tuple = (32, 32, 32)
    pme_order: int = 4
    ewald_beta: float = 3.12        # 1/nm; erfc(beta*rc) ~ 1e-5 at rc=1.2


# ---------------------------------------------------------------------------
# Bonded terms
# ---------------------------------------------------------------------------

def bond_energy(pos, box, bonds, params, mask):
    ri, rj = pos[bonds[:, 0]], pos[bonds[:, 1]]
    dr = minimum_image(rj - ri, box)
    # double-where: masked (padded) entries see a safe r so the backward pass
    # never differentiates sqrt at 0 (NaN * 0 == NaN in the cotangent).
    r2 = jnp.where(mask > 0, (dr ** 2).sum(-1), 1.0)
    r = jnp.sqrt(r2)
    r0, k = params[:, 0], params[:, 1]
    return (0.5 * k * (r - r0) ** 2 * mask).sum()


def angle_energy(pos, box, angles, params, mask):
    ri, rj, rk = pos[angles[:, 0]], pos[angles[:, 1]], pos[angles[:, 2]]
    v1 = minimum_image(ri - rj, box)
    v2 = minimum_image(rk - rj, box)
    nn = (v1 ** 2).sum(-1) * (v2 ** 2).sum(-1)
    cos = (v1 * v2).sum(-1) / jnp.sqrt(jnp.where(mask > 0, nn, 1.0))
    theta = jnp.arccos(jnp.clip(cos, -1 + 1e-7, 1 - 1e-7))
    t0, k = params[:, 0], params[:, 1]
    return (0.5 * k * (theta - t0) ** 2 * mask).sum()


def dihedral_energy(pos, box, dihedrals, params, mask):
    """Periodic proper dihedral: k (1 + cos(mult*phi - phi0))."""
    p = [pos[dihedrals[:, i]] for i in range(4)]
    b1 = minimum_image(p[1] - p[0], box)
    b2 = minimum_image(p[2] - p[1], box)
    b3 = minimum_image(p[3] - p[2], box)
    n1 = jnp.cross(b1, b2)
    n2 = jnp.cross(b2, b3)
    nb2 = jnp.sqrt(jnp.where(mask > 0, (b2 ** 2).sum(-1), 1.0))[:, None]
    m1 = jnp.cross(n1, b2 / nb2)
    x = jnp.where(mask > 0, (n1 * n2).sum(-1), 1.0)
    y = jnp.where(mask > 0, (m1 * n2).sum(-1), 0.0)
    phi = jnp.arctan2(y, x)
    phi0, k, mult = params[:, 0], params[:, 1], params[:, 2]
    return (k * (1 + jnp.cos(mult * phi - phi0)) * mask).sum()


def bonded_energy(pos, box, topology) -> jax.Array:
    t = topology
    return (bond_energy(pos, box, t.bonds, t.bond_params, t.bond_mask)
            + angle_energy(pos, box, t.angles, t.angle_params, t.angle_mask)
            + dihedral_energy(pos, box, t.dihedrals, t.dihedral_params,
                              t.dihedral_mask))


# ---------------------------------------------------------------------------
# Non-bonded short range (neighbor-list driven)
# ---------------------------------------------------------------------------

def _pair_mask(system: System, nlist: NeighborList) -> jax.Array:
    """Neighbor-list mask minus exclusions minus NN-NN pairs (NNPot contract)."""
    idx = nlist.idx
    n, k = idx.shape
    safe = jnp.where(idx >= 0, idx, 0)
    excl = system.topology.exclusions                      # (N, E)
    excluded = (idx[:, :, None] == excl[:, None, :]).any(-1)
    nn_nn = (system.nn_mask[:, None] * system.nn_mask[safe]) > 0.5
    return nlist.mask * (~excluded) * (~nn_nn)


def lj_energy(pos: jax.Array, system: System, nlist: NeighborList,
              cutoff: float, half: bool) -> jax.Array:
    idx = nlist.idx
    safe = jnp.where(idx >= 0, idx, 0)
    dr = minimum_image(pos[safe] - pos[:, None, :], system.box)
    r2 = (dr ** 2).sum(-1)
    mask = _pair_mask(system, nlist) * (r2 < cutoff ** 2)
    r2 = jnp.where(mask > 0, r2, 1.0)

    # Lorentz-Berthelot combining rules from per-type tables.
    si = system.lj_sigma[system.types][:, None]
    sj = system.lj_sigma[system.types[safe]]
    ei = system.lj_epsilon[system.types][:, None]
    ej = system.lj_epsilon[system.types[safe]]
    sig = 0.5 * (si + sj)
    eps = jnp.sqrt(ei * ej)

    sr2 = sig ** 2 / r2
    sr6 = sr2 ** 3
    e = 4.0 * eps * (sr6 ** 2 - sr6)
    # shift so E(r_c) = 0 (GROMACS potential-shift modifier)
    src6 = (sig ** 2 / cutoff ** 2) ** 3
    e = e - 4.0 * eps * (src6 ** 2 - src6)
    total = (e * mask).sum()
    return total if half else 0.5 * total


def _rf_constants(cfg: ForceFieldConfig) -> tuple[float, float]:
    """Reaction field E = qq (1/r + k_rf r^2 - c_rf), zero at r_c."""
    rc, eps = cfg.cutoff, cfg.eps_rf
    k_rf = (eps - 1.0) / (2 * eps + 1.0) / rc ** 3
    return k_rf, 1.0 / rc + k_rf * rc ** 2


def coulomb_energy(pos: jax.Array, system: System, nlist: NeighborList,
                   cfg: ForceFieldConfig, half: bool) -> jax.Array:
    """Cutoff Coulomb with reaction-field, or Ewald real-space when PME is on."""
    idx = nlist.idx
    safe = jnp.where(idx >= 0, idx, 0)
    dr = minimum_image(pos[safe] - pos[:, None, :], system.box)
    r2 = (dr ** 2).sum(-1)
    rc = cfg.cutoff
    mask = _pair_mask(system, nlist) * (r2 < rc ** 2)
    r = jnp.sqrt(jnp.where(mask > 0, r2, 1.0))
    qq = system.charges[:, None] * system.charges[safe]

    if cfg.use_pme:
        # real-space Ewald term; reciprocal handled in md/pme.py
        e = COULOMB * qq * jax.scipy.special.erfc(cfg.ewald_beta * r) / r
    else:
        k_rf, c_rf = _rf_constants(cfg)
        e = COULOMB * qq * (1.0 / r + k_rf * r2 - c_rf)
    total = (e * mask).sum()
    return total if half else 0.5 * total


# ---------------------------------------------------------------------------
# Short range in one analytic sweep
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PairTable:
    """What the short-range pair terms need of a list, per ``(N, K)`` slot,
    fixed until the list is rebuilt: E_ij = c12/r^12 - c6/r^6 + qq f(r) -
    shift inside the cutoff, all float32 ``(N, K)``; qq = COULOMB q_i q_j
    comes with the positions (:func:`pair_forces`)."""
    mask: jax.Array   # list mask minus exclusions minus NN-NN pairs
    c6: jax.Array     # 4 eps sigma^6, Lorentz-Berthelot
    c12: jax.Array    # 4 eps sigma^12
    shift: jax.Array  # E_ij(r_c) without it: LJ and reaction-field shifts


# Row blocks of the pair sweep and the table build.  The TPU gathers and
# scatters one lane-padded row per list slot, so a block of N/8 rows bounds
# those buffers to an eighth of N K x 128 lanes; on a v5e the sweep at the
# benchmark cell's shapes also ran faster so (27.3 ms against 31.3 ms).
_ROW_BLOCKS = 8


def _row_blocks(x: jax.Array) -> jax.Array:
    """``(N, ...)`` as ``(blocks, rows, ...)``, the last block zero-padded."""
    n = x.shape[0]
    blocks = min(_ROW_BLOCKS, n)
    rows = -(-n // blocks)
    x = jnp.pad(x, ((0, rows * blocks - n),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((blocks, rows) + x.shape[1:])


def pair_table(system: System, nlist: NeighborList,
               cfg: ForceFieldConfig) -> PairTable:
    """The list's pair constants, from one gather of each neighbour's packed
    (sigma, sqrt eps, q, NN flag).  Exclusions are compared one column at a
    time, so nothing wider than ``(N, K)`` is formed."""
    n, k = nlist.idx.shape
    atoms = jnp.stack([system.lj_sigma[system.types],
                       jnp.sqrt(system.lj_epsilon[system.types]),
                       system.charges, system.nn_mask], 1)
    src6 = 1.0 / cfg.cutoff ** 6

    def block(b):
        idx, mask, excl, ai = b
        aj = atoms[jnp.where(idx >= 0, idx, 0)]
        ai = ai[:, None, :]
        keep = mask > 0
        for c in range(excl.shape[1]):
            keep &= idx != excl[:, c:c + 1]
        keep &= ai[..., 3] * aj[..., 3] <= 0.5
        sig6 = (0.5 * (ai[..., 0] + aj[..., 0])) ** 6
        c6 = 4.0 * ai[..., 1] * aj[..., 1] * sig6
        c12 = c6 * sig6
        shift = c12 * src6 ** 2 - c6 * src6
        if not cfg.use_pme:
            qq = COULOMB * ai[..., 2] * aj[..., 2]
            shift = shift + qq * _rf_constants(cfg)[1]
        return PairTable(mask=keep.astype(jnp.float32), c6=c6, c12=c12,
                         shift=shift)

    table = jax.lax.map(block, tuple(map(_row_blocks, (
        nlist.idx, nlist.mask, system.topology.exclusions, atoms))))
    return jax.tree.map(lambda x: x.reshape(-1, k)[:n], table)


def pair_forces(pos: jax.Array, system: System, nlist: NeighborList,
                table: PairTable, cfg: ForceFieldConfig,
                half: bool = True) -> tuple[jax.Array, jax.Array]:
    """Short-range energy and forces (LJ plus reaction field, or LJ plus the
    Ewald real-space term) in one sweep over the list, ``N / 8`` rows at a
    time.

    Each slot gathers its neighbour's packed (x, y, z, q).  With s =
    (dE/dr)/r and d = r_j - r_i, atom i takes the row sum of s d; on a half
    list atom j takes -s d, and the pair energy, by one scatter-add of packed
    rows.  A full list holds each pair in both rows, so the row sums are the
    whole force and the energy is halved."""
    n = nlist.idx.shape[0]
    box = system.box
    atoms = jnp.concatenate([pos, system.charges[:, None]], 1)

    def sweep(acc, block):
        j, mask, c6, c12, shift, ai = block
        aj = atoms[j]
        d = []
        for a in range(3):
            da = aj[..., a] - ai[:, a:a + 1]
            d.append(da - box[a] * jnp.round(da / box[a]))
        qq = COULOMB * ai[:, 3:] * aj[..., 3]
        r2 = d[0] ** 2 + d[1] ** 2 + d[2] ** 2
        m = mask * (r2 < cfg.cutoff ** 2)
        r2 = jnp.where(m > 0, r2, 1.0)
        ir2 = 1.0 / r2
        ir6 = ir2 ** 3
        lj12 = c12 * ir6 ** 2
        lj6 = c6 * ir6
        e = lj12 - lj6 - shift
        s = (6.0 * lj6 - 12.0 * lj12) * ir2
        ir = jax.lax.rsqrt(r2)
        if cfg.use_pme:
            br = cfg.ewald_beta * ir * r2
            erfc = jax.scipy.special.erfc(br)
            e = e + qq * erfc * ir
            s = s - qq * (erfc * ir + 2.0 * cfg.ewald_beta / np.sqrt(np.pi)
                          * jnp.exp(-br * br)) * ir2
        else:
            k_rf = _rf_constants(cfg)[0]
            e = e + qq * (ir + k_rf * r2)
            s = s + qq * (2.0 * k_rf - ir * ir2)
        e = e * m
        sd = [s * m * da for da in d]
        f_i = jnp.stack([x.sum(1) for x in sd], 1)
        if half:
            acc = acc.at[j].add(jnp.stack([-x for x in sd] + [e], -1))
            return acc, f_i
        return acc + e.sum(), f_i

    safe = jnp.where(nlist.idx >= 0, nlist.idx, 0)
    acc0 = jnp.zeros((n, 4) if half else (), pos.dtype)
    acc, f_i = jax.lax.scan(sweep, acc0, tuple(map(_row_blocks, (
        safe, table.mask, table.c6, table.c12, table.shift, atoms))))
    f = f_i.reshape(-1, 3)[:n]
    if half:
        return acc[:, 3].sum(), f + acc[:, :3]
    return 0.5 * acc, f


# ---------------------------------------------------------------------------
# Total classical energy / forces
# ---------------------------------------------------------------------------

def _bonded_and_reciprocal_energy(pos: jax.Array, system: System,
                                  cfg: ForceFieldConfig) -> jax.Array:
    """Everything but the short-range pair terms."""
    e = bonded_energy(pos, system.box, system.topology)
    if cfg.use_pme:
        from .pme import pme_reciprocal_energy
        e += pme_reciprocal_energy(pos, system.charges, system.box,
                                   cfg.pme_grid, cfg.pme_order, cfg.ewald_beta)
        # Ewald self-energy
        e -= COULOMB * cfg.ewald_beta / jnp.sqrt(jnp.pi) * (system.charges ** 2).sum()
    return e


def classical_energy(pos: jax.Array, system: System, nlist: NeighborList,
                     cfg: ForceFieldConfig, half: bool = True) -> jax.Array:
    e = _bonded_and_reciprocal_energy(pos, system, cfg)
    e += lj_energy(pos, system, nlist, cfg.cutoff, half)
    e += coulomb_energy(pos, system, nlist, cfg, half)
    return e


def classical_forces(pos, system, nlist, cfg, half: bool = True):
    """Energy and forces: the pair terms from :func:`pair_forces` with the
    list's own table (built here when the list carries none), the rest from
    ``jax.grad``."""
    table = nlist.pairs if nlist.pairs is not None else pair_table(
        system, nlist, cfg)
    e, f = pair_forces(pos, system, nlist, table, cfg, half)
    e_rest, g = jax.value_and_grad(_bonded_and_reciprocal_energy)(
        pos, system, cfg)
    return e + e_rest, f - g
