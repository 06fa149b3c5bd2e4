"""Molecular system description: atoms, topology, box.

This is the GROMACS-substrate layer: a ``System`` carries everything the
classical force field and the NNPot special-force hook need.  All arrays are
fixed-shape JAX arrays so the whole engine jits.

Units (GROMACS convention):
  length nm, time ps, energy kJ/mol, mass amu, charge e.
  kB = 0.00831446261815324 kJ/(mol K).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

KB = 0.00831446261815324  # kJ/(mol K)
COULOMB = 138.935458  # kJ mol^-1 nm e^-2  (1/(4 pi eps0))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Topology:
    """Bonded topology with fixed-capacity index arrays.

    ``bonds``  (B, 2) int32 atom indices, ``bond_params`` (B, 2) = (r0, k)
    ``angles`` (A, 3) int32,  ``angle_params`` (A, 2) = (theta0, k)
    ``dihedrals`` (D, 4) int32, ``dihedral_params`` (D, 3) = (phi0, k, mult)
    ``exclusions`` (N, EMAX) int32 padded with -1: short-range-excluded
    partners per atom (bonded 1-2/1-3 pairs plus the NNPot group).
    Masks are float {0,1} so removed entries contribute nothing.
    """

    bonds: jax.Array
    bond_params: jax.Array
    bond_mask: jax.Array
    angles: jax.Array
    angle_params: jax.Array
    angle_mask: jax.Array
    dihedrals: jax.Array
    dihedral_params: jax.Array
    dihedral_mask: jax.Array
    exclusions: jax.Array  # (N, EMAX) int32, -1 padded

    @property
    def n_bonds(self) -> int:
        return self.bonds.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class System:
    """Complete simulation system (static description, not dynamic state)."""

    box: jax.Array            # (3,) orthorhombic box lengths [nm]
    types: jax.Array          # (N,) int32 species index (into LJ tables / DP types)
    masses: jax.Array         # (N,) float
    charges: jax.Array        # (N,) float [e]
    lj_sigma: jax.Array       # (T,) per-type sigma [nm]
    lj_epsilon: jax.Array     # (T,) per-type epsilon [kJ/mol]
    topology: Topology
    nn_mask: jax.Array        # (N,) float {0,1}: 1 = NNPot ("DP group") atom

    @property
    def n_atoms(self) -> int:
        return self.types.shape[0]

    @property
    def n_types(self) -> int:
        return self.lj_sigma.shape[0]


def _pad_rows(rows: list[list[int]], width: int, n: int) -> np.ndarray:
    out = np.full((n, width), -1, dtype=np.int32)
    for i, r in enumerate(rows):
        r = sorted(set(r))[:width]
        out[i, : len(r)] = r
    return out


def build_exclusions(n_atoms: int, bonds: np.ndarray, angles: np.ndarray,
                     extra_pairs: Optional[np.ndarray] = None,
                     width: int = 16) -> np.ndarray:
    """1-2 and 1-3 exclusions (GROMACS default nrexcl-ish) + extra pairs."""
    rows: list[list[int]] = [[] for _ in range(n_atoms)]

    def add(i, j):
        if i != j:
            rows[int(i)].append(int(j))
            rows[int(j)].append(int(i))

    for i, j in bonds:
        add(i, j)
    for i, j, k in angles:
        add(i, j), add(j, k), add(i, k)
    if extra_pairs is not None:
        for i, j in extra_pairs:
            add(i, j)
    return _pad_rows(rows, width, n_atoms)


def mark_nn_group(system: System, nn_indices: np.ndarray,
                  exclude_within_group: bool = True) -> System:
    """NNPot preprocessing (paper Sec. IV-A).

    Marked ("NN") atoms lose their bonded interactions, and pairs *within*
    the group are added to the exclusion lists so no short-range classical
    interaction is double counted against the Deep Potential.  Long-range
    Coulomb is left untouched (evaluated as usual by the classical engine).
    """
    nn_indices = np.asarray(nn_indices, dtype=np.int32)
    nn_mask = np.zeros(system.n_atoms, dtype=np.float32)
    nn_mask[nn_indices] = 1.0
    in_group = lambda idx: nn_mask[np.asarray(idx)].all(axis=-1)

    top = system.topology
    bond_mask = np.asarray(top.bond_mask) * (1.0 - in_group(np.asarray(top.bonds)))
    angle_mask = np.asarray(top.angle_mask) * (1.0 - in_group(np.asarray(top.angles)))
    dih_mask = np.asarray(top.dihedral_mask) * (1.0 - in_group(np.asarray(top.dihedrals)))

    exclusions = np.asarray(top.exclusions)
    if exclude_within_group and len(nn_indices) > 1:
        # Widen exclusion table to hold the full NN-NN clique.  For big NN
        # groups the pair loop instead masks on nn_mask[i]*nn_mask[j]; the
        # table-based route is exact for the sizes used in tests.
        width = max(exclusions.shape[1], min(len(nn_indices) - 1 + 8, 64))
        rows = [[int(x) for x in row if x >= 0] for row in exclusions]
        small = len(nn_indices) <= width
        if small:
            for i in nn_indices:
                rows[int(i)].extend(int(j) for j in nn_indices if j != i)
            exclusions = _pad_rows(rows, width, system.n_atoms)
        # else: rely on nn-nn pair masking in the force field (always on).

    return dataclasses.replace(
        system,
        nn_mask=jnp.asarray(nn_mask),
        topology=dataclasses.replace(
            top,
            bond_mask=jnp.asarray(bond_mask.astype(np.float32)),
            angle_mask=jnp.asarray(angle_mask.astype(np.float32)),
            dihedral_mask=jnp.asarray(dih_mask.astype(np.float32)),
            exclusions=jnp.asarray(exclusions),
        ),
    )


# ---------------------------------------------------------------------------
# Builders: water box and model "protein" chains (1YRF / 1HCI stand-ins).
# ---------------------------------------------------------------------------

def build_water_box(n_side: int, spacing: float = 0.31) -> System:
    """Cubic lattice of single-site "water" (OPC-like LJ + charge-neutral).

    One site per molecule keeps the classical baseline simple while still
    exercising LJ + Coulomb + neighbor lists; multi-site water adds nothing
    for the paper's benchmarks (the DP group is the protein).
    """
    n = n_side ** 3
    box = np.array([n_side * spacing] * 3, dtype=np.float32)
    grid = np.stack(np.meshgrid(*[np.arange(n_side)] * 3, indexing="ij"), -1)
    pos = (grid.reshape(-1, 3) + 0.5) * spacing
    types = np.zeros(n, dtype=np.int32)
    masses = np.full(n, 18.015, dtype=np.float32)
    charges = np.zeros(n, dtype=np.float32)
    topo = empty_topology(n)
    sys_ = System(
        box=jnp.asarray(box), types=jnp.asarray(types),
        masses=jnp.asarray(masses), charges=jnp.asarray(charges),
        lj_sigma=jnp.asarray(np.array([0.3166], np.float32)),
        lj_epsilon=jnp.asarray(np.array([0.6502], np.float32)),
        topology=topo, nn_mask=jnp.zeros(n, jnp.float32),
    )
    return sys_, jnp.asarray(pos, jnp.float32)


def empty_topology(n_atoms: int, width: int = 16) -> Topology:
    z2 = lambda *s: jnp.zeros(s, jnp.float32)
    return Topology(
        bonds=jnp.zeros((1, 2), jnp.int32), bond_params=z2(1, 2), bond_mask=z2(1),
        angles=jnp.zeros((1, 3), jnp.int32), angle_params=z2(1, 2), angle_mask=z2(1),
        dihedrals=jnp.zeros((1, 4), jnp.int32), dihedral_params=z2(1, 3),
        dihedral_mask=z2(1),
        exclusions=jnp.full((n_atoms, width), -1, jnp.int32),
    )


def build_protein_chain(n_residues: int, seed: int = 0,
                        atoms_per_residue: int = 4) -> dict:
    """Self-avoiding helical backbone chain used as the protein stand-in.

    Returns numpy arrays (positions, types, masses, charges, bonds, angles)
    for splicing into a solvated system.  ~4 atoms/residue; 1YRF (582 atoms)
    ~ 146 residues, 1HCI (15,668 atoms) ~ 3,917 residues.
    """
    rng = np.random.default_rng(seed)
    n = n_residues * atoms_per_residue
    # helix backbone with small random perturbation
    t = np.arange(n) * 0.6
    radius = 0.25
    pos = np.stack([
        radius * np.cos(t),
        radius * np.sin(t),
        0.05 * np.arange(n),
    ], -1) + rng.normal(0, 0.01, (n, 3))
    pos = pos.astype(np.float32)
    types = (np.arange(n) % 3 + 1).astype(np.int32)  # species 1..3 (0 = water)
    masses = np.array([12.011, 14.007, 15.999])[types - 1].astype(np.float32)
    charges = (rng.uniform(-0.3, 0.3, n)).astype(np.float32)
    charges -= charges.mean()  # neutral group
    bonds = np.stack([np.arange(n - 1), np.arange(1, n)], -1).astype(np.int32)
    angles = np.stack([np.arange(n - 2), np.arange(1, n - 1),
                       np.arange(2, n)], -1).astype(np.int32)
    return dict(positions=pos, types=types, masses=masses, charges=charges,
                bonds=bonds, angles=angles)


def build_solvated_protein(n_residues: int, water_per_protein_atom: float = 3.0,
                           seed: int = 0, spacing: float = 0.31):
    """Protein chain + surrounding water lattice, the paper's test scenario.

    Returns (System, positions, nn_indices).  The protein occupies species
    1..3; water is species 0.  NN group (DP group) = the protein, as in the
    paper (Tab. II, "DP Group: Protein").
    """
    prot = build_protein_chain(n_residues, seed)
    n_prot = len(prot["positions"])
    n_wat_target = int(n_prot * water_per_protein_atom)
    n_side = max(4, int(round(n_wat_target ** (1 / 3))))

    # Size the box around the protein extent + padding.
    extent = prot["positions"].max(0) - prot["positions"].min(0)
    box = np.maximum(extent + 2.0, n_side * spacing).astype(np.float32)

    rng = np.random.default_rng(seed + 1)
    grid = np.stack(np.meshgrid(*[np.arange(n_side)] * 3, indexing="ij"), -1)
    wpos = (grid.reshape(-1, 3) + 0.5) * (box / n_side)
    # carve out waters within 0.3 nm of any protein atom (about one LJ
    # sigma: a water left closer sits deep in the r^-12 wall), in chunks so
    # the (waters x protein atoms) distance block stays small
    center = box / 2
    ppos = prot["positions"] - prot["positions"].mean(0) + center
    keep = np.ones(len(wpos), bool)
    for i in range(0, len(wpos), 1024):
        d2 = ((wpos[i:i + 1024, None, :] - ppos[None, :, :]) ** 2).sum(-1)
        keep[i:i + 1024] = d2.min(1) > 0.3 ** 2
    wpos = wpos[keep]
    n_wat = len(wpos)

    positions = np.concatenate([ppos, wpos]).astype(np.float32)
    n = len(positions)
    types = np.concatenate([prot["types"], np.zeros(n_wat, np.int32)])
    masses = np.concatenate([prot["masses"], np.full(n_wat, 18.015, np.float32)])
    charges = np.concatenate([prot["charges"], np.zeros(n_wat, np.float32)])
    bonds, angles = prot["bonds"], prot["angles"]
    excl = build_exclusions(n, bonds, angles)

    topo = Topology(
        bonds=jnp.asarray(bonds),
        bond_params=jnp.asarray(np.tile([0.15, 25000.0], (len(bonds), 1)).astype(np.float32)),
        bond_mask=jnp.ones(len(bonds), jnp.float32),
        angles=jnp.asarray(angles),
        angle_params=jnp.asarray(np.tile([1.91, 300.0], (len(angles), 1)).astype(np.float32)),
        angle_mask=jnp.ones(len(angles), jnp.float32),
        dihedrals=jnp.zeros((1, 4), jnp.int32),
        dihedral_params=jnp.zeros((1, 3), jnp.float32),
        dihedral_mask=jnp.zeros(1, jnp.float32),
        exclusions=jnp.asarray(excl),
    )
    system = System(
        box=jnp.asarray(box),
        types=jnp.asarray(types), masses=jnp.asarray(masses),
        charges=jnp.asarray(charges),
        lj_sigma=jnp.asarray(np.array([0.3166, 0.34, 0.325, 0.296], np.float32)),
        lj_epsilon=jnp.asarray(np.array([0.6502, 0.36, 0.71, 0.88], np.float32)),
        topology=topo,
        nn_mask=jnp.zeros(n, jnp.float32),
    )
    nn_indices = np.arange(n_prot, dtype=np.int32)
    return system, jnp.asarray(positions), nn_indices
