"""The hybrid case as the program can hold it on one chip: a helical chain (the
DP group) in a lattice of single-site classical waters.

A copy of the repository's ``md.system.build_solvated_protein`` geometry, so
that the benchmark makes its own inputs: 4 atoms per residue on a helix
(species 1..3, 0.05 nm rise per atom), waters on a cubic lattice in a box
2 nm wider than the chain, waters within 0.3 nm of any protein atom carved
out.  The system is far below liquid density (about 1.5 atoms/nm^3); at
protein and water density the program's classical cell list does not fit
one chip (see PERF.md).  Every bonded term lies inside the DP group, so the
configuration's classical interactions are LJ and reaction-field Coulomb
between pairs that are not both DP atoms.
"""
from __future__ import annotations

import numpy as np

LJ_SIGMA = np.array([0.3166, 0.34, 0.325, 0.296], np.float32)
LJ_EPSILON = np.array([0.6502, 0.36, 0.71, 0.88], np.float32)
WATER_MASS = 18.015


def _chain(n_residues: int, seed: int, atoms_per_residue: int = 4) -> dict:
    rng = np.random.default_rng(seed)
    n = n_residues * atoms_per_residue
    t = np.arange(n) * 0.6
    radius = 0.25
    pos = np.stack([radius * np.cos(t), radius * np.sin(t),
                    0.05 * np.arange(n)], -1) + rng.normal(0, 0.01, (n, 3))
    types = (np.arange(n) % 3 + 1).astype(np.int32)
    masses = np.array([12.011, 14.007, 15.999])[types - 1].astype(np.float32)
    charges = rng.uniform(-0.3, 0.3, n).astype(np.float32)
    charges -= charges.mean()
    bonds = np.stack([np.arange(n - 1), np.arange(1, n)], -1).astype(np.int32)
    angles = np.stack([np.arange(n - 2), np.arange(1, n - 1),
                       np.arange(2, n)], -1).astype(np.int32)
    return dict(positions=pos.astype(np.float32), types=types, masses=masses,
                charges=charges, bonds=bonds, angles=angles)


def build(n_residues: int, water_per_protein_atom: float = 3.0,
          spacing: float = 0.31, structure_seed: int = 0) -> dict:
    """Numpy arrays of the system; ``nn_idx`` is the DP group (the chain)."""
    prot = _chain(n_residues, structure_seed)
    n_prot = len(prot["positions"])
    n_side = max(4, int(round((n_prot * water_per_protein_atom) ** (1 / 3))))
    extent = prot["positions"].max(0) - prot["positions"].min(0)
    box = np.maximum(extent + 2.0, n_side * spacing).astype(np.float32)
    grid = np.stack(np.meshgrid(*[np.arange(n_side)] * 3, indexing="ij"), -1)
    wpos = (grid.reshape(-1, 3) + 0.5) * (box / n_side)
    ppos = prot["positions"] - prot["positions"].mean(0) + box / 2
    keep = np.ones(len(wpos), bool)
    for i in range(0, len(wpos), 1024):
        d2 = ((wpos[i:i + 1024, None, :] - ppos[None, :, :]) ** 2).sum(-1)
        keep[i:i + 1024] = d2.min(1) > 0.3 ** 2
    wpos = wpos[keep]
    n_wat = len(wpos)
    return dict(
        positions=np.concatenate([ppos, wpos]).astype(np.float32),
        types=np.concatenate([prot["types"], np.zeros(n_wat, np.int32)]),
        masses=np.concatenate([prot["masses"],
                               np.full(n_wat, WATER_MASS, np.float32)]),
        charges=np.concatenate([prot["charges"],
                                np.zeros(n_wat, np.float32)]),
        lj_sigma=LJ_SIGMA, lj_epsilon=LJ_EPSILON, box=box,
        bonds=prot["bonds"], angles=prot["angles"],
        nn_idx=np.arange(n_prot, dtype=np.int32))
