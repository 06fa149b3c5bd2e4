"""Cell-list infrastructure: binning correctness, cell-vs-dense selection
parity (random / clustered / degenerate boxes), overflow-flag behavior —
single device."""
import dataclasses

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.ddinfer import suggest_config, _subdomain_nbr_list, \
    _subdomain_nbr_list_cells
from repro.core.domain import (balanced_planes, bin_atoms, select_ghosts,
                               select_ghosts_cells, select_local,
                               select_local_cells, uniform_grid)
from repro.md import cells


# ------------------------------------------------------------- binning core

def test_build_cell_table_places_each_atom_once():
    rng = np.random.default_rng(0)
    n, dims = 120, (3, 4, 2)
    ids = jnp.asarray(rng.integers(0, np.prod(dims), n), jnp.int32)
    tab = cells.build_cell_table(ids, dims, capacity=n)
    assert not bool(tab.overflow)
    table = np.asarray(tab.table)
    # spill row empty; every atom appears exactly once, in its own cell
    assert (table[-1] == -1).all()
    seen = {}
    for c in range(int(np.prod(dims))):
        for a in table[c][table[c] >= 0]:
            seen[int(a)] = c
    assert len(seen) == n
    ids_np = np.asarray(ids)
    assert all(ids_np[a] == c for a, c in seen.items())


def test_build_cell_table_overflow_flag():
    ids = jnp.zeros(10, jnp.int32)               # all atoms in cell 0
    tab = cells.build_cell_table(ids, (2, 2, 2), capacity=4)
    assert bool(tab.overflow)
    # spill-row crowding must NOT flag: invalid atoms go to the last row
    ids = jnp.full(10, 8, jnp.int32)             # all atoms invalid (spill)
    tab = cells.build_cell_table(ids, (2, 2, 2), capacity=4)
    assert not bool(tab.overflow)
    assert (np.asarray(tab.table) == -1).all()


def test_neighborhood_candidates_open_boundary_excludes_far_cells():
    # two atoms 2 cells apart on an open-boundary grid must not see each other
    dims = (4, 1, 1)
    ids = jnp.asarray([0, 3], jnp.int32)
    tab = cells.build_cell_table(ids, dims, capacity=2)
    frac = jnp.asarray([[0, 0, 0], [3, 0, 0]], jnp.int32)
    cand = np.asarray(cells.neighborhood_candidates(tab, frac, periodic=False))
    assert 1 not in cand[0]
    assert 0 not in cand[1]
    # with periodic wrap the grid closes and they do see each other
    cand_p = np.asarray(cells.neighborhood_candidates(tab, frac, periodic=True))
    assert 1 in cand_p[0]
    assert 0 in cand_p[1]


# ------------------------------------------- selection parity (cells==dense)

def _make_system(n, boxl, clustered, seed):
    rng = np.random.default_rng(seed)
    if clustered:
        half = n // 2
        coords = np.concatenate([rng.uniform(0, boxl * 0.3, (half, 3)),
                                 rng.uniform(0, boxl, (n - half, 3))])
    else:
        coords = rng.uniform(0, boxl, (n, 3))
    return jnp.asarray(coords, jnp.float32), np.array([boxl] * 3, np.float32)


def _assert_selection_parity(coords, box, cfg, grid):
    table = bin_atoms(coords, box, cfg.cell_dims, cfg.cell_capacity)
    assert not bool(table.overflow)
    for r in range(cfg.n_ranks):
        r = jnp.asarray(r)
        li, lm, lc = select_local(coords, grid, r, cfg.local_capacity)
        li2, lm2, lc2, lovf = select_local_cells(
            coords, grid, r, cfg.local_capacity, table, cfg.local_region, box)
        assert not bool(lovf)
        assert int(lc) == int(lc2)
        np.testing.assert_array_equal(np.asarray(li), np.asarray(li2))
        np.testing.assert_array_equal(np.asarray(lm), np.asarray(lm2))
        gi, gs, gm, gc = select_ghosts(coords, box, grid, r, cfg.halo,
                                       cfg.ghost_capacity)
        gi2, gs2, gm2, gc2, govf = select_ghosts_cells(
            coords, box, grid, r, cfg.halo, cfg.ghost_capacity, table,
            cfg.ghost_region)
        assert not bool(govf)
        assert int(gc) == int(gc2)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(gi2))
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(gm2))
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gs2))


@settings(max_examples=12, deadline=None)
@given(n=st.integers(24, 180), seed=st.integers(0, 1000),
       p=st.sampled_from([2, 4, 8]), clustered=st.booleans(),
       force_mode=st.sampled_from(["owner_full", "ghost_reduce"]))
def test_cell_selection_matches_dense(n, seed, p, clustered, force_mode):
    coords, box = _make_system(n, 4.0, clustered, seed)
    cfg = suggest_config(n, box, p, 0.6, slack=2.5, force_mode=force_mode,
                         coords=coords)
    grid = uniform_grid(box, cfg.grid_dims)
    _assert_selection_parity(coords, box, cfg, grid)


def test_cell_selection_matches_dense_balanced():
    """Quantile (load-balanced) planes move with the coordinates; the static
    region extents must still cover the widest slab."""
    coords, box = _make_system(300, 4.0, True, 7)
    cfg = suggest_config(300, box, 8, 0.6, slack=2.5, balanced=True,
                         coords=coords)
    grid = balanced_planes(coords, box, cfg.grid_dims)
    _assert_selection_parity(coords, box, cfg, grid)


def test_cell_selection_matches_dense_degenerate_box():
    """Box < 3 cells per axis: wrap aliasing / whole-axis subdomains."""
    for boxl, p in [(1.8, 2), (2.0, 4)]:
        coords, box = _make_system(48, boxl, False, 11)
        cfg = suggest_config(48, box, p, 0.6, slack=2.5,
                             force_mode="ghost_reduce", coords=coords)
        grid = uniform_grid(box, cfg.grid_dims)
        _assert_selection_parity(coords, box, cfg, grid)


def test_selection_overflow_flags_on_undersized_cells():
    coords, box = _make_system(160, 3.5, False, 3)
    cfg = suggest_config(160, box, 8, 0.6, slack=2.5, coords=coords)
    small = dataclasses.replace(cfg, cell_capacity=1)
    table = bin_atoms(coords, box, small.cell_dims, small.cell_capacity)
    assert bool(table.overflow)
    _, _, _, lovf = select_local_cells(coords, uniform_grid(box, cfg.grid_dims),
                                       jnp.asarray(0), cfg.local_capacity,
                                       table, cfg.local_region, box)
    assert bool(lovf)
    # undersized *region* must flag too (region (1,1,1) cannot cover the halo)
    full = bin_atoms(coords, box, cfg.cell_dims, cfg.cell_capacity)
    _, _, _, _, govf = select_ghosts_cells(
        coords, box, uniform_grid(box, cfg.grid_dims), jnp.asarray(0),
        cfg.halo, cfg.ghost_capacity, full, (1, 1, 1))
    assert bool(govf)


# -------------------------------------------- subdomain neighbor assembly

def test_subdomain_nbr_list_cells_matches_dense():
    rng = np.random.default_rng(5)
    for n, extent, rcut in [(64, 2.2, 0.6), (128, 3.0, 0.5), (16, 1.0, 0.4)]:
        origin = jnp.asarray([-0.6, -0.6, -0.6], jnp.float32)
        buf = jnp.asarray(rng.uniform(-0.5, extent - 0.6, (n, 3)), jnp.float32)
        mask = jnp.asarray(rng.random(n) > 0.2, jnp.float32)
        park = 100.0 * (1.0 + jnp.arange(n, dtype=jnp.float32))[:, None]
        buf = jnp.where(mask[:, None] > 0, buf, park)
        dims = tuple(int(np.ceil((extent + 0.2) / rcut)) + 1 for _ in range(3))
        k = 48
        i1, m1, o1 = _subdomain_nbr_list(buf, mask, rcut, k)
        i2, m2, o2 = _subdomain_nbr_list_cells(buf, mask, rcut, k, origin,
                                               dims, cell_capacity=n)
        assert bool(o1) == bool(o2) == False  # noqa: E712
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_subdomain_nbr_list_cells_overflow_flags():
    rng = np.random.default_rng(6)
    n = 64
    buf = jnp.asarray(rng.uniform(0, 1.5, (n, 3)), jnp.float32)
    mask = jnp.ones(n, jnp.float32)
    origin = jnp.zeros(3, jnp.float32)
    dims = (4, 4, 4)
    # undersized cell capacity
    _, _, ovf = _subdomain_nbr_list_cells(buf, mask, 0.5, 64, origin, dims,
                                          cell_capacity=1)
    assert bool(ovf)
    # undersized grid extent: valid atoms fall outside -> range overflow
    _, _, ovf = _subdomain_nbr_list_cells(buf, mask, 0.5, 64, origin, (1, 1, 1),
                                          cell_capacity=n)
    assert bool(ovf)


def test_subcell_capacity_fits_one_chip_at_liquid_density():
    """One rank's candidate planes for 4,096 atoms at 100 atoms/nm^3 and the
    paper's r_c = 0.8 nm stay under a quarter of a v5e's 16 GB of HBM.

    The planes are what ``_subdomain_nbr_list_cells`` hands the cell
    filter: candidate index, dx, dy, dz and validity, each (C, 27 * cap)
    with the candidate axis padded to 128 lanes.  Sizing ``subcell_capacity``
    from a 2x2x2 block bound instead of the grid the runtime bins gave
    cap = 704 and ~14.6 GB here."""
    n, density, rcut = 4096, 100.0, 0.8
    edge = (n / density) ** (1 / 3)
    coords = np.random.default_rng(0).uniform(0.0, edge, (n, 3))
    cfg = suggest_config(n, np.full(3, edge), 1, rcut, nbr_capacity=64,
                         coords=coords)
    mean_occupancy = density * rcut ** 3
    assert cfg.subcell_capacity <= 2.5 * mean_occupancy
    c = cfg.local_capacity + cfg.ghost_capacity
    lanes = -(-27 * cfg.subcell_capacity // 128) * 128
    footprint = 5 * c * lanes * 4
    assert footprint < 4e9, footprint
