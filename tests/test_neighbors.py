"""Neighbor-list correctness: cell list == brute force (hypothesis)."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.md.neighbors import (brute_force_neighbor_list,
                                build_neighbor_list, cell_capacity_scale,
                                cell_list_neighbor_list, minimum_image,
                                needs_rebuild)


def _neighbor_sets(nl):
    idx = np.asarray(nl.idx)
    mask = np.asarray(nl.mask) > 0
    return [frozenset(idx[i][mask[i]].tolist()) for i in range(len(idx))]


@settings(max_examples=20, deadline=None)
@given(n=st.integers(8, 80), seed=st.integers(0, 10_000),
       half=st.booleans(),
       box_l=st.floats(2.0, 5.0))
def test_cell_list_matches_brute_force(n, seed, half, box_l):
    rng = np.random.default_rng(seed)
    box = jnp.asarray([box_l, box_l, box_l], jnp.float32)
    pos = jnp.asarray(rng.uniform(0, box_l, (n, 3)), jnp.float32)
    cutoff = 0.9
    cap = n
    a = brute_force_neighbor_list(pos, box, cutoff, cap, half=half)
    b = build_neighbor_list(pos, box, cutoff, cap, half=half)
    assert not bool(a.overflow) and not bool(b.overflow)
    assert _neighbor_sets(a) == _neighbor_sets(b)


def test_minimum_image_bounds():
    box = jnp.asarray([2.0, 3.0, 4.0])
    rng = np.random.default_rng(1)
    dr = jnp.asarray(rng.uniform(-10, 10, (100, 3)), jnp.float32)
    mi = minimum_image(dr, box)
    assert bool((jnp.abs(mi) <= jnp.asarray(box) / 2 + 1e-5).all())


def test_full_list_is_symmetric():
    rng = np.random.default_rng(2)
    pos = jnp.asarray(rng.uniform(0, 3, (40, 3)), jnp.float32)
    box = jnp.asarray([3.0, 3.0, 3.0])
    nl = brute_force_neighbor_list(pos, box, 1.0, 40, half=False)
    sets = _neighbor_sets(nl)
    for i, s in enumerate(sets):
        for j in s:
            assert i in sets[j], f"{i} in N({j}) missing"


def test_overflow_flag():
    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.uniform(0, 1.0, (64, 3)), jnp.float32)
    box = jnp.asarray([1.0, 1.0, 1.0])
    nl = brute_force_neighbor_list(pos, box, 0.9, 4, half=False)
    assert bool(nl.overflow)


def test_needs_rebuild_on_displacement():
    rng = np.random.default_rng(4)
    pos = jnp.asarray(rng.uniform(0, 3, (32, 3)), jnp.float32)
    box = jnp.asarray([3.0, 3.0, 3.0])
    nl = build_neighbor_list(pos, box, 0.8, 64, skin=0.2)
    assert not bool(needs_rebuild(nl, pos, box, 0.2))
    moved = pos.at[0].add(jnp.asarray([0.15, 0.0, 0.0]))
    assert bool(needs_rebuild(nl, moved, box, 0.2))


def test_cell_capacity_scale_fits_a_clustered_frame():
    # 200 atoms spread over the box and 100 packed into one cell: the
    # density-derived cell capacity overflows, the sized one does not, and
    # in a batch of frames the busiest frame decides
    rng = np.random.default_rng(5)
    box = np.full(3, 4.0, np.float32)
    spread = rng.uniform(0, 4, (300, 3)).astype(np.float32)
    pos = np.concatenate([spread[:200],
                          2.0 + rng.uniform(-0.3, 0.3, (100, 3))])
    pos = pos.astype(np.float32)
    scale = cell_capacity_scale(pos, box, 1.2, skin=0.1)
    assert scale > 1
    assert cell_capacity_scale(spread, box, 1.2, skin=0.1) == 1.0
    assert cell_capacity_scale(np.stack([spread, pos]), box, 1.2,
                               skin=0.1) == scale
    default = build_neighbor_list(pos, box, 1.2, 256, half=True, skin=0.1)
    sized = build_neighbor_list(pos, box, 1.2, 256, half=True, skin=0.1,
                                cell_cap_scale=scale)
    assert bool(default.overflow) and not bool(sized.overflow)
