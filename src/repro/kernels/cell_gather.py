"""Pallas TPU kernel: fused cell-candidate distance filter.

The hot inner loop of cell-list subdomain assembly (core/ddinfer.py): after
gathering the 27-cell candidate set per atom, decide which candidates fall
inside the cutoff sphere.  The jnp path materializes the (C, M, 3)
displacement tensor plus three (C, M) intermediates in HBM; this kernel
fuses the norm + cutoff + validity test into one VMEM-tiled pass so HBM
traffic is exactly inputs + the (C, M) flag plane.

Layout mirrors env_mat.py (the repo's TPU convention): SoA displacement
planes (C, M) with the candidate axis on lanes (pad M to 128) and the atom
axis on sublanes (blocks of 8) — native (8, 128) VREG tiling.  The gather
itself stays in XLA: dynamic-index gathers from HBM inside a Mosaic kernel
would serialize on scalar loads, while XLA's gather is already
bandwidth-bound and fuses with the surrounding reshape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cell_filter_kernel(dx_ref, dy_ref, dz_ref, valid_ref, out_ref,
                        *, rcut: float):
    dx = dx_ref[...]
    dy = dy_ref[...]
    dz = dz_ref[...]
    valid = valid_ref[...]
    d2 = dx * dx + dy * dy + dz * dz
    within = (d2 < rcut * rcut) & (valid > 0)
    out_ref[...] = within.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rcut", "block_n", "interpret"))
def cell_filter(dx: jax.Array, dy: jax.Array, dz: jax.Array,
                valid: jax.Array, rcut: float, block_n: int = 8,
                interpret: bool = False) -> jax.Array:
    """Fused within-cutoff flags for gathered cell candidates.

    Args: dx/dy/dz (C, M) displacement planes atom->candidate and a (C, M)
    validity plane (0 = padded / self / masked candidate).  M should be a
    multiple of 128 on real TPUs (the ops.py wrapper pads); C is padded to
    ``block_n`` here.  Returns a (C, M) {0,1} plane of the same dtype.
    """
    n, m = dx.shape
    pad_n = (-n) % block_n
    if pad_n:
        padder = lambda a: jnp.pad(a, ((0, pad_n), (0, 0)))
        dx, dy, dz, valid = map(padder, (dx, dy, dz, valid))
    np_, mp = dx.shape

    grid = (np_ // block_n,)
    spec = pl.BlockSpec((block_n, mp), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_cell_filter_kernel, rcut=rcut),
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((np_, mp), dx.dtype),
        interpret=interpret,
        name="cell_filter",
    )(dx, dy, dz, valid)
    return out[:n] if pad_n else out
