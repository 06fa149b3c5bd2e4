"""Composable distributed force pipeline: one stage implementation, many drivers.

The distributed force path is five typed stages

    gather  ->  partition  ->  assemble  ->  evaluate  ->  reduce

each a per-rank body that runs inside ONE ``shard_map`` region:

* **gather** — collective 1: all-gather the sharded coordinates so every
  rank holds the replicated buffer (paper Fig. 6).
* **partition** — overlap-only collective: route each rank's *own* subdomain
  coordinates to it directly (a ``psum_scatter`` over a replicated routing
  table), so local work can start before the all-gather lands.
* **assemble** — virtual DD: local/ghost selection, image shifts, the
  skin-widened subdomain neighbor list (:func:`ddinfer._assemble_rank`).
* **evaluate** — buffer rebuild at fresh positions, exact-cutoff re-filter,
  DP inference with autodiff forces.
* **reduce** — collective 2: energy psum + force all-reduce/reduce-scatter,
  plus the diagnostics dictionary.

Every public driver is a thin composition over these bodies:
``build_force_fn`` (fused per-step), and ``build_assembly_fn`` +
``build_evaluation_fn`` + ``build_check_fn`` (amortized split).
Replica batching is a *transform*, not a second copy of each driver: the
:class:`_AxisOps` adapter moves every collective to the batched atom axis
and vmaps the per-replica stage bodies on the (replica x dd) mesh.

Comms/compute overlap (``DDConfig.overlap``)
--------------------------------------------
The amortized evaluation is split at the assemble/evaluate seam into an
**interior pass** that needs no halo exchange and a **boundary pass** that
does, so the interior DP work can be scheduled concurrently with the
coordinate all-gather (the async-collective pattern of the 100M-atom DPMD
runs, Lu et al. 2004.11658).  Row classification comes from the assembled
``DDState`` alone, so it is known *before* the gather:

    gfree(i)    local row whose build-list neighbors are all local rows
    interior(i) gfree and every neighbor gfree   (its force is ghost-free)
    deep(i)     interior and every neighbor interior (skippable downstream)

* Pass A (pre-gather): the partition collective delivers this rank's exact
  local coordinates; the model runs over the *local-only* buffer with
  ghost-pointing list slots masked.  Per-row outputs are bitwise equal to
  the sequential program for every ``gfree`` row, and accumulated forces
  are bitwise equal for every ``interior`` row (all force contributions to
  an interior row come from gfree rows; the build list is symmetric
  whenever it did not overflow, and the order-preserving row subset keeps
  the scatter-add order of the sequential backward).
* Pass B (post-gather): the full buffer is rebuilt and re-filtered exactly
  as the sequential path, then the non-``deep`` rows are compacted
  (order-preserving, index-remapped) into a static ``overlap_capacity``
  sub-buffer and evaluated there.  Every non-interior local row, and every
  row contributing force to one, is non-deep, so pass B reproduces the
  sequential per-row energies/forces for exactly the rows pass A cannot.
* Merge: per-row ``where`` selects (never adds) — pass A for forces on
  interior rows and energies on gfree rows, pass B elsewhere; the reported
  energy is reduced with the identical fusion-stable ``dot`` the
  sequential path uses (see ``_model_scatter``).
  With the default full-size sub-buffer the merged forces AND energy are
  bitwise equal to the sequential evaluation — the parity oracle in
  ``tests/test_pipeline.py``.

Two deliberate caveats to the bitwise claim.  (1) Bitwise parity requires
OPERAND-IDENTICAL passes, not just value-identical ones: XLA fuses the
model forward with whatever surrounds it, and a compacted gather/scatter
wrapper around the same math rounds differently at the last ulp for some
inputs.  With the default ``overlap_capacity = 0`` pass B therefore skips
the compaction entirely and evaluates the untouched buffer with every
valid center — the exact arrays and expression chain of the sequential
evaluate stage — and the merged energy is taken wholly from it, while
pass A (shape-preserving, full (C, K) with ghost rows parked) supplies
the interior forces that let XLA start the model before the gather
lands.  (2) A tuned smaller ``overlap_capacity`` trims pass B to the
subdomain boundary shell — saving the compute that motivates the knob —
at the cost of ulp-level (no longer bitwise) energy/force agreement, with
overflow flagged through the normal ``diag["overflow"]`` grow-and-retry
protocol.  When the measured
``diag["interior_frac"]`` sits below ``overlap_min_interior`` there is not
enough interior work to hide the gather — callers should build the
sequential evaluation instead (the knob is advisory; programs are chosen
at build time, not per step).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import compat
from ..dp.model import DPModel
from ..md.neighbors import max_displacement2
from .ddinfer import (DDConfig, DDState, _assemble_rank, _make_grid,
                      _pad_atoms, _pad_atoms_batched, _pad_types, _park)


# ---------------------------------------------------------------------------
# batching transform: one set of stage bodies, two mesh layouts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _AxisOps:
    """Collective/spec adapter that turns the unbatched per-rank bodies into
    replica-batched ones: the atom axis moves from 0 to 1, every collective
    follows it, and per-replica bodies are vmapped.  This is the *transform*
    that replaces the former hand-copied ``make_batched_*`` factories."""

    axis: str                           # dd mesh axis name
    replica_axis: Optional[str] = None  # None = unbatched

    @property
    def batched(self) -> bool:
        return self.replica_axis is not None

    @property
    def adim(self) -> int:
        """Position of the atom axis in sharded arrays."""
        return 1 if self.batched else 0

    # -- collectives --------------------------------------------------------
    def all_gather(self, x):
        return jax.lax.all_gather(x, self.axis, axis=self.adim, tiled=True)

    def gather_ranks(self, x):
        """Per-rank scalar(s) -> a trailing rank axis ((P,) / (r, P))."""
        return jax.lax.all_gather(x, self.axis, axis=self.adim)

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis)

    def psum_scatter(self, x):
        return jax.lax.psum_scatter(x, self.axis,
                                    scatter_dimension=self.adim, tiled=True)

    def slice_atoms(self, x, start, size):
        return jax.lax.dynamic_slice_in_dim(x, start, size, axis=self.adim)

    def vmap(self, f):
        """Per-replica body -> resident-replica batch (identity unbatched)."""
        return jax.vmap(f) if self.batched else f

    # -- partition specs ----------------------------------------------------
    def spec(self, *rest) -> P:
        """Leaf sharded along the dd axis (leading replica axis if batched)."""
        if self.batched:
            return P(self.replica_axis, self.axis, *rest)
        return P(self.axis, *rest)

    def rspec(self, *rest) -> P:
        """Per-replica leaf, replicated over the dd axis."""
        if self.batched:
            return P(self.replica_axis, *rest)
        return P(*rest)


def _replica_layout(mesh: Mesh, cfg: DDConfig, n_replicas: int,
                    replica_axis: str) -> int:
    """Validate the 2-D mesh and return replicas-per-device-group."""
    if replica_axis not in mesh.shape or cfg.axis not in mesh.shape:
        raise ValueError(
            f"mesh axes {tuple(mesh.shape)} must include "
            f"{replica_axis!r} and {cfg.axis!r}")
    if mesh.shape[cfg.axis] != cfg.n_ranks:
        raise ValueError(f"mesh {cfg.axis} size {mesh.shape[cfg.axis]} != "
                         f"grid {cfg.n_ranks}")
    rd = mesh.shape[replica_axis]
    if n_replicas % rd:
        raise ValueError(f"n_replicas {n_replicas} not divisible by the "
                         f"{replica_axis!r} mesh axis ({rd})")
    return n_replicas // rd


def _state_specs(ax: _AxisOps) -> DDState:
    """Partition specs for every DDState leaf under the given layout."""
    return DDState(
        l_idx=ax.spec(), l_mask=ax.spec(), l_slot=ax.rspec(None),
        g_idx=ax.spec(), g_shift=ax.spec(None), g_mask=ax.spec(),
        buf_types=ax.spec(), buf_mask=ax.spec(),
        nbr_idx=ax.spec(None), nbr_mask=ax.spec(None),
        local_count=ax.rspec(), ghost_count=ax.rspec(), cost_max=ax.rspec(),
        overflow=ax.rspec(), ref=ax.rspec(None, None))


def _st_dict(st: DDState) -> dict:
    return {f.name: getattr(st, f.name)
            for f in dataclasses.fields(DDState) if f.name != "ref"}


# ---------------------------------------------------------------------------
# evaluate stage: buffer rebuild + exact-cutoff re-filter + DP inference
# (per-rank, per-replica — the ONE implementation every driver composes)
# ---------------------------------------------------------------------------

def _rebuild_buffer(coords_all, ref_all, st: dict, box, cfg: DDConfig):
    """Subdomain buffer at fresh positions: ``current + (shift - img) * box``
    where ``img`` is the integer box crossing since the reference — an exact
    unwrap, so with ``ref_all is coords_all`` this reproduces the
    assembly-time buffer bitwise."""
    dtype = coords_all.dtype
    l_idx, g_idx = st["l_idx"], st["g_idx"]
    img_l = jnp.round((coords_all[l_idx] - ref_all[l_idx]) / box)
    img_g = jnp.round((coords_all[g_idx] - ref_all[g_idx]) / box)
    buf_l = coords_all[l_idx] - img_l.astype(dtype) * box
    buf_g = coords_all[g_idx] + (st["g_shift"].astype(dtype) - img_g) * box
    return _park(jnp.concatenate([buf_l, buf_g]), st["buf_mask"], box)


def _refilter_compact(buf_coords, nbr_idx, nbr_mask, cfg: DDConfig,
                      rcut: float):
    """Re-filter the (skin-widened, possibly stale) list to the exact cutoff
    and compact canonically: surviving entries sorted by buffer index,
    zeroed tail, trimmed to ``k_eval`` — the model input then depends only
    on the *within-cutoff* pair set, so a stale list gives bitwise-identical
    forces to a fresh one, and the model tensors stay at the unskinned K."""
    d2 = sum((x[nbr_idx] - x[:, None]) ** 2 for x in buf_coords.T)
    mask = nbr_mask * (d2 < rcut ** 2)
    k_eval = min(cfg.k_eval, nbr_idx.shape[1])
    trim_overflow = ((mask > 0).sum(1) > k_eval).any()
    score = jnp.where(mask > 0, -nbr_idx.astype(jnp.float32), -jnp.inf)
    _, order = jax.lax.top_k(score, k_eval)
    mask = jnp.take_along_axis(mask, order, axis=1)
    idx = jnp.where(mask > 0, jnp.take_along_axis(nbr_idx, order, axis=1), 0)
    return idx, mask, trim_overflow


def _model_scatter(model: DPModel, params, buf_coords, st: dict, nbr_idx,
                   nbr_mask, cfg: DDConfig, n: int):
    """DP inference over the buffer + scatter into the global force array."""
    dtype = buf_coords.dtype
    l_idx, l_mask = st["l_idx"], st["l_mask"]
    local_mask = jnp.concatenate([
        l_mask.astype(dtype), jnp.zeros(cfg.ghost_capacity, dtype)])
    f_global = jnp.zeros((n, 3), dtype)
    if cfg.force_mode == "owner_full":
        # Paper Sec. IV-A: the 2*r_c halo makes every first-layer ghost's
        # descriptor exact, so differentiating the *full* buffer energy gives
        # complete forces on local atoms; ghost rows are discarded and the
        # final collective only assembles (each row has exactly one writer).
        # The reported energy is reduced OUTSIDE the value_and_grad, from
        # the raw per-row energies, as a (C,)-dot — the identical reduction
        # the overlap merge performs.  A fused (e * mask).sum() is NOT
        # reduction-order-stable across programs: XLA fuses it with
        # whatever produces e (the model forward here, the pass-A/B merge
        # there) and the resulting loop nests round differently at ulp
        # level.  A dot of the same shape lowers to the same kernel in both
        # programs, which is what keeps the sequential path the bitwise
        # oracle for the overlapped one.
        force_maskf = st["buf_mask"].astype(dtype)

        def fsum(c):
            e = model._atomic_e(params, c, st["buf_types"], nbr_idx,
                                nbr_mask)
            return (e * force_maskf).sum(), e

        (_, e_rows), g = jax.value_and_grad(fsum, has_aux=True)(buf_coords)
        e_local = jnp.dot(e_rows, local_mask)
        # force reduction stays in the coordinate dtype (fp32) regardless of
        # the model's compute policy — the mixed-precision contract
        f_buf = (-g).astype(dtype)
        f_global = f_global.at[l_idx].add(f_buf[: cfg.local_capacity]
                                          * l_mask[:, None])
    else:
        # Eq. 7 ghost-masking: energy over local atoms only; partial forces
        # land on ghosts and are summed onto the owners by collective 2.
        e_local, f_buf = model.energy_and_forces(
            params, buf_coords, st["buf_types"], nbr_idx, nbr_mask,
            local_mask, box=None)
        f_buf = f_buf.astype(dtype)
        f_global = f_global.at[l_idx].add(f_buf[: cfg.local_capacity]
                                          * l_mask[:, None])
        f_global = f_global.at[st["g_idx"]].add(f_buf[cfg.local_capacity:]
                                                * st["g_mask"][:, None])
    return e_local, f_global


def _evaluate_rank(model: DPModel, params, coords_all, ref_all, st: dict,
                   box, cfg: DDConfig, rcut: float):
    """Sequential evaluate stage for one rank: reuse the assembled state at
    fresh positions (rebuild -> re-filter -> inference -> scatter)."""
    n = coords_all.shape[0]
    dtype = coords_all.dtype
    box = jnp.asarray(box)
    buf_coords = _rebuild_buffer(coords_all, ref_all, st, box, cfg)
    nbr_idx, nbr_mask, trim_overflow = _refilter_compact(
        buf_coords, st["nbr_idx"], st["nbr_mask"], cfg, rcut)
    e_local, f_global = _model_scatter(model, params, buf_coords, st,
                                       nbr_idx, nbr_mask, cfg, n)
    # occupancy of the model-facing (post-compaction) list: fill over the
    # slots the valid buffer rows actually paid for — the observability
    # layer's capacity-tuning signal (free: both factors already exist)
    k_eval = min(cfg.k_eval, st["nbr_idx"].shape[1])
    stats = {"nbr_fill": (nbr_mask > 0).sum().astype(dtype),
             "nbr_slots": st["buf_mask"].sum() * k_eval}
    return e_local, f_global, trim_overflow, stats


# ---------------------------------------------------------------------------
# overlap evaluate: interior pass (pre-gather) + boundary pass (post-gather)
# ---------------------------------------------------------------------------

def _overlap_masks(cfg: DDConfig, st: dict):
    """Row classification from the assembled state alone (pre-gather).

    Propagated over the *build* (skin-widened) list, whose membership is
    symmetric whenever assembly did not overflow, so ``interior`` rows
    receive force contributions only from ``gfree`` rows and ``deep`` rows
    contribute only to ``interior`` rows."""
    c = st["buf_mask"].shape[0]
    cl = cfg.local_capacity
    rowvalid = st["buf_mask"] > 0
    local_row = jnp.arange(c) < cl
    m = st["nbr_mask"] > 0
    idx = st["nbr_idx"]

    def allnbr(flag):
        return jnp.where(m, flag[idx], True).all(axis=1)

    gfree = rowvalid & local_row & allnbr(local_row)
    interior = gfree & allnbr(gfree)
    deep = interior & allnbr(interior)
    deep2 = deep & allnbr(deep)
    return gfree, interior, deep, deep2


def _route_contrib(coords_shard, l_slot, rank, chunk):
    """Partition-stage send buffer: this rank's shard coordinates placed at
    every routing slot it owns, zeros elsewhere.  A tiled ``psum_scatter``
    over the dd axis then hands each rank exactly ``coords_all[l_idx]`` —
    one writer per slot — without waiting for the all-gather."""
    mine = (l_slot // chunk) == rank
    off = jnp.clip(l_slot - rank * chunk, 0, chunk - 1)
    vals = coords_shard[off]
    return jnp.where(mine[:, None], vals, jnp.zeros_like(vals))


def _evaluate_interior(model: DPModel, params, cur_l, ref_all, st: dict,
                       box, cfg: DDConfig, rcut: float, gfree):
    """Pass A: exact current local coordinates (delivered by the partition
    collective), ghost rows parked, ghost-pointing list slots masked — no
    dependence on the all-gather.  The buffer keeps the sequential (C, K)
    shapes: XLA's reduction blocking — and therefore its rounding — depends
    on the array shapes, so only a shape-preserving pass reproduces the
    sequential per-row energies bitwise for every gfree row and the
    accumulated forces bitwise for every interior row (ghost rows feed
    exactly-zero cotangents and masked list slots, so their parked values
    never reach a gfree row's output)."""
    cl = cfg.local_capacity
    dtype = cur_l.dtype
    l_idx = st["l_idx"]
    img_l = jnp.round((cur_l - ref_all[l_idx]) / box)
    buf_l = cur_l - img_l.astype(dtype) * box
    row_mask = jnp.concatenate([st["l_mask"].astype(dtype),
                                jnp.zeros(cfg.ghost_capacity, dtype)])
    buf = _park(jnp.concatenate(
        [buf_l, jnp.zeros((cfg.ghost_capacity, 3), dtype)]), row_mask, box)
    idx = st["nbr_idx"]
    mask = st["nbr_mask"] * (idx < cl)
    idx = jnp.where(mask > 0, idx, 0)
    idx, mask, _ = _refilter_compact(buf, idx, mask, cfg, rcut)
    gfreef = gfree.astype(dtype)

    def fsum(c):
        e = model._atomic_e(params, c, st["buf_types"], idx, mask)
        return (e * gfreef).sum(), e

    (_, e_rows), g = jax.value_and_grad(fsum, has_aux=True)(buf)
    return e_rows[:cl], (-g[:cl]).astype(dtype)


def _evaluate_boundary(model: DPModel, params, buf_coords, st: dict,
                       nbr_idx, nbr_mask, cfg: DDConfig, deep, deep2):
    """Pass B: compact the non-deep rows (order-preserving) plus their
    neighbor closure (the non-deep2 rows) into a static sub-buffer, remap
    the already-refiltered list into it, and evaluate only those centers.
    Returns full-shape per-row energies/forces scattered back (exact for
    every non-deep row) and the sub-buffer overflow flag.

    At the full sub-buffer size (the default ``overlap_capacity = 0``)
    the compaction is skipped entirely and the pass evaluates the
    untouched buffer with every valid row as a center — operand-for-
    operand the sequential evaluate stage, so XLA emits the same fused
    kernels in both programs and the result is bitwise the sequential
    one at any positions.  A trimmed sub-buffer changes the operand
    shapes the model reduces over, and XLA's shape-dependent reduction
    blocking then rounds differently at the last ulp."""
    c = buf_coords.shape[0]
    dtype = buf_coords.dtype
    rowvalid = st["buf_mask"] > 0
    c_sub = min(cfg.overlap_capacity or c, c)
    if c_sub == c:
        # Full-fidelity mode: no row compaction, no list remap — the exact
        # arrays and expression chain of the sequential _model_scatter, so
        # the cross-program forward is fusion-identical (a compacted
        # gather/scatter wrapper around the same math is NOT: the forward
        # rounds differently at the last ulp for some inputs).
        center_bf = st["buf_mask"].astype(dtype)

        def fsum_full(cc):
            e = model._atomic_e(params, cc, st["buf_types"], nbr_idx,
                                nbr_mask)
            return (e * center_bf).sum(), e

        (_, e_rows), g = jax.value_and_grad(fsum_full, has_aux=True)(
            buf_coords)
        return e_rows, (-g).astype(dtype), jnp.zeros((), bool)
    centers = rowvalid & ~deep          # rows whose output pass A cannot give
    sources = rowvalid & ~deep2         # centers plus every row they gather
    score = jnp.where(sources, -jnp.arange(c, dtype=jnp.float32), -jnp.inf)
    _, sel = jax.lax.top_k(score, c_sub)
    take = jnp.take_along_axis(sources, sel, axis=0)
    sub_overflow = sources.sum() > c_sub
    sel = jnp.where(take, sel, 0)
    # full-index -> sub-index map; padding slots routed to a spill row so
    # the scatter has one writer per real slot
    inv = jnp.zeros((c + 1,), jnp.int32).at[
        jnp.where(take, sel, c)].set(jnp.arange(c_sub, dtype=jnp.int32))
    coords_sub = buf_coords[sel]
    center_b = jnp.take_along_axis(centers, sel, axis=0) & take
    center_bf = center_b.astype(dtype)
    idx_sub = inv[nbr_idx[sel]]
    mask_sub = nbr_mask[sel] * center_bf[:, None]
    idx_sub = jnp.where(mask_sub > 0, idx_sub, 0)

    def fsum(cc):
        e = model._atomic_e(params, cc, st["buf_types"][sel], idx_sub,
                            mask_sub)
        return (e * center_bf).sum(), e

    (_, e_sub), g = jax.value_and_grad(fsum, has_aux=True)(coords_sub)
    f_sub = (-g).astype(dtype)
    e_rows = jnp.zeros((c,), dtype).at[sel].add(e_sub * center_bf)
    f_rows = jnp.zeros((c, 3), dtype).at[sel].add(f_sub * center_bf[:, None])
    return e_rows, f_rows, sub_overflow


def _evaluate_rank_overlap(model: DPModel, params, coords_all, ref_all,
                           st: dict, box, cfg: DDConfig, rcut: float,
                           e_rows_a, f_rows_a, gfree, interior, deep, deep2):
    """Merge pass A (computed pre-gather) with pass B into the sequential
    evaluate-stage outputs — bitwise at the default full-size pass-B
    sub-buffer, ulp-level under a trimmed ``overlap_capacity``."""
    n = coords_all.shape[0]
    dtype = coords_all.dtype
    box = jnp.asarray(box)
    cl = cfg.local_capacity
    buf_coords = _rebuild_buffer(coords_all, ref_all, st, box, cfg)
    nbr_idx, nbr_mask, trim_overflow = _refilter_compact(
        buf_coords, st["nbr_idx"], st["nbr_mask"], cfg, rcut)
    e_rows_b, f_rows_b, sub_overflow = _evaluate_boundary(
        model, params, buf_coords, st, nbr_idx, nbr_mask, cfg, deep, deep2)

    l_idx, l_mask = st["l_idx"], st["l_mask"]
    l_maskf = l_mask.astype(dtype)
    c = buf_coords.shape[0]
    full = min(cfg.overlap_capacity or c, c) == c
    local_mask = jnp.concatenate([l_maskf,
                                  jnp.zeros(cfg.ghost_capacity, dtype)])
    if full:
        # pass B evaluated the untouched buffer with every valid center, so
        # its rows ARE the sequential per-row energies; reducing them with
        # the identical dot keeps the energy bitwise.  Pass A still feeds
        # the force merge below, which is what keeps it live (and
        # overlappable with the gather) in the compiled program.
        e_rows = e_rows_b
    else:
        # per-row select (never add): pass A where ghost-free, pass B
        # elsewhere; trimmed sub-buffers are ulp-level, not bitwise
        e_rows = jnp.concatenate([
            jnp.where(gfree[:cl], e_rows_a, e_rows_b[:cl]),
            jnp.zeros(cfg.ghost_capacity, dtype)])
    e_local = jnp.dot(e_rows, local_mask)
    f_l = jnp.where(interior[:cl, None], f_rows_a, f_rows_b[:cl])
    f_global = jnp.zeros((n, 3), dtype).at[l_idx].add(f_l * l_mask[:, None])

    k_eval = min(cfg.k_eval, st["nbr_idx"].shape[1])
    stats = {"nbr_fill": (nbr_mask > 0).sum().astype(dtype),
             "nbr_slots": st["buf_mask"].sum() * k_eval}
    n_int = (interior[:cl] & l_mask).sum()
    return (e_local, f_global, trim_overflow | sub_overflow, stats, n_int)


# ---------------------------------------------------------------------------
# stage descriptors + the pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage: a per-rank body over a context dict, with its
    in/out keys declared and the name scope its operations carry."""

    name: str
    scope: str
    inputs: tuple
    outputs: tuple
    body: Callable            # body(ctx) -> None (mutates ctx)


class ForcePipeline:
    """The composable distributed force pipeline for one (model, DDConfig,
    mesh, box, n_atoms) tuple — optionally replica-batched when
    ``n_replicas`` > 0 (the batching *transform*; see :class:`_AxisOps`).

    Builders return jitted drivers with the same signatures as the legacy
    ``make_*_fn`` factories (which now delegate here as deprecation shims).
    """

    def __init__(self, model: Optional[DPModel], cfg: DDConfig, mesh: Mesh,
                 box, n_atoms: int, *, n_replicas: int = 0,
                 replica_axis: str = "replica", fault_hook=None):
        cfg.validate(box)
        self._r_local = 0            # replicas per device group (0 unbatched)
        if n_replicas:
            self._r_local = _replica_layout(mesh, cfg, n_replicas,
                                            replica_axis)
            self.ax = _AxisOps(cfg.axis, replica_axis)
        else:
            if cfg.axis not in mesh.shape:
                raise ValueError(f"mesh axes {tuple(mesh.shape)} do not "
                                 f"include the dd axis {cfg.axis!r}")
            if mesh.shape[cfg.axis] != cfg.n_ranks:
                raise ValueError(
                    f"mesh {cfg.axis} size {mesh.shape[cfg.axis]} != grid "
                    f"{cfg.n_ranks} (= prod {cfg.grid_dims}): the dd mesh "
                    "axis must match the decomposition grid")
            self.ax = _AxisOps(cfg.axis)
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.box = jnp.asarray(box)
        self.n_atoms = int(n_atoms)
        self.n_replicas = int(n_replicas)
        self.n_pad = cfg.padded_atoms(n_atoms)
        self.chunk = self.n_pad // cfg.n_ranks
        # model=None builds a check-only pipeline (build_check_fn needs no
        # cutoff); every other builder requires the model
        self.rcut = model.cfg.descriptor.rcut if model is not None else 0.0
        # health.FaultPlan.pipeline_hook seam: read at trace time, so a
        # hook with no armed faults traces the identity (see _post_eval)
        self.fault_hook = fault_hook
        self.stages = self._fused_stages()

    def _require_model(self, builder: str) -> None:
        if self.model is None:
            raise ValueError(f"{builder} needs a model; this ForcePipeline "
                             "was built with model=None (check-only)")

    # -- stage bodies (per-rank; ctx maps names -> arrays) -------------------

    def _fused_stages(self) -> tuple:
        """The fused per-step stage list; stage names keep the Fig. 12 phase
        vocabulary."""
        model, cfg, box, ax = self.model, self.cfg, self.box, self.ax
        rcut, n_atoms = self.rcut, self.n_atoms

        def gather(ctx):
            ctx["coords_all"] = ax.all_gather(ctx["coords_shard"])

        def assemble(ctx):
            rank = jax.lax.axis_index(cfg.axis)

            def one(coords_one):
                grid = _make_grid(coords_one, box, cfg, n_atoms)
                return _assemble_rank(coords_one, ctx["types_all"], box,
                                      grid, cfg, rcut, rank, n_atoms)

            ctx["st"] = ax.vmap(one)(ctx["coords_all"])

        def evaluate(ctx):
            def one(coords_one, st_one):
                return _evaluate_rank(model, ctx["params"], coords_one,
                                      coords_one, st_one, box, cfg, rcut)

            (ctx["e_local"], ctx["f_global"], ctx["trim_ovf"],
             ctx["stats"]) = ax.vmap(one)(ctx["coords_all"], ctx["st"])
            ctx["e_local"], ctx["f_global"] = self._post_eval(
                ctx["e_local"], ctx["f_global"])

        def reduce(ctx):
            st = ctx["st"]
            ovf = st["overflow"] | ctx["trim_ovf"]
            ctx["energy"], ctx["forces"] = self._reduce_forces(
                ctx["e_local"], ctx["f_global"])
            l_count, g_count = st["local_count"], st["ghost_count"]
            cost_max = ax.pmax(l_count + g_count)
            diag = {"local_count": ax.psum(l_count),
                    "ghost_count": ax.psum(g_count),
                    "cost_max": cost_max,
                    "rank_cost": ax.gather_ranks(l_count + g_count),
                    "rank_nonfinite": self._rank_nonfinite(ctx["f_global"]),
                    **self._occupancy_diag(ctx["stats"]),
                    "overflow": ax.psum(ovf.astype(jnp.int32))}
            diag["cost_ratio"] = (
                cost_max * cfg.n_ranks
                / jnp.maximum(diag["local_count"] + diag["ghost_count"],
                              1).astype(jnp.float32))
            ctx["diag"] = diag

        return (
            Stage("gather", "obs.gather", ("coords_shard",), ("coords_all",),
                  gather),
            Stage("assembly", "obs.assembly", ("coords_all", "types_all"),
                  ("st",), assemble),
            Stage("inference", "obs.inference",
                  ("params", "coords_all", "st"),
                  ("e_local", "f_global", "trim_ovf", "stats"), evaluate),
            Stage("force_reduce", "obs.force_reduce",
                  ("e_local", "f_global", "st"),
                  ("energy", "forces", "diag"), reduce),
        )

    def _post_eval(self, e_local, f_global):
        """Fault-injection seam on the pre-reduce per-rank results.

        The hook (``health.FaultPlan.pipeline_hook``) poisons a target
        rank's force contribution *before* the force collective, so the
        failure propagates the way a real blown rank's would.  Its
        armed/unfired spec set is read at trace time: with nothing armed
        the hook returns its inputs and the traced program is unchanged."""
        if self.fault_hook is None:
            return e_local, f_global
        ax = self.ax
        rank = jax.lax.axis_index(self.cfg.axis)
        rep0 = (jax.lax.axis_index(ax.replica_axis) * self._r_local
                if ax.batched else 0)
        return self.fault_hook(rank, rep0, e_local, f_global)

    def _rank_nonfinite(self, f_global):
        """Per-rank count of non-finite entries in the pre-reduce force
        scatter — the per-rank attribution signal for blown evaluations
        (trailing rank axis, like ``rank_cost``)."""
        bad = (~jnp.isfinite(f_global)).sum((-2, -1)).astype(jnp.int32)
        return self.ax.gather_ranks(bad)

    def _reduce_forces(self, e_local, f_global):
        ax, cfg = self.ax, self.cfg
        energy = ax.psum(e_local)
        if cfg.reduce_mode == "reduce_scatter":
            forces = ax.psum_scatter(f_global)           # collective 2'
        else:
            forces = ax.psum(f_global)                   # collective 2
        return energy, forces

    def _occupancy_diag(self, stats) -> dict:
        """Mesh-wide and per-rank list occupancy: the capacity-tuning signal
        surfaced by the trace report's imbalance table."""
        ax = self.ax
        fill, slots = stats["nbr_fill"], stats["nbr_slots"]
        occ_rank = fill / jnp.maximum(slots, 1.0)
        return {"nbr_occupancy": (ax.psum(fill)
                                  / jnp.maximum(ax.psum(slots), 1.0)),
                "rank_occupancy": ax.gather_ranks(occ_rank)}

    def _diag_specs(self, keys) -> dict:
        ax = self.ax
        specs = {k: ax.rspec() for k in keys}
        specs["rank_cost"] = ax.rspec(None)
        specs["rank_occupancy"] = ax.rspec(None)
        specs["rank_nonfinite"] = ax.rspec(None)
        return specs

    def _force_out_spec(self) -> P:
        ax = self.ax
        return (ax.spec(None) if self.cfg.reduce_mode == "reduce_scatter"
                else ax.rspec(None, None))

    def _pad(self, coords, types=None):
        if self.ax.batched:
            coords_p = _pad_atoms_batched(coords, self.n_pad, self.box)
            if types is None:
                return coords_p
            return coords_p, _pad_types(types, self.n_pad)
        return _pad_atoms(coords, self.n_pad, self.box, types)

    # -- drivers: thin compositions over the stage bodies --------------------

    def build_force_fn(self):
        """Fused per-step driver: f(params, coords, types) ->
        (energy, forces, diag) — every stage in one shard_map program."""
        self._require_model("build_force_fn")
        stages = self.stages

        def per_rank(params, coords_shard, types_all):
            ctx = {"params": params, "coords_shard": coords_shard,
                   "types_all": types_all}
            for stage in stages:
                with jax.named_scope(stage.scope):
                    stage.body(ctx)
            return ctx["energy"], ctx["forces"], ctx["diag"]

        ax = self.ax
        diag_specs = self._diag_specs(
            ("local_count", "ghost_count", "cost_max", "nbr_occupancy",
             "cost_ratio", "overflow"))
        mapped = compat.shard_map(
            per_rank, mesh=self.mesh,
            in_specs=(P(), ax.spec(None), P()),
            out_specs=(ax.rspec(), self._force_out_spec(), diag_specs))
        n_atoms = self.n_atoms

        def fn(params, coords, types):
            coords_p, types_p = self._pad(coords, types)
            e, f, diag = mapped(params, coords_p, types_p)
            return e, f[..., :n_atoms, :], diag

        return jax.jit(fn)

    def build_assembly_fn(self):
        """Assembly driver: f(coords, types) -> DDState (gather + assemble,
        plus the replicated routing table the partition stage consumes)."""
        self._require_model("build_assembly_fn")
        ax, cfg = self.ax, self.cfg
        gather_s, assemble_s = self.stages[0], self.stages[1]

        def per_rank(coords_shard, types_all):
            ctx = {"coords_shard": coords_shard, "types_all": types_all}
            with jax.named_scope(gather_s.scope):
                gather_s.body(ctx)
            with jax.named_scope(assemble_s.scope):
                assemble_s.body(ctx)
            st = ctx["st"]
            # replicated routing table: which padded-atom index fills every
            # rank's local slot (the partition stage's send map)
            st["l_slot"] = ax.all_gather(st["l_idx"])
            st["cost_max"] = ax.pmax(st["local_count"] + st["ghost_count"])
            st["local_count"] = ax.psum(st["local_count"])
            st["ghost_count"] = ax.psum(st["ghost_count"])
            st["overflow"] = ax.psum(st["overflow"].astype(jnp.int32))
            return st

        specs = _state_specs(ax)
        out_specs = {f.name: getattr(specs, f.name)
                     for f in dataclasses.fields(DDState) if f.name != "ref"}
        mapped = compat.shard_map(per_rank, mesh=self.mesh,
                                  in_specs=(ax.spec(None), P()),
                                  out_specs=out_specs)

        def assemble(coords, types):
            coords_p, types_p = self._pad(coords, types)
            st = mapped(coords_p, types_p)
            return DDState(ref=coords_p, **st)

        return jax.jit(assemble)

    def build_evaluation_fn(self):
        """Evaluation driver: f(params, coords, state) ->
        (energy, forces, diag).  With ``cfg.overlap`` the interior pass is
        scheduled against the all-gather (partition stage + pass A before
        the gather; pass B and the merge after it)."""
        self._require_model("build_evaluation_fn")
        if self.cfg.overlap:
            return self._build_evaluation_overlap()
        model, cfg, box, ax = self.model, self.cfg, self.box, self.ax
        rcut, chunk = self.rcut, self.chunk

        def per_rank(params, coords_shard, st: DDState):
            with jax.named_scope("obs.gather"):
                coords_all = ax.all_gather(coords_shard)     # collective 1
            rank = jax.lax.axis_index(cfg.axis)
            st_d = _st_dict(st)
            with jax.named_scope("obs.inference"):
                def one(coords_one, ref_one, st_one):
                    return _evaluate_rank(model, params, coords_one, ref_one,
                                          st_one, box, cfg, rcut)

                e_local, f_global, trim_ovf, stats = ax.vmap(one)(
                    coords_all, st.ref, st_d)
            e_local, f_global = self._post_eval(e_local, f_global)
            with jax.named_scope("obs.force_reduce"):
                energy, forces = self._reduce_forces(e_local, f_global)
            disp2 = self._disp2(coords_shard, st.ref, rank)
            diag = self._eval_diag(st, trim_ovf, stats, disp2, f_global)
            return energy, forces, diag

        return self._finish_evaluation(per_rank)

    def _build_evaluation_overlap(self):
        model, cfg, box, ax = self.model, self.cfg, self.box, self.ax
        rcut, chunk = self.rcut, self.chunk

        def per_rank(params, coords_shard, st: DDState):
            rank = jax.lax.axis_index(cfg.axis)
            st_d = _st_dict(st)
            # row classification from the state alone — known pre-gather
            masks = ax.vmap(lambda s: _overlap_masks(cfg, s))(st_d)
            gfree, interior, deep, deep2 = masks
            with jax.named_scope("obs.partition"):
                contrib = ax.vmap(
                    lambda ls, cs: _route_contrib(cs, ls, rank, chunk))(
                        st.l_slot, coords_shard)
                cur_l = ax.psum_scatter(contrib)         # overlap collective
            with jax.named_scope("obs.interior"):
                # pass A: no dependence on the all-gather below — the
                # scheduler is free to run it under the gather's latency
                e_a, f_a = ax.vmap(
                    lambda cl_, ref_, st_, gf_: _evaluate_interior(
                        model, params, cl_, ref_, st_, box, cfg, rcut, gf_))(
                            cur_l, st.ref, st_d, gfree)
            with jax.named_scope("obs.gather"):
                coords_all = ax.all_gather(coords_shard)     # collective 1
            with jax.named_scope("obs.inference"):
                def one(coords_one, ref_one, st_one, ea, fa, gf, it, dp, dp2):
                    return _evaluate_rank_overlap(
                        model, params, coords_one, ref_one, st_one, box, cfg,
                        rcut, ea, fa, gf, it, dp, dp2)

                e_local, f_global, trim_ovf, stats, n_int = ax.vmap(one)(
                    coords_all, st.ref, st_d, e_a, f_a,
                    gfree, interior, deep, deep2)
            e_local, f_global = self._post_eval(e_local, f_global)
            with jax.named_scope("obs.force_reduce"):
                energy, forces = self._reduce_forces(e_local, f_global)
            disp2 = self._disp2(coords_shard, st.ref, rank)
            diag = self._eval_diag(st, trim_ovf, stats, disp2, f_global)
            n_loc = st_d["l_mask"].sum(-1).astype(jnp.int32)
            diag["interior_frac"] = (
                ax.psum(n_int.astype(jnp.int32)).astype(jnp.float32)
                / jnp.maximum(ax.psum(n_loc), 1).astype(jnp.float32))
            return energy, forces, diag

        return self._finish_evaluation(per_rank,
                                       extra_diag=("interior_frac",))

    def _disp2(self, coords_shard, ref, rank):
        """Skin check on this rank's shard only; pmax = the mesh-wide rebuild
        criterion (mirrors ``md.neighbors.needs_rebuild``)."""
        ax, box = self.ax, self.box
        ref_shard = ax.slice_atoms(ref, rank * self.chunk, self.chunk)
        return ax.pmax(ax.vmap(
            lambda c, r: max_displacement2(c, r, box))(coords_shard,
                                                       ref_shard))

    def _eval_diag(self, st: DDState, trim_ovf, stats, disp2,
                   f_global) -> dict:
        ax, cfg = self.ax, self.cfg
        overflow = st.overflow + ax.psum(trim_ovf.astype(jnp.int32))
        total = st.local_count + st.ghost_count
        # per-rank Eq.-8 cost vector, replicated: the masks shard along the
        # mesh axis, so each rank contributes its own local+ghost count
        rank_cost = ax.gather_ranks(
            st.l_mask.sum(-1).astype(jnp.int32)
            + st.g_mask.sum(-1).astype(jnp.int32))
        return {"local_count": st.local_count, "ghost_count": st.ghost_count,
                "overflow": overflow, "max_disp2": disp2,
                "cost_max": st.cost_max, "rank_cost": rank_cost,
                "rank_nonfinite": self._rank_nonfinite(f_global),
                **self._occupancy_diag(stats),
                # max/mean per-rank Eq.-8 cost: the load-imbalance figure the
                # rebalance knob is meant to push toward 1.0
                "cost_ratio": st.cost_max * cfg.n_ranks
                              / jnp.maximum(total, 1).astype(jnp.float32),
                "needs_rebuild": (disp2 > (0.5 * cfg.skin) ** 2)
                                 | (st.overflow > 0)}

    def _finish_evaluation(self, per_rank, extra_diag: tuple = ()):
        ax = self.ax
        diag_specs = self._diag_specs(
            ("local_count", "ghost_count", "overflow", "max_disp2",
             "cost_max", "nbr_occupancy", "cost_ratio", "needs_rebuild")
            + extra_diag)
        mapped = compat.shard_map(
            per_rank, mesh=self.mesh,
            in_specs=(P(), ax.spec(None), _state_specs(ax)),
            out_specs=(ax.rspec(), self._force_out_spec(), diag_specs))
        n_atoms = self.n_atoms

        def evaluate(params, coords, state):
            coords_p = self._pad(coords)
            e, f, diag = mapped(params, coords_p, state)
            return e, f[..., :n_atoms, :], diag

        return jax.jit(evaluate)

    def build_check_fn(self):
        """Standalone rebuild check: f(coords, state) -> bool (per replica
        when batched) — any atom moved more than skin/2 since ``state.ref``
        (pmax across the mesh) or the build overflowed."""
        ax, cfg = self.ax, self.cfg

        def per_rank(coords_shard, ref):
            rank = jax.lax.axis_index(cfg.axis)
            return self._disp2(coords_shard, ref, rank)

        mapped = compat.shard_map(
            per_rank, mesh=self.mesh,
            in_specs=(ax.spec(None), ax.rspec(None, None)),
            out_specs=ax.rspec())

        def check(coords, state):
            disp2 = mapped(self._pad(coords), state.ref)
            return (disp2 > (0.5 * cfg.skin) ** 2) | (state.overflow > 0)

        return jax.jit(check)
