"""NNPot-style special-force provider with a DeePMD backend (paper Sec. IV-A).

``DeepmdForceProvider`` is the analogue of the paper's ``DeepmdModel`` class
inside GROMACS's NNPot module: it owns the DP model handle, performs the
data-layout + unit conversions before inference, extracts the marked ("NN")
atoms from the full position array, runs (optionally distributed) inference,
and scatters the resulting forces back into engine layout.

With a positive skin (``DDConfig.skin`` distributed, the ``skin`` argument
single-domain) the provider exposes the amortized two-phase API the engine's
fused scan loop drives — ``assemble`` / ``evaluate`` / ``needs_rebuild`` /
``grow`` — mirroring how GROMACS amortizes pair-list construction over
``nstlist`` steps.

The provider implements :class:`repro.backend.StatefulForceBackend`: the
typed entry point is :meth:`DeepmdForceProvider.compute` (a
:class:`~repro.backend.ForceRequest` in, a
:class:`~repro.backend.ForceResult` out); the legacy eager
``__call__(positions, box)`` survives as a deprecation shim that routes
through the protocol.  Subclasses change the execution engine by overriding
the documented ``backend_*`` hooks (see the class docstring), not by
copying private methods.

Kernel path + precision: the model's ``DescriptorConfig.use_pallas`` and
``DPConfig.dtype`` flow through unchanged — the provider hands the model
fp32 coordinates and receives fp32 energies/forces whatever the compute
policy (bf16 only ever touches matmul operands inside the model), so unit
conversion and the engine-layout scatter are precision-neutral.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..backend import ForceRequest, ForceResult
from ..dp.model import DPModel
from ..md.neighbors import needs_rebuild as _nlist_needs_rebuild
from .ddinfer import (DDConfig, single_domain_forces,
                      single_domain_forces_nlist, single_domain_state)
from .pipeline import ForcePipeline


# dd diag entries surfaced as per-step observability counters (see
# repro.obs.trace): everything the Fig. 12 / imbalance reports consume
_COUNTER_KEYS = ("local_count", "ghost_count", "cost_max", "cost_ratio",
                 "rank_cost", "nbr_occupancy", "rank_occupancy", "max_disp2",
                 "interior_frac", "rank_nonfinite")


@dataclasses.dataclass(frozen=True)
class UnitConversion:
    """GROMACS (nm, kJ/mol) <-> model native units (DeePMD: Angstrom, eV).

    The in-house model here is trained directly in GROMACS units, so the
    default is identity; the eV/Angstrom preset mirrors the conversions the
    paper's DeepmdModel wrapper performs around deepmd::compute().
    """

    length_to_model: float = 1.0   # nm -> model length
    energy_to_engine: float = 1.0  # model energy -> kJ/mol

    @staticmethod
    def deepmd_ev_angstrom() -> "UnitConversion":
        return UnitConversion(length_to_model=10.0,      # nm -> A
                              energy_to_engine=96.48533212)  # eV -> kJ/mol

    @property
    def force_to_engine(self) -> float:
        # dE/dr: (eV -> kJ/mol) * (1/A -> 1/nm)
        return self.energy_to_engine * self.length_to_model


class DeepmdForceProvider:
    """Plugs into ``MDEngine(special_force=...)``.

    nn_indices are static (topology-time preprocessing marks the DP group);
    the provider is jit-transparent: calling it inside the engine's jitted
    step traces straight through shard_map when distributed.

    ``skin`` (model length units; for the distributed path set
    ``DDConfig.skin`` instead, e.g. via ``suggest_config(..., skin=...)``)
    enables decomposition reuse: ``assemble`` builds a persistent state
    (distributed: a :class:`repro.core.DDState`; single-domain: a
    skin-widened full :class:`~repro.md.neighbors.NeighborList`) and
    ``evaluate`` reuses it until ``needs_rebuild`` reports an atom moved
    more than skin/2.  ``grow`` doubles the static capacities after an
    overflow (the engine re-runs the affected window).

    **Extension hooks** (the official subclassing surface — override these,
    never the underscore internals): the distributed drivers come from
    :meth:`backend_build_fns` (called at init and after every ``grow``),
    and the single-domain execution engine is the four hooks

    ============================  =========================================
    ``backend_assemble``          nn_pos -> reusable neighbor state
    ``backend_needs_rebuild``     (nn_pos, state) -> rebuild flag(s)
    ``backend_evaluate``          (nn_pos, state) -> (e, f_nn, flags)
    ``backend_forces``            nn_pos -> (e, f_nn) fused per-step path
    ============================  =========================================

    all in *model* units over the extracted NN group (leading batch axes
    pass through) — ``repro.ensemble.BatchedDeepmdProvider`` overrides
    exactly this set to vmap the pipeline over a replica axis."""

    batched = False    # ForceBackend capability flag: no leading replica axis
    host_side = False  # jit-transparent: fuses into the engine's windows

    def __init__(self, model: DPModel, params, nn_indices: np.ndarray,
                 types, box, n_atoms: int,
                 dd_config: Optional[DDConfig] = None,
                 mesh: Optional[Mesh] = None,
                 units: UnitConversion = UnitConversion(),
                 nbr_capacity: int = 64, skin: float = 0.0,
                 fault_hook=None):
        self.model = model
        self.params = params
        self.nn_indices = jnp.asarray(np.asarray(nn_indices, np.int32))
        self.n_nn = len(nn_indices)
        self.n_atoms = n_atoms
        self.units = units
        self.nbr_capacity = nbr_capacity
        nn_types = jnp.asarray(types)[self.nn_indices]
        box_model = jnp.asarray(box) * units.length_to_model
        self.box_model = box_model
        self.nn_types = nn_types
        self.dd_config = dd_config
        self.mesh = mesh
        # health.FaultPlan.pipeline_hook seam, threaded into every
        # ForcePipeline this provider (re)builds
        self.fault_hook = fault_hook
        if dd_config is not None:
            assert mesh is not None, "distributed mode needs a mesh"
            self.skin = dd_config.skin
        else:
            self.skin = skin
            if skin > 0:
                # widen the single-domain list capacity with the skin volume
                rcut = model.cfg.descriptor.rcut
                self.nbr_capacity = int(np.ceil(
                    nbr_capacity * ((rcut + skin) / rcut) ** 3))
        self.backend_build_fns()
        self._state = None
        self.growths = 0
        self.last_diag: Optional[dict] = None

    def backend_build_fns(self) -> None:
        """Hook: (re)build the jitted distributed drivers from ONE
        :class:`~repro.core.pipeline.ForcePipeline` — called at init and
        after every ``grow`` (capacities may have changed).  The pipeline is
        exposed as ``self.pipeline`` so callers (serve executors) can derive
        further compositions from the same stage list."""
        if self.dd_config is not None:
            self.pipeline = ForcePipeline(self.model, self.dd_config,
                                          self.mesh, self.box_model,
                                          self.n_nn,
                                          fault_hook=self.fault_hook)
            self._dist_fn = self.pipeline.build_force_fn()
            self._asm_fn = self.pipeline.build_assembly_fn()
            self._eval_fn = self.pipeline.build_evaluation_fn()
            self._check_fn = self.pipeline.build_check_fn()
        else:
            self.pipeline = None
            self._dist_fn = None

    # -- amortized two-phase API (engine scan loop) -------------------------

    @property
    def stateful(self) -> bool:
        """True when the engine should drive the assemble/evaluate split."""
        return self.skin > 0

    def _to_model(self, positions: jax.Array) -> jax.Array:
        # leading batch axes (the ensemble's replica axis) pass through
        nn_pos = (positions[..., self.nn_indices, :]
                  * self.units.length_to_model)
        # wrap into the model box (virtual DD expects wrapped coordinates)
        return jnp.mod(nn_pos, self.box_model)

    def assemble(self, positions: jax.Array):
        """Assembly phase at the current positions -> reusable state."""
        nn_pos = self._to_model(positions)
        if self.dd_config is not None:
            return self._asm_fn(nn_pos, self.nn_types)
        return self.backend_assemble(nn_pos)

    def backend_assemble(self, nn_pos: jax.Array):
        """Hook: single-domain assembly (model units, NN group)."""
        return single_domain_state(self.model, nn_pos, self.box_model,
                                   self.nbr_capacity, self.skin)

    def state_overflow(self, state) -> jax.Array:
        """() bool/int — static capacities were exceeded; state invalid."""
        if self.dd_config is not None:
            return state.overflow > 0
        return state.overflow

    def needs_rebuild(self, positions: jax.Array, state) -> jax.Array:
        """() bool — some atom moved more than skin/2 since assembly (the
        distributed path checks shard-locally and pmaxes across the mesh)."""
        nn_pos = self._to_model(positions)
        if self.dd_config is not None:
            return self._check_fn(nn_pos, state)
        return self.backend_needs_rebuild(nn_pos, state)

    def backend_needs_rebuild(self, nn_pos: jax.Array, state):
        """Hook: single-domain skin displacement check."""
        return _nlist_needs_rebuild(state, nn_pos, self.box_model, self.skin)

    def evaluate(self, positions: jax.Array, state):
        """Evaluation phase: (energy, forces (N,3) engine units, flags).

        ``flags["needs_rebuild"]`` is the skin displacement check evaluated
        at these positions (free for the distributed path — the evaluation
        already pmaxes the shard displacements), so callers evaluate first
        and rebuild + re-evaluate only when it fires, instead of paying a
        separate check dispatch every step."""
        nn_pos = self._to_model(positions)
        if self.dd_config is not None:
            e, f_nn, diag = self._eval_fn(self.params, nn_pos, state)
            flags = {"overflow": diag["overflow"] > 0,
                     "needs_rebuild": diag["needs_rebuild"],
                     # per-step device counters for the observability layer
                     # (already computed inside the evaluation — free); the
                     # engine threads these out of its scan windows when the
                     # tracer wants them, XLA drops them otherwise
                     "counters": {k: diag[k] for k in _COUNTER_KEYS
                                  if k in diag}}
        else:
            e, f_nn, flags = self.backend_evaluate(nn_pos, state)
        e, forces = self._to_engine(e, f_nn, positions)
        return e, forces, flags

    def backend_evaluate(self, nn_pos: jax.Array, state):
        """Hook: single-domain evaluation reusing ``state``."""
        e, f_nn = single_domain_forces_nlist(
            self.model, self.params, nn_pos, self.nn_types,
            self.box_model, state)
        flags = {"overflow": state.overflow,
                 "needs_rebuild": self.backend_needs_rebuild(
                     nn_pos, state)}
        return e, f_nn, flags

    def grow(self) -> None:
        """Double the static capacities after an overflow (rare: triggers a
        re-jit; the engine re-runs the affected window afterwards)."""
        self.growths += 1
        if self.dd_config is not None:
            c = self.dd_config
            # the Pallas attention kernel caps the model-facing K at 128
            # (DDConfig.__post_init__ rejects more); growth keeps the build
            # list doubling regardless — only the compacted K saturates
            k_eval = 2 * c.k_eval
            if c.use_pallas:
                k_eval = min(k_eval, 128)
            self.dd_config = dataclasses.replace(
                c, nbr_capacity=2 * c.nbr_capacity,
                nbr_capacity_eval=k_eval,
                local_capacity=2 * c.local_capacity,
                ghost_capacity=min(2 * c.ghost_capacity, 27 * self.n_nn),
                cell_capacity=2 * c.cell_capacity,
                subcell_capacity=2 * c.subcell_capacity,
                overlap_capacity=(2 * c.overlap_capacity
                                  if c.overlap_capacity else 0))
            self.backend_build_fns()
        else:
            self.nbr_capacity *= 2
        self._state = None

    # -- ForceBackend entry point -------------------------------------------

    def _to_engine(self, e, f_nn, positions):
        e = e * self.units.energy_to_engine
        f_nn = f_nn * self.units.force_to_engine
        forces = jnp.zeros(positions.shape[:-2] + (self.n_atoms, 3),
                           positions.dtype)
        forces = forces.at[..., self.nn_indices, :].set(
            f_nn.astype(positions.dtype))
        return e.astype(positions.dtype), forces

    def compute(self, request: ForceRequest) -> ForceResult:
        """:class:`~repro.backend.ForceBackend` entry point.

        ``request.positions`` is the full engine-layout position array
        (engine units); the result carries (energy kJ/mol, forces (N,3)
        kJ/mol/nm) with zeros off the NN group.  Eager calls with a positive
        skin reuse the cached state across calls (rebuilding when the
        displacement check trips); traced calls — and skin = 0 — run the
        fused per-step pipeline and trace straight through (jit-transparent).
        """
        positions = request.positions
        traced = isinstance(positions, jax.core.Tracer)
        if self.stateful and not traced:
            if self._state is None:
                self._state = self.assemble(positions)
            e, forces, flags = self.evaluate(positions, self._state)
            if bool(jnp.any(flags["needs_rebuild"])):
                self._state = self.assemble(positions)
                e, forces, flags = self.evaluate(positions, self._state)
            for _ in range(8):
                # capacity overflow (assembly or k_eval trim) would silently
                # truncate forces: grow and recompute until the state fits
                if not bool(jnp.any(flags["overflow"])):
                    break
                self.grow()
                self._state = self.assemble(positions)
                e, forces, flags = self.evaluate(positions, self._state)
            else:
                raise RuntimeError("special-force capacity still exceeded "
                                   "after 8 doublings")
            self.last_diag = {k: bool(jnp.any(v)) for k, v in flags.items()
                              if k != "counters"}
            return ForceResult(energy=e, forces=forces,
                               diagnostics=dict(self.last_diag),
                               tenant=request.tenant, req_id=request.req_id)
        nn_pos = self._to_model(positions)
        diag = {}
        if self._dist_fn is not None:
            e, f_nn, diag = self._dist_fn(self.params, nn_pos, self.nn_types)
            if not traced:
                # only observable when called eagerly; inside a jitted MD
                # step the diag values are tracers and must not leak
                self.last_diag = diag
        else:
            e, f_nn = self.backend_forces(nn_pos)
        e, forces = self._to_engine(e, f_nn, positions)
        return ForceResult(energy=e, forces=forces, diagnostics=dict(diag),
                           tenant=request.tenant, req_id=request.req_id)

    # -- deprecated eager surface -------------------------------------------

    _warned_eager_call = False

    def __call__(self, positions: jax.Array, box: jax.Array):
        """Deprecated eager entry point — use :meth:`compute` with a
        :class:`~repro.backend.ForceRequest` instead.  Kept as a shim (warns
        once per provider class) that routes through the protocol."""
        cls = type(self)
        if not cls._warned_eager_call:
            cls._warned_eager_call = True
            warnings.warn(
                f"{cls.__name__}(positions, box) is deprecated; use "
                f"{cls.__name__}.compute(ForceRequest(positions=..., "
                "box=...)) — the ForceBackend protocol entry point",
                DeprecationWarning, stacklevel=2)
        res = self.compute(ForceRequest(positions=positions, box=box))
        return res.energy, res.forces

    def backend_forces(self, nn_pos: jax.Array):
        """Hook: single-domain fused per-step forces (model units)."""
        return single_domain_forces(
            self.model, self.params, nn_pos, self.nn_types,
            self.box_model, self.nbr_capacity)
