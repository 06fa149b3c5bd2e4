"""MD engine: the GROMACS main-loop analogue (paper Fig. 5).

Conceptual step order: (1) init, (2) domain decomposition / load balance,
(3) position exchange, (4) neighbor-list construction, (5) interaction
evaluation, (6) special force (NNPot), (7) force reduction + update,
(8) output.  Stages (2), (3) and the NN part of (6) live in
``repro.core`` when running distributed; this module owns the host loop,
the classical interactions, and checkpoint/restart fault tolerance.

Two host-loop modes (``EngineConfig.loop_mode``):

``"scan"`` (default)
    The inner window between rebuild/observe/checkpoint boundaries runs as
    a *single* jitted ``lax.scan`` — classical forces, the (optionally
    distributed) DP evaluation, integrator and thermostat all fused, with
    displacement-triggered neighbor/decomposition rebuilds folded in as
    ``lax.cond`` branches.  The host only syncs at window boundaries,
    removing the per-step ``block_until_ready`` that made every step a
    global sync point (the paper's Fig. 6 bottleneck).

``"step"``
    One host round-trip per step with the neighbor / classical / special /
    integrate stages timed separately — the paper-Fig.-9-style overhead
    decomposition (see ``benchmarks/fig9_overhead.py``).

Mid-run failures no longer kill the trajectory.  Every window ends in a
``repro.health.WindowVerdict`` dispatched through the ``RECOVERY_POLICY``
table: capacity overflow keeps the grow-and-replay path (host rebuild with
doubled capacity, re-jit, replay from the window's saved start state);
numerical guard trips (``GuardConfig`` — NaN/Inf, displacement bound,
temperature ceiling, energy jump, compiled into the scan when enabled) roll
back to the window start — or the last verified ``AsyncCheckpointer`` step
when the start itself is tainted — and replay, first at the original dt
(transient-fault hypothesis: an injected one-shot fault replays bitwise
fault-free) and then with a temporarily shrunk dt; exhausted recovery dumps
an emergency checkpoint + diagnostics bundle before raising.  Deterministic
fault injection (``repro.health.FaultPlan``) exercises each path.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import ForceRequest
from ..health import (GuardConfig, GuardTripError, WindowVerdict,
                      dump_emergency, step_guard_trip)
from ..obs import Tracer
from . import observables
from .forcefield import ForceFieldConfig, classical_forces, pair_table
from .integrators import MDState, init_velocities, leapfrog_step, berendsen_rescale
from .neighbors import (NeighborList, build_neighbor_list,
                        cell_capacity_scale, needs_rebuild)
from .system import System

# the run loop's host spans, whose seconds per run are published as gauges
RUN_SPANS = ("md.window", "md.verdict", "md.rebuild")


class ForceProvider(Protocol):
    """NNPot-style special-force provider (paper Sec. IV-A).

    The engine prefers the typed :class:`repro.backend.ForceBackend`
    surface (``compute(ForceRequest) -> ForceResult``, plus the stateful
    assemble/evaluate split when ``stateful`` is true) and falls back to
    this legacy eager callable for plain-function providers."""

    def __call__(self, positions: jax.Array, box: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Returns (energy, forces(N,3)); forces are zero off the NN group."""


@dataclasses.dataclass
class EngineConfig:
    dt: float = 0.002                  # ps (paper Tab. II)
    cutoff: float = 1.2                # classical cutoff
    skin: float = 0.1                  # Verlet buffer
    neighbor_capacity: int = 96
    rebuild_every: int = 10            # also displacement-triggered
    thermostat_t: Optional[float] = None
    thermostat_tau: float = 0.5
    checkpoint_every: int = 0          # steps; 0 = off
    checkpoint_path: Optional[str] = None
    loop_mode: str = "scan"            # "scan" (fused windows) | "step"
    max_capacity_growths: int = 6      # doublings before giving up
    emergency_path: Optional[str] = None  # unrecoverable-verdict dump root
    ff: ForceFieldConfig = dataclasses.field(default_factory=ForceFieldConfig)


class MDEngine:
    """Host-side driver around fully jitted inner windows.

    Fault tolerance: ``checkpoint_every`` snapshots (positions, velocities,
    forces, step, rng) via ``repro.ckpt``; ``MDEngine.restore`` resumes a run
    bit-exactly (deterministic integrator + stored RNG), and the *virtual*
    decomposition in repro.core means restart works at any device count —
    the decoupling argument from the paper.

    The window machinery (fused-scan segments, displacement-triggered
    rebuild conds, grow-and-replay on overflow, observe/checkpoint cadence)
    is shared with the replica-batched ``repro.ensemble.EnsembleEngine``:
    every per-trajectory flag is shaped ``_batch_shape`` (``()`` here,
    ``(R,)`` there), host decisions reduce with any()/sum(), and the
    rebuild check / integrator / observation packaging are overridable
    hooks.
    """

    _batch_shape: tuple = ()        # leading shape of per-trajectory flags
    _extra_boundary_every: int = 0  # extra host boundary (replica exchange)

    def __init__(self, system: System, config: EngineConfig,
                 special_force: Optional[ForceProvider] = None,
                 obs=None, guard: Optional[GuardConfig] = None,
                 faults=None, checkpointer=None):
        self.system = system
        self.config = config
        self.special_force = special_force
        # obs is a Tracer, an ObsConfig, or None (disabled).  The tracer's
        # wants_counters flag is baked into the jitted windows at trace
        # time, so decide observability at construction, not mid-run.
        self.tracer = Tracer.ensure(obs)
        # guard/faults are likewise trace-time state: the guard-trip flag
        # only enters the scan carry when guard.enabled, so a disabled
        # guard traces a program identical to pre-guard engines (bitwise
        # contract, enforced by tests/test_health.py)
        self.guard = guard if guard is not None else GuardConfig()
        self._guard_on = bool(self.guard.enabled)
        self.faults = faults                 # Optional[health.FaultPlan]
        self.checkpointer = checkpointer     # Optional[AsyncCheckpointer]
        self._last_state = None              # for emergency dumps
        self._stateful = bool(getattr(special_force, "stateful", False))
        # host_side backends (ForceBackend capability flag, e.g. the serving
        # client) block on host round-trips and must not be fused into
        # jitted windows: force the per-step host loop for them
        self._host_special = bool(getattr(special_force, "host_side", False))
        self._cell_cap_scale = 1.0
        self._cells_sized = False        # sized from the first list build
        self._build_fns()
        self._window_cache: dict[int, Callable] = {}
        self.timings: dict[str, float] = self._init_timings()
        self.diagnostics: dict = self._init_diagnostics()

    def _init_timings(self) -> dict:
        # timings and per-step device-counter records share a lifetime —
        # both are per-run.  Clearing them together keeps back-to-back
        # run() calls from leaking the previous run's stale step counters
        # (or duplicate absolute step numbers, after a restart from step 0)
        # into the next trace.  Guarded: __init__ calls this before the
        # tracer exists on some subclass construction orders.
        tracer = getattr(self, "tracer", None)
        if tracer is not None:
            tracer.clear_steps()
        return {"classical": 0.0, "special": 0.0, "integrate": 0.0,
                "neighbor": 0.0, "scan": 0.0}

    def _init_diagnostics(self) -> dict:
        return {"capacity_growths": [],
                "special_growths": 0,
                "displacement_rebuilds": 0,
                "special_rebuilds": 0,
                "cadence_rebuilds": 0,
                "window_reruns": 0,
                "guard_trips": 0,
                "guard_rollbacks": 0,
                "checkpoint_restores": 0,
                "emergency_dumps": []}

    def reset(self) -> None:
        """Zero ``timings`` and ``diagnostics`` and clear the tracer's event
        buffer.  ``run`` already resets ``timings`` on entry (they are
        per-run); ``diagnostics`` are cumulative across runs — capacity
        growths outlive the run that triggered them — so a full reset is
        explicit, via this method."""
        self.timings = self._init_timings()
        self.diagnostics = self._init_diagnostics()
        self.tracer.reset()

    # -- construction ------------------------------------------------------

    def _eval_special_stateless(self, positions, box):
        """Per-step special force through the ForceBackend protocol
        (``compute`` with a typed request); legacy plain callables keep the
        eager two-tuple convention.  Jit-transparent either way."""
        special = self.special_force
        if hasattr(special, "compute"):
            res = special.compute(ForceRequest(positions=positions, box=box))
            return res.energy, res.forces
        return special(positions, box)

    def _classical_one(self, pos, nlist):
        """Single-trajectory classical forces — the one definition both the
        scalar engine and the vmapped ensemble engine build on."""
        return classical_forces(pos, self.system, nlist, self.config.ff, True)

    def _classical_neighbor_list(self, positions, capacity: int,
                                 cell_cap_scale: float) -> NeighborList:
        """Single-trajectory classical half list with its pair table."""
        cfg = self.config
        nl = build_neighbor_list(positions, self.system.box, cfg.cutoff,
                                 capacity, half=True, skin=cfg.skin,
                                 cell_cap_scale=cell_cap_scale)
        return dataclasses.replace(nl, pairs=pair_table(self.system, nl,
                                                        cfg.ff))

    def _integrate_one(self, state: MDState, f, thermostat_t):
        """Single-trajectory leapfrog + optional Berendsen rescale toward
        ``thermostat_t`` (None disables; the ensemble engine passes each
        replica's ladder temperature)."""
        cfg = self.config
        new = leapfrog_step(state, f, self.system.masses, self.system.box,
                            cfg.dt)
        if thermostat_t is not None:
            v = berendsen_rescale(new.velocities, self.system.masses,
                                  thermostat_t, cfg.dt, cfg.thermostat_tau)
            new = dataclasses.replace(new, velocities=v)
        return new

    def _build_fns(self):
        cfg = self.config
        self._classical_fn = jax.jit(self._classical_one)
        self._nlist_fn = jax.jit(self._classical_neighbor_list,
                                 static_argnums=(1, 2))
        self._integrate_fn = jax.jit(
            lambda state, f: self._integrate_one(state, f, cfg.thermostat_t))

    def _step_parts(self, state: MDState, nlist: NeighborList, sp_state,
                    e_prev=None):
        """One step from already-valid lists: the shared scan/step core.

        Returns (new_state, nlist_out, sp_state_out, e_cl, e_sp, rb, sp_rb,
        sp_ovf, trip, rec) — ``rec`` is the per-step counter record for the
        observability tracer (empty unless ``tracer.wants_counters``; XLA
        dead-code-eliminates the counters whenever it stays empty) and
        ``trip`` the per-trajectory guard flag (None with the guard off —
        the traced program is then unchanged).  ``e_prev`` is the previous
        step's total potential energy for the energy-jump guard (only
        passed when the guard is on).  Traceable: rebuilds inside are
        data-dependent ``lax.cond`` branches, and injected faults gate on
        ``state.step`` device-side.
        """
        cfg = self.config
        system = self.system
        special = self.special_force

        # name scopes are HLO metadata only: they let a device trace credit
        # the window's operations to the engine's own stages
        with jax.named_scope("md.rebuild_check"):
            rb = self._check_rebuild(nlist, state.positions)
        with jax.named_scope("md.neighbor"):
            nlist = jax.lax.cond(jnp.any(rb),
                                 lambda p, nl: self.build_nlist(p),
                                 lambda p, nl: nl, state.positions, nlist)
        with jax.named_scope("md.classical"):
            e_cl, f = self._classical_fn(state.positions, nlist)
        f_sp = None
        e_sp = jnp.zeros(self._batch_shape, f.dtype)
        sp_rb = jnp.zeros(self._batch_shape, bool)
        sp_ovf = jnp.zeros(self._batch_shape, bool)
        sp_counters: dict = {}
        if special is not None:
            if self._stateful:
                # evaluate first: the displacement check comes out of the
                # evaluation's own diagnostics, so the common (no-rebuild)
                # step pays no separate check dispatch.  When it fires, the
                # stale result is discarded: rebuild and re-evaluate.
                e_sp, f_sp, fl = special.evaluate(state.positions, sp_state)
                sp_rb = fl["needs_rebuild"]

                def rebuilt(p, s):
                    s2 = special.assemble(p)
                    e2, f2, fl2 = special.evaluate(p, s2)
                    return s2, e2, f2, fl2

                def kept(p, s):
                    return s, e_sp, f_sp, fl

                sp_state, e_sp, f_sp, fl_out = jax.lax.cond(
                    jnp.any(sp_rb), rebuilt, kept, state.positions, sp_state)
                sp_ovf = fl_out["overflow"]
                sp_counters = fl_out.get("counters", {})
            else:
                e_sp, f_sp = self._eval_special_stateless(state.positions,
                                                          system.box)
        with jax.named_scope("md.integrate"):
            if f_sp is not None:
                f = f + f_sp
            if self.faults is not None:
                # exact-step injection seam; a fully fired plan contributes
                # nothing and traces the unfaulted program
                f, sp_ovf = self.faults.apply_engine(state.step, f, sp_ovf)
            new = self._integrate_fn(state, f)
        trip = None
        if self._guard_on:
            trip = step_guard_trip(self.guard, state.positions, new,
                                   system.masses, system.box,
                                   e_cl + e_sp, e_prev)
        rec = {}
        if self.tracer.wants_counters:
            rec = {"e_classical": e_cl, "e_special": e_sp,
                   "rebuild": rb, "sp_rebuild": sp_rb,
                   "nlist_overflow": nlist.overflow, "sp_overflow": sp_ovf,
                   **sp_counters}
        return (new, nlist, sp_state, e_cl, e_sp, rb, sp_rb, sp_ovf, trip,
                rec)

    def _check_rebuild(self, nlist: NeighborList, positions) -> jax.Array:
        """Displacement-triggered rebuild flag(s), shaped ``_batch_shape``."""
        return needs_rebuild(nlist, positions, self.system.box,
                             self.config.skin)

    def _window_fn(self, k: int) -> Callable:
        """Jitted ``lax.scan`` over ``k`` fused steps (cached per length)."""
        if k in self._window_cache:
            return self._window_cache[k]

        def body(carry, _):
            state, nlist, sp_state, flags, e_cl0, e_sp0 = carry
            # previous step's total energy feeds the energy-jump guard;
            # with the guard off nothing extra is computed or carried
            e_prev = (e_cl0 + e_sp0) if self._guard_on else None
            (state, nlist, sp_state, e_cl, e_sp, rb, sp_rb,
             sp_ovf, trip, rec) = self._step_parts(state, nlist, sp_state,
                                                   e_prev=e_prev)
            out_flags = {
                "rebuilds": flags["rebuilds"] + rb.astype(jnp.int32),
                "sp_rebuilds": flags["sp_rebuilds"] + sp_rb.astype(jnp.int32),
                "nlist_overflow": flags["nlist_overflow"] | nlist.overflow,
                "sp_overflow": flags["sp_overflow"] | sp_ovf,
            }
            if self._guard_on:
                out_flags["guard_trip"] = flags["guard_trip"] | trip
            # the scan stacks rec along the step axis for free; with the
            # tracer off rec is {} and nothing is carried
            return (state, nlist, sp_state, out_flags, e_cl, e_sp), rec

        def run_window(state, nlist, sp_state):
            bs = self._batch_shape
            flags = {"rebuilds": jnp.zeros(bs, jnp.int32),
                     "sp_rebuilds": jnp.zeros(bs, jnp.int32),
                     "nlist_overflow": jnp.zeros(bs, bool),
                     "sp_overflow": jnp.zeros(bs, bool)}
            zero = jnp.zeros(bs)
            e0 = zero
            if self._guard_on:
                flags["guard_trip"] = jnp.zeros(bs, bool)
                # NaN disables the first step's energy-jump comparison
                # (IEEE: NaN > thr is False) without a first-step flag
                e0 = jnp.full(bs, jnp.nan)
            carry = (state, nlist, sp_state, flags, e0, zero)
            carry, recs = jax.lax.scan(body, carry, None, length=k)
            return carry, recs

        fn = jax.jit(run_window)
        self._window_cache[k] = fn
        return fn

    def lower_window(self, state: MDState, nlist: NeighborList,
                     sp_state) -> jax.stages.Lowered:
        """The ``rebuild_every``-step window program lowered at these
        arguments.  Its compiled text (``.compile().as_text()``) carries
        the ``md.*``, ``obs.*`` and ``dp.*`` name scopes of every operation,
        which is how a device trace of the window is credited to stages."""
        return self._window_fn(self.config.rebuild_every).lower(
            state, nlist, sp_state if self._stateful else None)

    # -- lifecycle ---------------------------------------------------------

    def init_state(self, positions: jax.Array, temperature: float = 300.0,
                   seed: int = 0) -> MDState:
        rng = jax.random.PRNGKey(seed)
        rng, sub = jax.random.split(rng)
        v = init_velocities(sub, self.system.masses, temperature)
        return MDState(positions=positions, velocities=v,
                       forces=jnp.zeros_like(positions),
                       step=jnp.zeros((), jnp.int32), rng=rng)

    def build_nlist(self, positions) -> NeighborList:
        """The classical list and its pair table, one jitted program (on
        the host side and in the window's displacement-rebuild branch)."""
        return self._nlist_fn(positions, self.config.neighbor_capacity,
                              self._cell_cap_scale)

    # -- capacity growth (mid-run overflow no longer kills the run) --------

    def _grow_neighbor_capacity(self) -> None:
        cfg = self.config
        if len(self.diagnostics["capacity_growths"]) >= cfg.max_capacity_growths:
            self._emergency("neighbor capacity still exceeded after "
                            f"{cfg.max_capacity_growths} doublings")
        cfg.neighbor_capacity *= 2
        self._cell_cap_scale *= 2.0  # cell occupancy can be the overflow too
        self.diagnostics["capacity_growths"].append(cfg.neighbor_capacity)
        self._window_cache.clear()   # windows close over the old capacity

    def _build_nlist_grown(self, positions) -> NeighborList:
        """Build the classical list, doubling capacity until it fits.  The
        first build sizes the cells from the busiest one in ``positions``."""
        if not self._cells_sized:
            cfg = self.config
            self._cell_cap_scale = max(self._cell_cap_scale,
                                       cell_capacity_scale(
                                           positions, self.system.box,
                                           cfg.cutoff, cfg.skin))
            self._cells_sized = True
        while True:
            nlist = self.build_nlist(positions)
            if not self._host_read(bool, jnp.any(nlist.overflow)):
                return nlist
            self._grow_neighbor_capacity()

    def _assemble_special_grown(self, positions):
        """Assemble the special-force state, growing its capacities on
        overflow (rare re-jit; surfaced in diagnostics)."""
        special = self.special_force
        for _ in range(self.config.max_capacity_growths + 1):
            sp_state = special.assemble(positions)
            if not self._host_read(bool,
                                   jnp.any(special.state_overflow(sp_state))):
                return sp_state
            special.grow()
            self.diagnostics["special_growths"] += 1
            self._window_cache.clear()
        self._emergency("special-force capacity still exceeded after "
                        f"{self.config.max_capacity_growths} doublings")

    # -- main loop ---------------------------------------------------------

    def _segment_len(self, i: int, abs_step: int, n_steps: int,
                     observing: bool, observe_every: int) -> int:
        """Steps until the next host boundary (rebuild cadence, observe,
        checkpoint, or end of run), counting from relative step ``i``."""
        cfg = self.config
        ends = [n_steps]
        re = cfg.rebuild_every
        ends.append((i // re + 1) * re)
        if self._extra_boundary_every:
            ee = self._extra_boundary_every
            ends.append((i // ee + 1) * ee)
        if observing:
            # observation happens after relative steps 1, 1+obs, 1+2*obs, ...
            ends.append(i + 1 if i % observe_every == 0
                        else ((i - 1) // observe_every + 1) * observe_every + 1)
        if cfg.checkpoint_every and (cfg.checkpoint_path
                                     or self.checkpointer is not None):
            # abs_step is the absolute step count at relative step i
            ce = cfg.checkpoint_every
            ends.append(i + (-abs_step - 1) % ce + 1)
        return max(1, min(e for e in ends if e > i) - i)

    def _window_verdict(self, flags) -> WindowVerdict:
        """Host-side verdict for one finished window's device flags.

        Capacity overflow takes precedence over a guard trip: an overflowed
        window computed truncated forces, so any trip it reports is judged
        afresh on the grown replay."""
        nlist_ovf = self._host_read(bool, jnp.any(flags["nlist_overflow"]))
        sp_ovf = self._host_read(bool, jnp.any(flags["sp_overflow"]))
        if nlist_ovf or sp_ovf:
            return WindowVerdict("capacity_overflow",
                                 detail={"nlist": nlist_ovf,
                                         "special": sp_ovf})
        trip = flags.get("guard_trip")
        if trip is not None and self._host_read(bool, jnp.any(trip)):
            return WindowVerdict("guard_trip",
                                 trip_mask=self._host_read(np.asarray, trip))
        return WindowVerdict("ok")

    def _run_segment_scan(self, state, nlist, sp_state, k: int):
        """One fused window, dispatched through the ``WindowVerdict`` →
        ``RECOVERY_POLICY`` table: commit / grow-and-replay on capacity
        overflow / rollback-and-replay on a guard trip (escalating to an
        emergency dump when recovery is exhausted)."""
        tracer = self.tracer
        start = (state, nlist, sp_state)
        step0 = self._abs_step(state)
        committed = None   # first tripped window's results, for masking
        mask0 = None
        rollbacks = 0
        dt0 = self.config.dt
        try:
            while True:
                t0 = time.perf_counter()
                with tracer.span("md.window", phase="scan", steps=k):
                    (state, nlist, sp_state, flags, e_cl,
                     e_sp), recs = self._window_fn(k)(*start)
                    jax.block_until_ready(state.positions)
                self.timings["scan"] += time.perf_counter() - t0
                with tracer.span("md.verdict", phase="verdict"):
                    verdict = self._window_verdict(flags)
                    if verdict.policy == "commit":
                        # batched engines count per-trajectory triggers
                        # (replica-steps)
                        self.diagnostics["displacement_rebuilds"] += (
                            self._host_read(int, jnp.sum(flags["rebuilds"])))
                        self.diagnostics["special_rebuilds"] += (
                            self._host_read(int,
                                            jnp.sum(flags["sp_rebuilds"])))
                        tracer.record_window(step0, k, recs)
                if verdict.policy == "commit":
                    out = (state, nlist, sp_state, e_cl, e_sp)
                    if committed is not None:
                        # per-replica masking: untripped trajectories keep
                        # the originally committed window, only tripped
                        # ones take the replay
                        out = self._merge_rollback(committed, out, mask0)
                        tracer.registry.counter("guard.recoveries").inc()
                    return out
                self.diagnostics["window_reruns"] += 1
                if verdict.policy == "grow_replay":
                    state0, nlist0, sp_state0 = start
                    injected = self._consume_faults(step0, k,
                                                    kinds=("overflow_flag",))
                    if not injected:
                        # grow whichever capacity overflowed — correctness
                        # over throughput on the rare growth event
                        with tracer.span("md.rebuild", phase="neighbor",
                                         why="grow"):
                            if verdict.detail["nlist"]:
                                self._grow_neighbor_capacity()
                                nlist0 = self._build_nlist_grown(
                                    state0.positions)
                            if self._stateful and verdict.detail["special"]:
                                self.special_force.grow()
                                self.diagnostics["special_growths"] += 1
                                self._window_cache.clear()
                                sp_state0 = self._assemble_special_grown(
                                    state0.positions)
                    # injected flag: disarmed above, replay unchanged
                    start = (state0, nlist0, sp_state0)
                    continue
                # rollback_replay: a numerical guard tripped
                if committed is None:
                    committed = (state, nlist, sp_state, e_cl, e_sp)
                    mask0 = verdict.trip_mask
                start = self._guard_rollback(start, step0, k,
                                             verdict.trip_mask, rollbacks,
                                             dt0)
                rollbacks += 1
        finally:
            if self.config.dt != dt0:
                self._set_dt(dt0)

    def _run_segment_step(self, state, nlist, sp_state, k: int):
        """Per-step host loop wrapped in the same verdict → policy recovery
        as the scan path: guard trips roll back to the segment start and
        replay (capacity overflow is already handled inline per step).  A
        replayed segment re-records its step counters — the trace shows the
        replay, which is the point of tracing a chaos run."""
        start = (state, nlist, sp_state)
        step0 = self._abs_step(state)
        committed = None
        mask0 = None
        rollbacks = 0
        dt0 = self.config.dt
        try:
            while True:
                state, nlist, sp_state, e_cl, e_sp, trip = (
                    self._attempt_segment_step(*start, k))
                if trip is None or not self._host_read(bool, jnp.any(trip)):
                    out = (state, nlist, sp_state, e_cl, e_sp)
                    if committed is not None:
                        out = self._merge_rollback(committed, out, mask0)
                        self.tracer.registry.counter(
                            "guard.recoveries").inc()
                    return out
                self.diagnostics["window_reruns"] += 1
                trip = self._host_read(np.asarray, trip)
                if committed is None:
                    committed = (state, nlist, sp_state, e_cl, e_sp)
                    mask0 = trip
                start = self._guard_rollback(start, step0, k, trip,
                                             rollbacks, dt0)
                rollbacks += 1
        finally:
            if self.config.dt != dt0:
                self._set_dt(dt0)

    def _attempt_segment_step(self, state, nlist, sp_state, k: int):
        """One per-step segment attempt: the Fig.-9 stage timers split out,
        guard trips accumulated across all ``k`` steps (mirroring the scan
        window's OR-reduce — no early abort, so scan and step recovery see
        identical verdicts)."""
        cfg = self.config
        system = self.system
        special = self.special_force
        tracer = self.tracer
        want = tracer.wants_counters
        step0 = self._abs_step(state) if want else 0
        e_cl = e_sp = jnp.zeros(self._batch_shape)
        trip = None
        e_prev = (jnp.full(self._batch_shape, jnp.nan) if self._guard_on
                  else None)
        for j in range(k):
            rec = {"rebuild": 0, "sp_rebuild": 0} if want else {}
            t0 = time.perf_counter()
            with tracer.span("neighbor", phase="neighbor"):
                if self._host_read(bool, jnp.any(
                        self._check_rebuild(nlist, state.positions))):
                    nlist = self._build_nlist_grown(state.positions)
                    self.diagnostics["displacement_rebuilds"] += 1
                    if want:
                        rec["rebuild"] = 1
                jax.block_until_ready(nlist.idx)
            self.timings["neighbor"] += time.perf_counter() - t0

            t0 = time.perf_counter()
            with tracer.span("classical", phase="classical"):
                e_cl, f = self._classical_fn(state.positions, nlist)
                jax.block_until_ready(f)
            self.timings["classical"] += time.perf_counter() - t0

            if special is not None:
                t0 = time.perf_counter()
                with tracer.span("special", phase="inference"):
                    if self._stateful:
                        e_sp, f_sp, fl = special.evaluate(state.positions,
                                                          sp_state)
                        if self._host_read(bool,
                                           jnp.any(fl["needs_rebuild"])):
                            sp_state = self._assemble_special_grown(
                                state.positions)
                            self.diagnostics["special_rebuilds"] += 1
                            if want:
                                rec["sp_rebuild"] = 1
                            e_sp, f_sp, fl = special.evaluate(state.positions,
                                                              sp_state)
                        while self._host_read(bool, jnp.any(fl["overflow"])):
                            # evaluation-side overflow (e.g. k_eval trim):
                            # grow and recompute — mirrors the scan replay
                            special.grow()
                            self.diagnostics["special_growths"] += 1
                            self._window_cache.clear()
                            if self.diagnostics["special_growths"] > (
                                    cfg.max_capacity_growths):
                                self._emergency(
                                    "special-force capacity still exceeded "
                                    f"after {cfg.max_capacity_growths} "
                                    "doublings", state=state)
                            sp_state = self._assemble_special_grown(
                                state.positions)
                            e_sp, f_sp, fl = special.evaluate(state.positions,
                                                              sp_state)
                        if want:
                            rec.update(fl.get("counters", {}))
                    else:
                        e_sp, f_sp = self._eval_special_stateless(
                            state.positions, system.box)
                    f = f + f_sp
                    jax.block_until_ready(f)
                self.timings["special"] += time.perf_counter() - t0

            if self.faults is not None:
                # step-mode injection: nan faults only (overflow_flag needs
                # the scan window's flag plumbing)
                f, _ = self.faults.apply_engine(
                    state.step, f, jnp.zeros(self._batch_shape, bool))
            t0 = time.perf_counter()
            with tracer.span("integrate", phase="integrate"):
                prev = state
                state = self._integrate_fn(state, f)
                jax.block_until_ready(state.positions)
            self.timings["integrate"] += time.perf_counter() - t0
            if self._guard_on:
                t = step_guard_trip(self.guard, prev.positions, state,
                                    system.masses, system.box,
                                    e_cl + e_sp, e_prev)
                trip = t if trip is None else (trip | t)
                e_prev = e_cl + e_sp
            if want:
                tracer.record_step(step0 + j, rec)
        return state, nlist, sp_state, e_cl, e_sp, trip

    # -- guard recovery (rollback-and-replay, emergency dumps) -------------

    def _guard_rollback(self, start, step0: int, k: int, mask,
                        rollbacks: int, dt0: float):
        """Shared rollback bookkeeping for both loop modes: count the trips,
        disarm one-shot injected faults covering the window, choose the
        replay start (window start, or the last verified checkpoint when
        the start itself is tainted), and shrink dt from the second replay
        on.  Returns the replay's start tuple; escalates to an emergency
        dump once ``GuardConfig.max_rollbacks`` is exhausted."""
        n_trips = int(np.sum(mask))
        self.diagnostics["guard_trips"] += n_trips
        self._note_guard_trips(mask)
        self.tracer.registry.counter("guard.trips").inc(n_trips)
        if rollbacks >= self.guard.max_rollbacks:
            self._emergency(
                f"guard trips persist after {rollbacks} rollback replays "
                f"(window start step {step0}, length {k}, "
                f"trips={np.asarray(mask).tolist()})",
                state=start[0], raise_cls=GuardTripError)
        self.diagnostics["guard_rollbacks"] += 1
        # one-shot injected faults covering this window: fire them and
        # clear the window cache so the replay traces fault-free
        self._consume_faults(step0, k)
        start = self._rollback_start(start, step0)
        if rollbacks >= 1:
            # the first replay keeps the original dt (transient-fault
            # hypothesis — preserves the bitwise-replay contract for
            # injected faults); later replays shrink it (instability
            # hypothesis); _run_segment_* restores dt0 on exit
            self._set_dt(dt0 * self.guard.dt_shrink ** rollbacks)
        return start

    def _consume_faults(self, step0: int, k: int, kinds=None) -> list:
        """Fire injected MD-path faults in [step0, step0+k) and force the
        re-traces that make the replay fault-free."""
        if self.faults is None:
            return []
        fired = self.faults.consume_in_window(step0, step0 + k, kinds)
        if fired:
            self._window_cache.clear()
            if (any(s.rank is not None for s in fired)
                    and hasattr(self.special_force, "backend_build_fns")):
                # rank faults live in the provider's compiled drivers
                self.special_force.backend_build_fns()
        return fired

    def _rollback_start(self, start, step0: int):
        """The replay's start tuple: the window start when healthy, else
        the newest verified ``AsyncCheckpointer`` step caught up to
        ``step0``.  The catch-up re-integrates the committed trajectory
        bitwise: faults are already disarmed, and checkpoint boundaries
        are clean rebuild points (``run`` rebuilds the neighbor/special
        state right after saving), so the committed continuation and this
        fresh-built replay see identical inputs."""
        state0 = start[0]
        if self._state_healthy(state0):
            return start
        if self.checkpointer is None:
            self._emergency(
                "window-start state is non-finite and no checkpointer is "
                "attached — cannot roll back", state=state0,
                raise_cls=GuardTripError)
        tree, cstep = self.checkpointer.restore_latest(
            dataclasses.asdict(state0))
        if tree is None or cstep > step0:
            self._emergency(
                "window-start state is non-finite and no verified "
                f"checkpoint at or before step {step0} exists",
                state=state0, raise_cls=GuardTripError)
        self.diagnostics["checkpoint_restores"] += 1
        state0 = self._state_from_tree(tree)
        nlist0 = self._build_nlist_grown(state0.positions)
        sp_state0 = (self._assemble_special_grown(state0.positions)
                     if self._stateful else None)
        catchup = step0 - cstep
        if catchup:
            (state0, nlist0, sp_state0, _, _, _), _ = (
                self._window_fn(catchup)(state0, nlist0, sp_state0))
            jax.block_until_ready(state0.positions)
        return (state0, nlist0, sp_state0)

    def _state_healthy(self, state) -> bool:
        return all(bool(np.isfinite(self._host_read(np.asarray, x)).all())
                   for x in (state.positions, state.velocities))

    def _state_from_tree(self, tree) -> MDState:
        return MDState(**{key: jnp.asarray(v) for key, v in tree.items()})

    def _merge_rollback(self, committed, replayed, mask):
        """Leaf-wise select between the committed and replayed window
        results: tripped trajectories (mask True) take the replay,
        untripped keep the original — the ensemble's per-replica masking.
        A scalar engine's mask is ``()``, so the replay wins wholesale."""
        m = jnp.asarray(mask)

        def sel(old, new):
            mm = m.reshape(m.shape + (1,) * (jnp.ndim(new) - m.ndim))
            return jnp.where(mm, new, old)

        return jax.tree.map(sel, committed, replayed)

    def _note_guard_trips(self, mask) -> None:
        """Per-trajectory trip attribution hook (ensemble override)."""

    def _set_dt(self, dt: float) -> None:
        """Swap the integration timestep: the jitted step fns and cached
        windows close over dt at trace time, so both are rebuilt."""
        self.config.dt = float(dt)
        self._build_fns()
        self._window_cache.clear()

    def _emergency_root(self) -> Optional[str]:
        cfg = self.config
        if cfg.emergency_path:
            return cfg.emergency_path
        if self.checkpointer is not None:
            return os.path.join(self.checkpointer.root, "emergency")
        if cfg.checkpoint_path:
            return cfg.checkpoint_path + ".emergency"
        return None

    def _emergency(self, reason: str, state=None, raise_cls=RuntimeError):
        """Unrecoverable-verdict exit: dump an emergency checkpoint plus a
        diagnostics bundle (when a dump root is configured and a state is
        known), then raise with the dump path in the message."""
        state = state if state is not None else self._last_state
        root = self._emergency_root()
        path = None
        if root is not None and state is not None:
            try:
                step = self._abs_step(state)
            except (TypeError, ValueError):
                step = None
            bundle = {"reason": reason, "step": step,
                      "diagnostics": self.diagnostics,
                      "timings": self.timings,
                      "config": dataclasses.asdict(self.config),
                      "faults": (self.faults.summary()
                                 if self.faults is not None else None)}
            path = dump_emergency(root, dataclasses.asdict(state), bundle,
                                  step=step)
        self.diagnostics["emergency_dumps"].append(path or reason)
        if path is not None:
            reason = f"{reason} (emergency checkpoint: {path})"
        raise raise_cls(reason)

    def run(self, state: MDState, n_steps: int,
            observe: Optional[Callable[[MDState, dict], None]] = None,
            observe_every: int = 10) -> MDState:
        cfg = self.config
        tracer = self.tracer
        self._last_state = state
        # timings are per-run: repeated run() calls on one engine no longer
        # silently accumulate (diagnostics stay cumulative — see reset())
        self.timings = self._init_timings()
        scan_mode = cfg.loop_mode != "step" and not self._host_special
        tracer.meta(kind="run", engine=type(self).__name__,
                    loop_mode="scan" if scan_mode else "step",
                    n_steps=int(n_steps),
                    n_atoms=int(self.system.masses.shape[0]))
        tracer.begin_run()
        tracer.start_capture()
        nlist, sp_state = self._rebuild_lists(state.positions, "build")

        i = windows = 0
        while i < n_steps:
            if i > 0 and i % cfg.rebuild_every == 0:
                # cadence rebuild on the host (the redundant step-0 rebuild
                # right after the pre-loop build is skipped)
                nlist, sp_state = self._rebuild_lists(state.positions,
                                                      "cadence")
                self.diagnostics["cadence_rebuilds"] += 1

            k = self._segment_len(i, self._abs_step(state), n_steps,
                                  observe is not None, observe_every)
            if self.faults is not None and self.faults.sync_window(
                    self._abs_step(state), k):
                # rank-targeted faults changed armed state: force a
                # re-trace so the pipeline seam sees it
                self._window_cache.clear()
                if hasattr(self.special_force, "backend_build_fns"):
                    self.special_force.backend_build_fns()
            if cfg.loop_mode == "step" or self._host_special:
                state, nlist, sp_state, e_cl, e_sp = self._run_segment_step(
                    state, nlist, sp_state, k)
            else:
                state, nlist, sp_state, e_cl, e_sp = self._run_segment_scan(
                    state, nlist, sp_state, k)
            i += k
            windows += 1
            state = self._post_segment(state, e_cl, e_sp, i)
            self._last_state = state

            if observe is not None and (i - 1) % observe_every == 0:
                observe(state, self._observation(state, e_cl, e_sp))

            if (cfg.checkpoint_every
                    and self._abs_step(state) % cfg.checkpoint_every == 0):
                if self.checkpointer is not None:
                    self.checkpointer.save(dataclasses.asdict(state),
                                           self._abs_step(state))
                if cfg.checkpoint_path:
                    self.checkpoint(state, cfg.checkpoint_path)
                # a checkpoint boundary is a clean rebuild point: the
                # continuation depends only on the saved state (not on a
                # carried list whose reference positions predate it), so a
                # restart/rollback from this checkpoint replays the
                # committed continuation bitwise (see _rollback_start)
                nlist, sp_state = self._rebuild_lists(state.positions,
                                                      "checkpoint")
        tracer.stop_capture()
        self._publish_run(n_steps, windows)
        tracer.flush()  # no-op unless ObsConfig.trace_dir is set
        return state

    def _rebuild_lists(self, positions, why: str):
        """The host-driven classical list and special-force assembly, with
        their overflow checks, as one ``md.rebuild`` span tagged ``why``."""
        t0 = time.perf_counter()
        with self.tracer.span("md.rebuild", phase="neighbor", why=why):
            nlist = self._build_nlist_grown(positions)
            sp_state = (self._assemble_special_grown(positions)
                        if self._stateful else None)
        self.timings["neighbor"] += time.perf_counter() - t0
        return nlist, sp_state

    def _host_read(self, convert, value):
        """``convert(value)``: the one door for the run loop's blocking
        reads of device values, each counted into the tracer
        (``host_reads``) when tracing is on."""
        self.tracer.count("host_reads")
        return convert(value)

    def _publish_run(self, n_steps: int, windows: int) -> None:
        """This run's totals as registry gauges, overwritten by the next
        run: seconds per run-loop span (``md.run.span_s.<span>``), blocking
        host reads, windows and steps.  Nothing when tracing is off."""
        tracer = self.tracer
        if not tracer.enabled:
            return
        span_s, counts = tracer.run_totals()
        gauge = tracer.registry.gauge
        for name in RUN_SPANS:
            gauge(f"md.run.span_s.{name}").set(span_s.get(name, 0.0))
        gauge("md.run.host_reads").set(counts.get("host_reads", 0))
        gauge("md.run.windows").set(windows)
        gauge("md.run.steps").set(n_steps)

    # -- batched-engine hooks (overridden by repro.ensemble) ---------------

    def _abs_step(self, state) -> int:
        return self._host_read(int, state.step)

    def _post_segment(self, state, e_cl, e_sp, i: int):
        """Host boundary between fused windows (replica exchange hook)."""
        return state

    def _observation(self, state, e_cl, e_sp) -> dict:
        return {
            "step": self._abs_step(state),
            "e_classical": self._host_read(float, e_cl),
            "e_special": self._host_read(float, e_sp),
            "temperature": self._host_read(float, observables.temperature(
                state.velocities, self.system.masses)),
        }

    # -- fault tolerance ----------------------------------------------------

    def checkpoint(self, state: MDState, path: str) -> None:
        from ..ckpt.checkpoint import save_pytree
        save_pytree(path, dataclasses.asdict(state))

    @staticmethod
    def restore(path: str) -> MDState:
        from ..ckpt.checkpoint import load_pytree
        d = load_pytree(path)
        return MDState(**{k: jnp.asarray(v) for k, v in d.items()})
