"""Replica-ensemble MD engine: R trajectories as one jitted program.

The paper's strong-scaling ceiling (40% efficiency at 32 devices, Sec. VI)
means that past ~16 ranks extra hardware buys more from *more trajectories*
than from more ranks per trajectory.  ``EnsembleEngine`` makes replica
count that first-class scaling dimension: a :class:`ReplicaState` batches R
independent replicas of one system over a leading axis, the classical
force path and the integrator are vmapped, the Deep-Potential special
force runs through :class:`repro.ensemble.BatchedDeepmdProvider` (vmapped
single-domain, or the 2-D replica x dd mesh drivers in
``repro.core.ddinfer``), and an optional temperature-ladder
replica-exchange move (``repro.ensemble.exchange``) turns the ensemble
into REMD.

The host-side window machinery — fused ``lax.scan`` segments,
displacement-triggered rebuild conds, capacity grow-and-replay,
observe/checkpoint cadence — is *inherited* from ``repro.md.MDEngine``,
not forked: per-trajectory flags are shaped (R,) (``_batch_shape``), the
shared code reduces them with any()/sum() for host decisions, and rebuild
conds fire when *any* replica trips.  Executing a rebuild for all replicas
when one trips is exact, not approximate: both the classical force field
(cutoff re-filter at evaluation) and the DP evaluation phase (canonical
within-cutoff compaction) are bitwise-independent of list staleness inside
the skin bound, so a batched run with exchange disabled reproduces R
independent ``MDEngine`` runs trajectory-for-trajectory (same per-replica
seeds and temperatures).

Replica exchange happens at window boundaries (``exchange_interval`` is an
extra host-boundary cadence): the Metropolis criterion uses the potential
energies from the window's final force evaluation — i.e. the energies at
the positions *entering* the last step, the standard cheap-REMD compromise
that avoids a dedicated energy pass.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..md import observables
from ..md.engine import EngineConfig, ForceProvider, MDEngine
from ..md.neighbors import needs_rebuild
from ..md.system import System
from .exchange import make_exchange_fn
from .state import ReplicaState, stack_states


@dataclasses.dataclass
class EnsembleConfig:
    """Replica-ensemble knobs, orthogonal to :class:`EngineConfig`."""

    n_replicas: int
    temps: Optional[tuple] = None      # temperature ladder (len R, ascending);
    #   None = every replica at EngineConfig.thermostat_t
    exchange_interval: int = 0         # steps between exchange attempts; 0=off
    seeds: Optional[tuple] = None      # per-replica velocity seeds (default
    #   0..R-1); also seed the exchange PRNG streams


class EnsembleEngine(MDEngine):
    """R-replica batched MD with optional replica exchange.

    Usage mirrors ``MDEngine``::

        ens = EnsembleConfig(n_replicas=4, temps=(300, 330, 365, 400),
                             exchange_interval=20)
        eng = EnsembleEngine(system, EngineConfig(...), ens,
                             special_force=BatchedDeepmdProvider(...))
        state = eng.run(eng.init_state(positions), n_steps)

    Exchange statistics land in ``diagnostics`` (``exchange_attempts`` /
    ``exchange_accepts`` plus per-rung-pair vectors).
    """

    def __init__(self, system: System, config: EngineConfig,
                 ens: EnsembleConfig,
                 special_force: Optional[ForceProvider] = None,
                 obs=None, guard=None, faults=None, checkpointer=None):
        r = ens.n_replicas
        if r < 1:
            raise ValueError("n_replicas must be >= 1")
        if ens.temps is not None and len(ens.temps) != r:
            raise ValueError(f"temps has {len(ens.temps)} entries for "
                             f"{r} replicas")
        if ens.temps is None and ens.exchange_interval:
            if config.thermostat_t is None:
                raise ValueError("replica exchange needs a temperature "
                                 "ladder (EnsembleConfig.temps) or a "
                                 "thermostat target")
        self.ens = ens
        self._thermostat = (ens.temps is not None
                            or config.thermostat_t is not None)
        base_t = config.thermostat_t if config.thermostat_t is not None \
            else 300.0
        self._temp_table = jnp.asarray(
            ens.temps if ens.temps is not None else (base_t,) * r,
            jnp.float32)
        self._batch_shape = (r,)
        self._extra_boundary_every = ens.exchange_interval
        super().__init__(system, config, special_force, obs=obs,
                         guard=guard, faults=faults, checkpointer=checkpointer)
        self._exchange_fn = make_exchange_fn(self._temp_table)

    def _init_diagnostics(self) -> dict:
        # called from MDEngine.__init__ and reset(); self.ens is set first
        r = self.ens.n_replicas
        d = super()._init_diagnostics()
        d.update({
            "exchange_attempts": 0, "exchange_accepts": 0,
            "pair_attempts": np.zeros(max(r - 1, 0), np.int64),
            "pair_accepts": np.zeros(max(r - 1, 0), np.int64),
            # per-replica guard-trip attribution (recovery is masked per
            # replica: untripped replicas keep the committed window)
            "replica_guard_trips": np.zeros(r, np.int64),
        })
        return d

    # -- vmapped construction ----------------------------------------------

    def _build_fns(self):
        def integrate_fn(state: ReplicaState, f):
            if not self._thermostat:
                return jax.vmap(
                    lambda s, f1: self._integrate_one(s, f1, None))(state, f)
            # each replica thermostats toward its current ladder rung
            return jax.vmap(self._integrate_one)(
                state, f, self._temp_table[state.ladder])

        self._classical_fn = jax.jit(jax.vmap(self._classical_one))
        self._nlist_fn = jax.jit(
            jax.vmap(self._classical_neighbor_list, in_axes=(0, None, None)),
            static_argnums=(1, 2))
        self._integrate_fn = jax.jit(integrate_fn)

    def _check_rebuild(self, nlist, positions):
        cfg = self.config
        return jax.vmap(lambda nl, p: needs_rebuild(
            nl, p, self.system.box, cfg.skin))(nlist, positions)

    # -- lifecycle ---------------------------------------------------------

    def init_state(self, positions, seeds: Optional[Sequence[int]] = None
                   ) -> ReplicaState:
        """Batched init: per-replica Maxwell-Boltzmann draws at the ladder
        temperatures, from per-replica seeds — replica r's state is exactly
        ``MDEngine.init_state(positions[r], temps[r], seed=seeds[r])``."""
        r = self.ens.n_replicas
        if seeds is None:
            seeds = self.ens.seeds if self.ens.seeds is not None else range(r)
        if not isinstance(seeds, (list, tuple, range, np.ndarray)):
            raise TypeError(
                "EnsembleEngine.init_state takes per-replica `seeds` (a "
                "sequence), not MDEngine's scalar temperature/seed — "
                "replica temperatures come from EnsembleConfig.temps")
        seeds = list(seeds)
        if len(seeds) != r:
            raise ValueError(f"{len(seeds)} seeds for {r} replicas")
        positions = jnp.asarray(positions)
        if positions.ndim == 2:
            positions = jnp.broadcast_to(positions,
                                         (r,) + positions.shape)
        states = [MDEngine.init_state(self, positions[k],
                                      float(self._temp_table[k]),
                                      seed=int(seeds[k]))
                  for k in range(r)]
        return stack_states(states)

    # -- batched-engine hooks ----------------------------------------------

    def _abs_step(self, state) -> int:
        return self._host_read(int, state.step[0])

    def _post_segment(self, state, e_cl, e_sp, i: int):
        ex = self.ens.exchange_interval
        if not ex or i % ex != 0 or self.ens.n_replicas < 2:
            return state
        energies = jnp.asarray(e_cl) + jnp.asarray(e_sp)
        # parity derives from the *absolute* step, so it is part of the
        # checkpointed state (not hidden engine state): a restored run
        # continues the same alternating rung-pair schedule as an
        # uninterrupted one whenever checkpoints land on exchange
        # boundaries (checkpoint_every a multiple of exchange_interval)
        parity = (self._abs_step(state) // ex) % 2
        state, stats = self._exchange_fn(state, energies, jnp.int32(parity))
        d = self.diagnostics
        d["exchange_attempts"] += int(stats["attempted"])
        d["exchange_accepts"] += int(stats["accepted"])
        d["pair_attempts"] = d["pair_attempts"] + np.asarray(
            stats["pair_attempts"], np.int64)
        d["pair_accepts"] = d["pair_accepts"] + np.asarray(
            stats["pair_accepts"], np.int64)
        return state

    def _observation(self, state: ReplicaState, e_cl, e_sp) -> dict:
        temps = jax.vmap(observables.temperature, in_axes=(0, None))(
            state.velocities, self.system.masses)
        return {
            "step": self._abs_step(state),
            "e_classical": np.asarray(e_cl),
            "e_special": np.asarray(e_sp),
            "temperature": np.asarray(temps),
            "ladder": np.asarray(state.ladder),
            "target_t": np.asarray(self._temp_table)[
                np.asarray(state.ladder)],
        }

    # -- fault tolerance ---------------------------------------------------

    def _note_guard_trips(self, mask) -> None:
        self.diagnostics["replica_guard_trips"] += np.asarray(mask,
                                                              np.int64)

    def _state_from_tree(self, tree) -> ReplicaState:
        return ReplicaState(**{k: jnp.asarray(v) for k, v in tree.items()})

    @staticmethod
    def restore(path: str) -> ReplicaState:
        from ..ckpt.checkpoint import load_pytree
        d = load_pytree(path)
        return ReplicaState(**{k: jnp.asarray(v) for k, v in d.items()})
