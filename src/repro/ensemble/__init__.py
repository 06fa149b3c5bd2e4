"""Ensemble subsystem: batched multi-replica MD with replica exchange.

Replica count as a first-class scaling dimension alongside domain count —
R replicas of one system run as a single jitted program over a 2-D
(replica x dd) device mesh, with a jit-safe temperature-ladder exchange
move opening REMD-style enhanced-sampling workloads.
"""
from .engine import EnsembleConfig, EnsembleEngine  # noqa: F401
from .exchange import geometric_ladder, make_exchange_fn  # noqa: F401
from .provider import BatchedDeepmdProvider  # noqa: F401
from .state import ReplicaState, replica_state, stack_states  # noqa: F401


def make_ensemble_mesh(n_replica_shards: int, n_dd: int,
                       replica_axis: str = "replica"):
    """2-D (replica x dd) mesh: replicas shard over the leading axis, the
    virtual decomposition runs over the trailing ``dd`` axis within each
    replica group.  ``(1, n_dd)`` batches all replicas onto every device
    group (pure vmap batching, one fused collective pair per step)."""
    from .. import compat
    from ..launch.runtime import require_devices
    n = n_replica_shards * n_dd
    devices = require_devices(n, f"make_ensemble_mesh({n_replica_shards}, "
                                 f"{n_dd})")
    return compat.make_mesh((n_replica_shards, n_dd), (replica_axis, "dd"),
                            devices=devices)
