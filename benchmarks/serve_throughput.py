"""Force-serving throughput: requests/s vs concurrent clients.

The paper's profiling makes DP inference >90% of MD wall time, which turns
the force evaluator into a shared service problem: N independent client
simulations each dispatching their own per-step inference leave the
evaluator idle between calls and pay N sharded dispatches (N all-gathers +
N reductions) where one would do.  This benchmark stands the
:mod:`repro.serve` ForceServer on the distributed drivers (8 forced host
devices, same harness as ``ensemble_throughput``) and measures what
continuous batching buys over the pre-serving baseline:

  looped    every client dispatches its own requests one at a time through
            the unbatched dd-8 pipeline (``ForcePipeline.build_force_fn``) —
            what N simulations get without a batching queue: each request
            occupies the whole device set, clients time-slice it (their
            dispatches MUST serialize — see the rendezvous note below)
  batched   N concurrent client threads submitting to the ForceServer,
            whose pluggable executor routes a coalesced batch of B
            requests through ONE replica-batched pipeline dispatch on a
            (replica=B, dd=8/B) mesh: the batch partitions the device set,
            each request runs on fewer dd ranks (less Eq.-8 ghost work)
            and the whole group pays one rendezvous instead of B

Writes ``BENCH_serve_throughput.json`` with per-client-count rps and
speedups; the acceptance figure is ``speedup_c4`` (continuous batching vs
looped at 4 concurrent clients) > 1.

Usage:
  python -m benchmarks.serve_throughput           # full (2048 atoms, C<=8)
  python -m benchmarks.serve_throughput --smoke   # tiny point (CI)
"""
from __future__ import annotations

import sys
import threading
import time

import numpy as np

from .common import save_json

DENSITY = 3.7
RCUT = 0.6
N_DEV = 8
CLIENTS = (1, 2, 4, 8)


def run(smoke: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.backend import ForceRequest
    from repro.core import ForcePipeline, suggest_config
    from repro.dp.descriptors import DescriptorConfig
    from repro.dp.model import DPConfig, DPModel
    from repro.ensemble import make_ensemble_mesh
    from repro.launch.mesh import make_dd_mesh
    from repro.launch.runtime import require_devices
    from repro.serve import (ForceServer, ServeConfig,
                             pipeline_executor_factory)

    require_devices(N_DEV, "benchmarks.serve_throughput")

    n = 512 if smoke else 2048
    clients = (1, 4) if smoke else CLIENTS
    # power-of-two batch buckets so every bucket B tiles the device set as
    # a (B, N_DEV/B) mesh
    buckets = (1, 2, 4) if smoke else (1, 2, 4, 8)
    n_req = 3 if smoke else 8
    boxl = float((n / DENSITY) ** (1.0 / 3.0))
    box = np.array([boxl] * 3, np.float32)
    rng = np.random.default_rng(0)
    types = rng.integers(0, 4, n).astype(np.int32)
    types_j = jnp.asarray(types)

    model = DPModel(DPConfig(
        descriptor=DescriptorConfig(kind="dpse", rcut=RCUT,
                                    rcut_smth=RCUT - 0.3, sel=48, ntypes=4,
                                    neuron=(8, 16), axis_neuron=4),
        fitting_neuron=(32, 32)))
    params = model.init_params(jax.random.PRNGKey(0))

    coords_probe = rng.uniform(0, boxl, (n, 3))

    def cfg_for(nb, p):
        assert nb == n, (nb, n)
        return suggest_config(n, box, p, RCUT, nbr_capacity=48, slack=2.0,
                              nbr_method="cells", coords=coords_probe)

    fused8 = ForcePipeline(model, cfg_for(n, N_DEV), make_dd_mesh(N_DEV),
                           box, n).build_force_fn()

    # the server's pluggable executor: each (atoms x batch) bucket is a
    # replica-batched ForcePipeline dispatch on a (B, N_DEV/B) mesh — the
    # batch partitions the device set, so each request decomposes over
    # fewer dd ranks (less Eq.-8 ghost work per request) and B requests
    # pay one collective rendezvous instead of B.  All tenants share this
    # system's box/types (the ensemble-farm scenario).
    executor_factory = pipeline_executor_factory(
        model, box, types, cfg_for,
        mesh_for=lambda b: make_ensemble_mesh(b, N_DEV // b))

    # a short straggler window: per-request service time is O(100ms) here,
    # so waiting a few ms coalesces the lockstep clients into full batches
    server = ForceServer(model, params, ServeConfig(
        atom_buckets=(n,), batch_buckets=buckets, nbr_capacity=48,
        batch_window_s=0.01, queue_bound=256),
        executor_factory=executor_factory)

    def make_req(tenant):
        return ForceRequest(
            positions=rng.uniform(0, boxl, (n, 3)).astype(np.float32),
            box=box, types=types, tenant=tenant)

    rows, points = [], []
    try:
        # a timed configuration that overflows its static capacities would
        # silently truncate neighbor/ghost sets — refuse to record it
        overflow = int(np.asarray(
            fused8(params, jnp.asarray(make_req("probe").positions),
                   types_j)[2]["overflow"]).max())
        assert overflow == 0, "dd-8 capacity overflow"
        server.warmup(n_atoms=n)  # compile every batch bucket up front

        for c in clients:
            total = c * n_req

            # looped baseline: each client dispatches its own requests.
            # Dispatches must serialize: concurrent shard_map dispatches
            # from independent threads interleave their all-gather
            # participants across distinct rendezvous and deadlock the CPU
            # collective runtime — uncoordinated clients cannot even share
            # the device set safely, which is half the case for the server
            # (whose single worker thread serializes every dispatch).
            dispatch_lock = threading.Lock()

            def looped_client(reqs):
                for r in reqs:
                    with dispatch_lock:
                        jax.block_until_ready(
                            fused8(params, jnp.asarray(r.positions), types_j))

            looped_reqs = [[make_req("looped") for _ in range(n_req)]
                           for _ in range(c)]
            threads = [threading.Thread(target=looped_client, args=(rs,))
                       for rs in looped_reqs]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            t_loop = time.perf_counter() - t0

            # continuous batching: c lockstep client threads -> one server
            errs = []

            def client(tenant):
                for _ in range(n_req):
                    res = server.compute(make_req(tenant))
                    if not res.ok or res.diagnostics.get("overflow"):
                        errs.append(res.error or "overflow")

            threads = [threading.Thread(target=client, args=(f"c{c}-{i}",))
                       for i in range(c)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            t_batch = time.perf_counter() - t0
            assert not errs, f"batched errors at C={c}: {errs[:3]}"
            totals = server.metrics.totals()
            assert totals["errors"] == 0 and totals["timeouts"] == 0, totals

            point = {
                "clients": c, "requests": total,
                "looped_rps": total / t_loop,
                "batched_rps": total / t_batch,
                "speedup": t_loop / t_batch,
                "overflow": 0,
            }
            points.append(point)
            rows.append((f"serve_c{c}_looped", t_loop / total * 1e6,
                         f"{point['looped_rps']:.1f}rps"))
            rows.append((f"serve_c{c}_batched", t_batch / total * 1e6,
                         f"x{point['speedup']:.2f}"))
    finally:
        server.stop()

    at4 = [p for p in points if p["clients"] == 4]
    payload = {
        "n_atoms": n, "n_devices": N_DEV, "rcut": RCUT, "density": DENSITY,
        "requests_per_client": n_req,
        "model": "dpse(8,16)x(32,32)",
        "executor": "pipeline_executor_factory (replica=B, dd=8/B)",
        "batch_window_ms": 10.0, "batch_buckets": list(buckets),
        "points": points,
        "speedup_c4": at4[0]["speedup"] if at4 else None,
    }
    save_json("BENCH_serve_throughput", payload)
    return rows


if __name__ == "__main__":
    from repro.launch.runtime import use_cpu_devices
    use_cpu_devices(N_DEV)
    for name, us, derived in run(smoke="--smoke" in sys.argv[1:]):
        print(f"{name},{us:.1f},{derived}")
