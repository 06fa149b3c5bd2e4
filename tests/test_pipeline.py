"""ForcePipeline acceptance suite (PR 8 tentpole):

* parity matrix — {dense, cells} neighbor builds x {fused driver vs
  assembly+evaluation split} x {unbatched dd-8, (2 x 4) replica-batched}:
  the split is bitwise-equal to the fused driver everywhere and both match
  the single-domain oracle to fp tolerance;
* comms-overlap evaluation — with ``DDConfig.overlap`` the interior pass
  runs against the all-gather yet the merged energy/forces stay
  bitwise-equal to the sequential evaluation at the build positions AND at
  drifted (stale-state reuse) positions; a trimmed ``overlap_capacity``
  degrades gracefully to ulp-level and reports overflow through the normal
  grow-and-retry protocol;
* ``DDConfig.__post_init__`` rejects broken geometries/capacities at
  construction time with actionable messages (in-process, no devices);
* the legacy ``make_*_fn`` factories are warn-once deprecation shims that
  delegate to ForcePipeline builders, and model-needing builders refuse a
  check-only (``model=None``) pipeline.

Multi-device blocks run in a subprocess (forced host devices); the config
validation and shim tests run in-process."""
import dataclasses
import json
import warnings

import numpy as np
import pytest

from parity_support import SYSTEM_PRELUDE, run_json

_MATRIX_CODE = SYSTEM_PRELUDE + r"""
from repro.core import ForcePipeline, single_domain_forces, suggest_config
from repro.ensemble import make_ensemble_mesh
from repro.launch.mesh import make_dd_mesh

R = 2
coordsR = jnp.asarray(rng.uniform(0, L, (R, n, 3)).astype(np.float32))
e_sd, f_sd = single_domain_forces(model, params, coords, types, box, 64)
sdR = [single_domain_forces(model, params, coordsR[r], types, box, 64)
       for r in range(R)]

for method in ["dense", "cells"]:
    # unbatched dd-8: fused driver vs assembly+evaluation split
    cfg8 = suggest_config(n, box, 8, 0.6, nbr_capacity=64, slack=2.5,
                          nbr_method=method, coords=ch)
    pipe = ForcePipeline(model, cfg8, make_dd_mesh(8), box, n)
    e0, f0, d0 = pipe.build_force_fn()(params, coords, types)
    st = pipe.build_assembly_fn()(coords, types)
    e1, f1, d1 = pipe.build_evaluation_fn()(params, coords, st)
    out[method] = {
        "overflow": int(np.asarray(d0["overflow"])),
        "split_bitwise": bitwise(f0, f1) and float(e0) == float(e1),
        "df_single": float(jnp.abs(f0 - f_sd).max()),
    }
    # (replica=2, dd=4) batched: same split-vs-fused contract per replica
    cfg4 = suggest_config(n, box, 4, 0.6, nbr_capacity=64, slack=2.5,
                          nbr_method=method, coords=np.asarray(coordsR[0]))
    bpipe = ForcePipeline(model, cfg4, make_ensemble_mesh(2, 4), box, n,
                          n_replicas=R)
    eb0, fb0, db0 = bpipe.build_force_fn()(params, coordsR, types)
    stb = bpipe.build_assembly_fn()(coordsR, types)
    eb1, fb1, _ = bpipe.build_evaluation_fn()(params, coordsR, stb)
    out[method]["batched_overflow"] = np.asarray(db0["overflow"]).tolist()
    out[method]["batched_split_bitwise"] = (
        bitwise(fb0, fb1) and bitwise(eb0, eb1))
    out[method]["batched_df_single"] = [
        float(jnp.abs(fb0[r] - sdR[r][1]).max()) for r in range(R)]
print("JSON" + json.dumps(out))
"""

_OVERLAP_CODE = SYSTEM_PRELUDE + r"""
from repro.core import ForcePipeline, suggest_config
from repro.launch.mesh import make_dd_mesh

SKIN = 0.05
mesh = make_dd_mesh(8)
cfg = suggest_config(n, box, 8, 0.6, nbr_capacity=64, slack=2.5, skin=SKIN,
                     coords=ch)
pipe = ForcePipeline(model, cfg, mesh, box, n)
asm = pipe.build_assembly_fn()
ev = pipe.build_evaluation_fn()
cfg_ov = dataclasses.replace(cfg, overlap=True)
ev_ov = ForcePipeline(model, cfg_ov, mesh, box, n).build_evaluation_fn()

st = asm(coords, types)
e0, f0, d0 = ev(params, coords, st)
e1, f1, d1 = ev_ov(params, coords, st)
out["overflow"] = int(np.asarray(d1["overflow"]))
out["build_bitwise"] = bitwise(f0, f1) and float(e0) == float(e1)
out["interior_frac"] = float(np.asarray(d1["interior_frac"]))

# stale-state reuse at drifted positions (the steady-state MD hot path)
c1 = frozen_drift(halo_eff=cfg.halo_eff)
e2, f2, _ = ev(params, c1, st)
e3, f3, _ = ev_ov(params, c1, st)
out["drift_bitwise"] = bitwise(f2, f3) and float(e2) == float(e3)

# trimmed pass-B sub-buffer: ulp-level agreement, no overflow while the
# boundary shell fits; a too-small capacity trips the overflow protocol
C = cfg.local_capacity + cfg.ghost_capacity
ev_tr = ForcePipeline(model, dataclasses.replace(cfg_ov,
                      overlap_capacity=C - 8), mesh, box,
                      n).build_evaluation_fn()
e4, f4, d4 = ev_tr(params, coords, st)
out["trim_overflow"] = int(np.asarray(d4["overflow"]))
out["trim_df"] = float(jnp.abs(f4 - f0).max())
out["trim_de"] = abs(float(e4 - e0)) / abs(float(e0))
ev_tiny = ForcePipeline(model, dataclasses.replace(cfg_ov,
                        overlap_capacity=8), mesh, box,
                        n).build_evaluation_fn()
_, _, d5 = ev_tiny(params, coords, st)
out["tiny_overflow"] = int(np.asarray(d5["overflow"]))

print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def matrix_results():
    return run_json(_MATRIX_CODE, n_devices=8, timeout=560)


@pytest.fixture(scope="module")
def overlap_results():
    return run_json(_OVERLAP_CODE, n_devices=8, timeout=560)


@pytest.mark.parametrize("method", ["dense", "cells"])
def test_split_bitwise_equals_fused(matrix_results, method):
    """assembly+evaluation == the fused per-step driver, bitwise, for both
    neighbor builds, unbatched and replica-batched."""
    r = matrix_results[method]
    assert r["overflow"] == 0
    assert r["split_bitwise"]
    assert r["batched_overflow"] == [0, 0]
    assert r["batched_split_bitwise"]


@pytest.mark.parametrize("method", ["dense", "cells"])
def test_matrix_matches_single_domain(matrix_results, method):
    r = matrix_results[method]
    assert r["df_single"] < 1e-4, r
    assert all(df < 1e-4 for df in r["batched_df_single"]), r


def test_overlap_bitwise_at_build_positions(overlap_results):
    """Overlapped evaluation == sequential evaluation, bitwise in energy
    and forces, at the positions the state was built from."""
    r = overlap_results
    assert r["overflow"] == 0
    assert r["build_bitwise"]


def test_overlap_bitwise_at_drifted_positions(overlap_results):
    """Same bitwise contract under stale-state reuse — the per-step hot
    path the overlap exists for."""
    assert overlap_results["drift_bitwise"]


def test_overlap_interior_fraction_reported(overlap_results):
    f = overlap_results["interior_frac"]
    assert 0.0 < f < 1.0


def test_overlap_trimmed_capacity_protocol(overlap_results):
    """A trimmed ``overlap_capacity`` stays ulp-close while the boundary
    shell fits and reports overflow (grow-and-retry) when it does not."""
    r = overlap_results
    assert r["trim_overflow"] == 0
    assert r["trim_df"] < 1e-5, r
    assert r["trim_de"] < 1e-5, r
    assert r["tiny_overflow"] > 0


# -- in-process: config validation + deprecation shims -----------------------

def _base_cfg():
    from repro.core import suggest_config
    return suggest_config(160, np.array([3.5] * 3, np.float32), 8, 0.6,
                          nbr_capacity=64, slack=2.5)


@pytest.mark.parametrize("changes,match", [
    (dict(grid_dims=(0, 2, 2)), "three positive factors"),
    (dict(grid_dims=(2, 4)), "three positive factors"),
    (dict(local_capacity=0), "capacities must be positive"),
    (dict(ghost_capacity=-3), "capacities must be positive"),
    (dict(skin=-0.01), "skin must be >= 0"),
    (dict(nbr_capacity_eval=128), "cannot widen it"),
    (dict(nbr_capacity=256, nbr_capacity_eval=200, use_pallas=True),
     "128 lanes"),
    (dict(overlap=True, force_mode="ghost_reduce"),
     "requires force_mode='owner_full'"),
    (dict(overlap_capacity=-1), "must be >= 0"),
    (dict(overlap_min_interior=1.5), r"in \[0, 1\]"),
])
def test_ddconfig_rejects_invalid(changes, match):
    """Config-time validation: broken geometries/capacities fail loudly at
    construction instead of as silent trim/overflow inside a jitted
    driver (PR 8 satellite)."""
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(_base_cfg(), **changes)


def test_ddconfig_accepts_valid_edits():
    cfg = dataclasses.replace(_base_cfg(), skin=0.05, overlap=True)
    assert cfg.overlap and cfg.skin == 0.05


def _one_rank_setup():
    from repro.dp import DPModel, paper_dpa1_config
    from repro.core import suggest_config
    from repro.launch.mesh import make_dd_mesh
    model = DPModel(paper_dpa1_config(ntypes=2, rcut=0.6, sel=16))
    box = np.array([3.5] * 3, np.float32)
    cfg = suggest_config(32, box, 1, 0.6, nbr_capacity=32, slack=2.5)
    return model, cfg, make_dd_mesh(1), box


def test_legacy_factories_are_warn_once_shims():
    """The old ``make_*_fn`` entry points still work but emit ONE
    DeprecationWarning each, naming the ForcePipeline replacement."""
    from repro.core import ddinfer, make_assembly_fn
    model, cfg, mesh, box = _one_rank_setup()
    ddinfer._DEPRECATION_WARNED.discard("make_assembly_fn")
    with pytest.warns(DeprecationWarning, match="ForcePipeline"):
        assert callable(make_assembly_fn(model, cfg, mesh, box, 32))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must stay silent
        assert callable(make_assembly_fn(model, cfg, mesh, box, 32))


def test_check_only_pipeline_refuses_model_builders():
    """``ForcePipeline(model=None, ...)`` supports the displacement check
    but refuses the builders that need DP inference."""
    from repro.core import ForcePipeline
    _, cfg, mesh, box = _one_rank_setup()
    pipe = ForcePipeline(None, cfg, mesh, box, 32)
    assert callable(pipe.build_check_fn())
    with pytest.raises(ValueError, match="model=None"):
        pipe.build_force_fn()
