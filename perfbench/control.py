#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's numbers and the
control's, seed by seed, in one process (set-up compiles once).

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed it runs the cell as ``run.py`` does (set-up, warm-up, a short
measured window) and compares the last step's forces with the reference:
the lower reading.  The control is the reference put in the program's place
one precision below what the configuration states, compared the same way:
the DP model with every matmul as three bfloat16 products (the "high"
setting, for float32 at "highest"), and the classical pair terms in
bfloat16 (for float32).  The benchmark's own runs never run it.  One JSON
line per seed, then the largest program reading and the smallest control
reading of each number.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness  # noqa: E402
from perfbench.check import compare, reference_forces  # noqa: E402


def control_numbers(cell, ref_mod, m: dict, c: dict) -> dict:
    """The control's numbers at the frame the program's run was checked at:
    DP numbers from the lowered DP reference (classical at full precision),
    classical numbers from the lowered classical reference."""
    spec, ref, nn = m["spec"], c["ref"], m["spec"]["nn_idx"]
    e_cl, e_dp = m["energies"]
    out = {}
    low = reference_forces(ref_mod, m["params"], cell.config, spec,
                           c["x_prev"], dp_precision="bf16_3x")
    f = ref["f_cl"].copy()
    f[nn] += low["f_dp"]
    out.update({k: v for k, v in compare(f, low["e_dp"], ref["e_cl"], ref,
                                         nn, c["skip"]).items()
                if k.startswith("dp_")})
    if len(nn) < len(spec["types"]):
        low = reference_forces(ref_mod, m["params"], cell.config, spec,
                               c["x_prev"], cl_precision="bfloat16")
        f = low["f_cl"].copy()
        f[nn] += ref["f_dp"]
        out.update({k: v for k, v in compare(f, ref["e_dp"], low["e_cl"],
                                             ref, nn, c["skip"]).items()
                    if k.startswith("cl_")})
    return out


def readings(name: str, seeds, seconds: float, *, bench_dir=harness.BENCH,
             benchmark=None, require_tpu: bool = True) -> dict:
    cell, devices, _, ref_mod = harness.prepare(name, bench_dir, benchmark,
                                                require_tpu)
    lows, highs = {}, {}
    for seed in seeds:
        m = harness.measure(cell, devices, ref_mod, seed, seconds, False,
                            time.perf_counter())
        c = harness.check(cell, ref_mod, m)
        ctrl = control_numbers(cell, ref_mod, m, c)
        print(json.dumps({"seed": seed, "program": c["numbers"],
                          "control": ctrl,
                          "unresolved": c["unresolved"],
                          "skipped": int(c["skip"].sum()),
                          "counts": m["counts"], "steps": m["n_steps"]}),
              flush=True)
        for k, v in c["numbers"].items():
            lows[k] = max(lows.get(k, -np.inf), v)
        for k, v in ctrl.items():
            highs[k] = min(highs.get(k, np.inf), v)
    return {"program_max": lows, "control_min": highs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    print(json.dumps(readings(args.workload, seeds, args.seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
