"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus writes JSON artifacts under
experiments/bench/ for EXPERIMENTS.md).
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from . import (comms_overlap, dd_reuse, dd_scaling, dp_inference,
                   ensemble_throughput, fig7_training, fig8_validation,
                   fig9_overhead, fig10_strong_scaling, fig11_weak_scaling,
                   roofline_bench, serve_throughput)
    modules = [
        ("dd_scaling", dd_scaling),
        ("dd_reuse", dd_reuse),
        ("comms_overlap", comms_overlap),
        ("dp_inference", dp_inference),
        ("ensemble_throughput", ensemble_throughput),
        ("serve_throughput", serve_throughput),
        ("fig10_strong_scaling", fig10_strong_scaling),
        ("fig11_weak_scaling", fig11_weak_scaling),
        ("fig9_overhead", fig9_overhead),
        ("fig8_validation", fig8_validation),
        ("fig7_training", fig7_training),
        ("roofline_bench", roofline_bench),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.run()
            for row in rows:
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},NaN,FAILED {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.runtime import use_cpu_devices
    use_cpu_devices(8)   # the multi-rank modules; only the CPU backend reads it
    main()
