"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import numpy as np

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench")


def save_json(name: str, payload) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1)


def time_fn(fn: Callable, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time (us) of fn() with warmup."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))
