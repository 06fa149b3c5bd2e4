"""From a profiler trace of the measured window to layer metrics.

Two steps, kept apart so that the second can be checked on a recorded trace:

* :func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote and returns
  plain events: the device's operations (name, start, duration, the module
  and name stack they belong to) and the host's annotated spans.
* :func:`reduce_events` turns those events into busy and idle time, device
  time per name-stack scope, per kernel, and the longest idle gaps labelled
  with what the host was doing.

Times are in nanoseconds on the trace's own clock; results in seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

# control-flow operations span their bodies; their own time is not work
_CONTAINERS = re.compile(r"^(while|conditional|call|tuple)([.\d]|$)")
# the host span the harness puts around the measured call
WINDOW_SPAN = "perfbench.window"


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """Instruction name -> the name stack (``op_name`` metadata) in a
    compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def extract(trace_dir: str, stacks: dict | None = None) -> dict:
    """Device op events and host spans from the newest trace in the dir.

    Each op is [instruction name, start, duration, scope]: the scope is the
    name of the module (program) the op ran in, then ``/`` and the op's name
    stack where ``stacks`` maps that module's name to :func:`op_names` of
    its text (the device trace itself carries no name stack)."""
    from jax.profiler import ProfileData
    stacks = stacks or {}
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {"devices": {}, "host": []}
    prof = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in prof.planes:
        if _device_plane(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0])
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines.get("XLA Ops", []):
                name = ev.name.split(" = ")[0].lstrip("%")
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] \
                    else ""
                stack = stacks.get(mod, {}).get(name, "")
                ops.append([name, float(ev.start_ns), float(ev.duration_ns),
                            f"{mod}/{stack}"])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for ev in line.events:
                    if not ev.name.startswith("$"):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": host}


def union_length(intervals) -> tuple[float, list]:
    """Total length of a union of [start, end) intervals, and the merged
    intervals in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _base(name: str) -> str:
    return re.sub(r"[.\d]+$", "", name) or name


def _host_activity(host, t: float) -> str:
    """Innermost host span covering time ``t`` (the shortest that does)."""
    best = None
    for name, s, d in host:
        if name == WINDOW_SPAN:
            continue
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host:unannotated"


def reduce_events(events: dict, scopes: dict, kernels: tuple) -> dict | None:
    """Per-device busy/idle and attributed times, averaged over devices.

    The window is the host span named ``WINDOW_SPAN`` (else the extent of
    the device's operations), and operations are clipped to it.  ``scopes``
    maps a label to a regular expression searched in an op's scope (module
    and name stack); ``kernels`` are kernel names found in op names.  Returns None
    when the window holds no device operation."""
    host = events.get("host", [])
    win = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    per = []
    for ops in (events.get("devices") or {}).values():
        if win:
            lo, hi = win[-1]
            ops = [[n, max(s, lo), min(s + d, hi) - max(s, lo), st]
                   for n, s, d, st in ops if s < hi and s + d > lo]
        if not ops:
            continue
        if not win:
            lo = min(s for _, s, _, _ in ops)
            hi = max(s + d for _, s, d, _ in ops)
        span = hi - lo
        busy, merged = union_length((s, s + d) for _, s, d, _ in ops)
        scope_t = defaultdict(float)
        kern_t = defaultdict(float)
        kern_n = defaultdict(int)
        by_op = defaultdict(float)
        for name, s, d, stack in ops:
            if _CONTAINERS.match(name):
                continue
            label = "other"
            for key, pat in scopes.items():
                if re.search(pat, stack):
                    scope_t[key] += d
                    label = key
                    break
            for k in kernels:
                if k in name:
                    kern_t[k] += d
                    kern_n[k] += 1
                    label = k
                    break
            by_op[label if label in kernels else
                  f"{label}:{_base(name)}"] += d
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
        per.append({"busy": busy, "span": span, "scope": scope_t,
                    "kern": kern_t, "kern_n": kern_n, "ops": by_op,
                    "gaps": gaps})
    if not per:
        return None
    n = len(per)
    mean = lambda f: sum(f(p) for p in per) / n
    keys = lambda f: {k for p in per for k in f(p)}
    ops = {k: mean(lambda p: p["ops"].get(k, 0.0)) * 1e-9
           for k in keys(lambda p: p["ops"])}
    gaps = sorted((g for p in per for g in p["gaps"]), reverse=True)[:10]
    return {
        "busy_s": mean(lambda p: p["busy"]) * 1e-9,
        "window_s": mean(lambda p: p["span"]) * 1e-9,
        "scope_s": {k: mean(lambda p: p["scope"].get(k, 0.0)) * 1e-9
                    for k in scopes},
        "kernel_s": {k: mean(lambda p: p["kern"].get(k, 0.0)) * 1e-9
                     for k in kernels},
        "kernel_calls": {k: mean(lambda p: p["kern_n"].get(k, 0))
                         for k in kernels},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": [[_host_activity(host, 0.5 * (a + b)), g * 1e-9]
                      for g, a, b in gaps],
    }
