"""The reduction from trace events to layer times, on a hand-made trace and
on a recorded one."""
import json
import re
from pathlib import Path

import pytest
from perfbench_tiny import ROOT  # noqa: F401

from perfbench.trace_reduce import (WINDOW_SPAN, op_names, reduce_events,
                                    union_length)

SCOPES = {"inference": r"obs\.inference", "classical": r"_classical_one"}
KERNELS = ("nbr_attn_stack_fwd", "nbr_attn_stack_bwd")

# times in ns: a window [100, 1100) on the host, two device planes
EVENTS = {
    "host": [[WINDOW_SPAN, 100.0, 1000.0], ["scan_window", 100.0, 700.0],
             ["cadence_rebuild", 800.0, 200.0]],
    "devices": {
        "/device:TPU:0": [
            ["fusion.1", 50.0, 100.0,
             "jit_w/jit(w)/while/body/jit(_classical_one)/mul"],
            ["nbr_attn_stack_fwd.7", 200.0, 200.0,
             "jit_w/jit(w)/obs.inference/pallas_call"],
            ["while.3", 150.0, 700.0, "jit_w/jit(w)/while"],
            ["nbr_attn_stack_bwd.7", 400.0, 300.0,
             "jit_w/jit(w)/obs.inference/pallas_call"],
            ["fusion.7", 900.0, 100.0, "jit_assemble/"],
            ["copy.2", 1050.0, 200.0, "/"],
        ],
        "/device:TPU:1": [["fusion.1", 100.0, 500.0, "jit_w/obs.inference/y"]],
    },
}


def test_op_names_from_compiled_text():
    text = (
        'HloModule jit_w\n'
        '  %fusion.674 = f32[16,3]{1,0} fusion(f32[16]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(w)/while/body/jit(_classical_one)'
        '/mul" source_file="x.py" source_line=3}\n'
        '  ROOT %tuple.2 = (f32[16,3]{1,0}) tuple(%fusion.674)\n'
        '  nbr_attn_stack_fwd.7 = f32[8]{0} custom-call(), '
        'metadata={op_name="jit(w)/obs.inference/pallas_call"}\n')
    assert op_names(text) == {
        "fusion.674": "jit(w)/while/body/jit(_classical_one)/mul",
        "nbr_attn_stack_fwd.7": "jit(w)/obs.inference/pallas_call"}


def test_union_of_overlapping_intervals():
    total, merged = union_length([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]


def test_busy_idle_stage_and_kernel_times_by_hand():
    r = reduce_events(EVENTS, SCOPES, KERNELS)
    # device 0, clipped to [100, 1100): busy [100, 850) + [900, 1000)
    # + [1050, 1100) = 900; device 1: [100, 600) = 500; mean over devices
    assert r["busy_s"] == pytest.approx((900 + 500) / 2 * 1e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    # inference: 200 + 300 on device 0, 500 on device 1; while.3 not counted
    assert r["scope_s"]["inference"] == pytest.approx((500 + 500) / 2 * 1e-9)
    # classical: fusion.1 clipped to [100, 150)
    assert r["scope_s"]["classical"] == pytest.approx(50 / 2 * 1e-9)
    assert r["kernel_s"]["nbr_attn_stack_fwd"] == pytest.approx(100e-9)
    assert r["kernel_s"]["nbr_attn_stack_bwd"] == pytest.approx(150e-9)
    assert r["kernel_calls"]["nbr_attn_stack_fwd"] == 0.5
    # idle [850, 900) falls inside the host's rebuild span, [1000, 1050)
    # inside no span of the program's
    assert sorted(r["idle_gaps"]) == [
        ["cadence_rebuild", pytest.approx(50e-9)],
        ["host:unannotated", pytest.approx(50e-9)]]
    ops = dict(r["device_ops"])
    assert ops["inference:fusion"] == pytest.approx(250e-9)


def test_no_device_operation_gives_nothing():
    assert reduce_events({"host": [], "devices": {}}, SCOPES, KERNELS) is None
    empty = {"host": [[WINDOW_SPAN, 0.0, 10.0]],
             "devices": {"/device:TPU:0": [["f", 20.0, 5.0, ""]]}}
    assert reduce_events(empty, SCOPES, KERNELS) is None


RECORDED = Path(__file__).parent / "data" / "tpu_trace_excerpt.json"


def test_recorded_chip_trace_by_independent_count():
    """An excerpt of a traced ``protein_hybrid.skin_reuse`` window on one
    v5e (the extract() form), reduced and checked against sums made here
    op by op."""
    events = json.loads(RECORDED.read_text())
    scopes = {"inference": r"obs\.inference",
              "classical": r"_classical_one|neighbor_list",
              "assembly": r"obs\.assembly|^jit_assemble/"}
    r = reduce_events(events, scopes, KERNELS)
    (ops,) = events["devices"].values()
    lo, hi = next((s, s + d) for n, s, d in events["host"]
                  if n == WINDOW_SPAN)
    clipped = [(n, max(s, lo), min(s + d, hi), st) for n, s, d, st in ops
               if s < hi and s + d > lo]
    # busy: a time point is busy when some op covers it; count on a grid
    # of the ops' own boundaries
    edges = sorted({t for _, a, b, _ in clipped for t in (a, b)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e, _ in clipped))
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    leaf = [(n, e - s, st) for n, s, e, st in clipped
            if not n.startswith(("while", "conditional", "call", "tuple"))]
    for k in KERNELS:
        want = sum(d for n, d, _ in leaf if n.startswith(k))
        assert r["kernel_s"][k] == pytest.approx(want * 1e-9, rel=1e-9)
        assert want > 0
    taken = set()
    for label, pat in scopes.items():
        want = sum(d for i, (n, d, st) in enumerate(leaf)
                   if re.search(pat, st) and i not in taken)
        taken |= {i for i, (n, d, st) in enumerate(leaf)
                  if re.search(pat, st)}
        assert r["scope_s"][label] == pytest.approx(want * 1e-9, rel=1e-9)
    assert r["scope_s"]["inference"] > 0 and r["scope_s"]["classical"] > 0
