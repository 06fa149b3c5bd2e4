"""Host time per MD step in the engine's ``md.verdict`` span: the window
verdict's flag reads and the diagnostics reads after it, from the totals
the engine publishes into the program's registry at the end of the
measured run (none when the last run published is not the measured one)."""

GAUGE = "md.run.span_s.md.verdict"


def read(ctx):
    from repro.obs import get_registry
    gauges = (ctx.get("registry") or get_registry()).snapshot()["gauges"]
    steps = ctx["steps"]
    ran = gauges.get("md.run.steps", {}).get("value")
    if not steps or ran != steps or GAUGE not in gauges:
        return None
    return 1e3 * gauges[GAUGE]["value"] / steps
