"""Blocking reads of device values that the engine's run loop makes per
window, from the totals the engine publishes into the program's registry
at the end of the measured run (none when the last run published is not
the measured one)."""


def read(ctx):
    from repro.obs import get_registry
    gauges = (ctx.get("registry") or get_registry()).snapshot()["gauges"]
    value = lambda name: gauges.get(name, {}).get("value")
    steps, windows = ctx["steps"], value("md.run.windows")
    if (not steps or value("md.run.steps") != steps or not windows
            or value("md.run.host_reads") is None):
        return None
    return value("md.run.host_reads") / windows
