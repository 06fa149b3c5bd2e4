"""Device time per MD step of the engine's own in-window work: the
operations in the ``md.rebuild_check`` (displacement check) and
``md.integrate`` (force sum and integrator) scopes of the window program."""

SCOPES = {"engine": r"md\.(integrate|rebuild_check)"}


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("engine") if t else None
    return 1e3 * s / ctx["steps"] if s else None
