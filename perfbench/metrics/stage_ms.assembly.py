"""Device time per MD step of the operations in the pipeline's
``obs.assembly`` scope in the window, and of the assembly program the host
runs at a list rebuild."""

SCOPES = {"assembly": r"obs\.assembly|^jit_assemble/"}


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("assembly") if t else None
    return 1e3 * s / ctx["steps"] if s else None
