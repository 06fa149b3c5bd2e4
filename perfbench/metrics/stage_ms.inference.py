"""Device time per MD step of the operations in the pipeline's
``obs.inference`` scope (DP model and kernels)."""

SCOPES = {"inference": r"obs\.inference"}


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("inference") if t else None
    return 1e3 * s / ctx["steps"] if s else None
