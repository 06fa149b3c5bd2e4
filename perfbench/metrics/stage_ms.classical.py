"""Device time per MD step of the classical force field: the operations of
``_classical_one`` (pair forces) and of the classical neighbour-list
builder, in the window and on the host."""

SCOPES = {"classical": r"_classical_one|neighbor_list"}


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("classical") if t else None
    return 1e3 * s / ctx["steps"] if s else None
