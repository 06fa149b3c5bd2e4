"""Share of the evaluated buffer rows (local plus ghost capacity) that hold
an atom the decomposition asked for."""


def read(ctx):
    c = ctx["counts"]
    cap = c["local_capacity"] + c["ghost_capacity"] if c else 0
    return 100.0 * (c["local"] + c["ghost"]) / cap if cap else None
