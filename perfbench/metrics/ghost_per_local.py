"""Ghost rows per local DP atom in the decomposition's buffer."""


def read(ctx):
    c = ctx["counts"]
    return c["ghost"] / c["local"] if c and c["local"] else None
