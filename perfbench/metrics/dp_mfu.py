"""Model FLOPs of the DP group's own atoms at ``sel`` neighbours (forward and
force backward) per second of the measured window, over the chip's peak."""


def read(ctx):
    if not ctx["peak"] or ctx["wall_s"] <= 0:
        return None
    flops = ctx["cost"]("dpa1_model").flops_per_atom(ctx["model"])
    rate = flops * ctx["dp_atoms"] * ctx["steps"] / ctx["wall_s"]
    return 100.0 * rate / ctx["peak"]["flops_per_s"]
