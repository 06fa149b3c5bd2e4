"""The attention stack's least time on the chip over its measured device
time: forward and backward calls from the trace, each counted at the rows
the decomposition holds (local plus ghost) and ``sel`` neighbours."""

KERNELS = ("nbr_attn_stack_fwd", "nbr_attn_stack_bwd")


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if not (t and c and ctx["peak"]):
        return None
    busy = sum(t["kernel_s"][k] for k in KERNELS)
    if busy <= 0:
        return None
    m, peak = ctx["model"], ctx["peak"]
    cost = ctx["cost"]("nbr_attn_stack")
    shape = (c["local"] + c["ghost"], m["sel"], m["neuron"][-1],
             m["attn_hidden"], m["attn_layers"])
    least = 0.0
    for kernel, work in zip(KERNELS, (cost.fwd, cost.bwd)):
        flops, nbytes = work(*shape)
        least += t["kernel_calls"][kernel] * max(
            flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
