"""Work of the fused DPA-1 attention stack for ``rows`` atoms of ``k``
neighbours: FLOPs of its matmuls and the bytes it must move at least
(float32 activations in and out, the weights once).  The backward pass is
counted as forces need it: activation gradients only."""

F32 = 4


def fwd(rows: int, k: int, m: int, h: int, layers: int) -> tuple:
    flops = rows * layers * (8 * k * m * h + 4 * k * k * h)
    weights = layers * (4 * m * h + 2 * m) * F32
    io = rows * k * (m + 5) * F32 + rows * k * m * F32
    return flops, io + weights


def bwd(rows: int, k: int, m: int, h: int, layers: int) -> tuple:
    flops = rows * layers * (8 * k * m * h + 8 * k * k * h)
    weights = layers * (4 * m * h + 2 * m) * F32
    io = (rows * k * (m + 5) * F32 + rows * k * m * F32
          + rows * k * (m + 4) * F32)
    return flops, io + weights
