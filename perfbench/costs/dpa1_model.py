"""Operations of one DPA-1 energy-and-force evaluation, per atom, from the
configuration's widths: the matmuls of the forward pass and of the
backward pass that forces need (gradients of the activations, none of the
weights).  A product of a weight and an activation costs its forward
FLOPs again in the backward pass; a product of two activations (attention
scores, attention output, the bilinear reduction) twice."""


def flops_per_atom(m: dict, k: int | None = None) -> float:
    k = m["sel"] if k is None else k
    mm, h, layers = m["neuron"][-1], m["attn_hidden"], m["attn_layers"]
    widths = (1 + m["type_embed_dim"],) + tuple(m["neuron"])
    embed = k * sum(2 * a * b for a, b in zip(widths, widths[1:]))
    attn_w = layers * 8 * k * mm * h           # q, k, v and output projections
    attn_a = layers * 4 * k * k * h            # scores and weighted values
    bilinear = 2 * k * mm * 4 + 2 * mm * m["axis_neuron"] * 4
    fit = (mm * m["axis_neuron"],) + tuple(m["fitting_neuron"]) + (1,)
    fitting = sum(2 * a * b for a, b in zip(fit, fit[1:]))
    return 2 * embed + 2 * attn_w + 3 * attn_a + 3 * bilinear + 2 * fitting
