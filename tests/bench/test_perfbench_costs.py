"""Cost functions against hand counts at tiny shapes."""
from perfbench_tiny import ROOT  # noqa: F401  (puts the checkout on the path)

from perfbench import harness

COSTS = harness.BENCH / "costs"
TINY = {"sel": 2, "neuron": [2, 4], "type_embed_dim": 1, "attn_hidden": 2,
        "attn_layers": 1, "axis_neuron": 1, "fitting_neuron": [3]}


def test_dpa1_model_flops_per_atom_by_hand():
    cost = harness.load_module(COSTS / "dpa1_model.py")
    # K=2, M=4, H=2, L=1, M2=1; embedding 2 -> 2 -> 4, fitting 4 -> 3 -> 1
    embed = 2 * (2 * 2 * 2 + 2 * 2 * 4)            # 48
    attn_w = 8 * 2 * 4 * 2                         # 128
    attn_a = 4 * 2 * 2 * 2                         # 32
    bilinear = 2 * 2 * 4 * 4 + 2 * 4 * 1 * 4      # 96
    fitting = 2 * 4 * 3 + 2 * 3 * 1                # 30
    want = 2 * embed + 2 * attn_w + 3 * attn_a + 3 * bilinear + 2 * fitting
    assert cost.flops_per_atom(TINY) == want == 796


def test_nbr_attn_stack_work_by_hand():
    cost = harness.load_module(COSTS / "nbr_attn_stack.py")
    rows, k, m, h, layers = 3, 2, 4, 2, 1
    f, b = cost.fwd(rows, k, m, h, layers)
    assert f == 3 * (8 * 2 * 4 * 2 + 4 * 2 * 2 * 2)          # 480
    weights = (4 * 4 * 2 + 2 * 4) * 4                      # 160 B
    assert b == 3 * 2 * (4 + 5) * 4 + 3 * 2 * 4 * 4 + weights
    f, b = cost.bwd(rows, k, m, h, layers)
    assert f == 3 * (8 * 2 * 4 * 2 + 8 * 2 * 2 * 2)          # 576
    assert b == (3 * 2 * 9 * 4 + 3 * 2 * 4 * 4 + 3 * 2 * 8 * 4) + weights


def test_solvated_protein_group_box_and_carving():
    import numpy as np
    build = harness.load_module(harness.BENCH / "systems"
                                / "solvated_protein.py").build
    s = build(16)
    x, nn, box = s["positions"], s["nn_idx"], s["box"]
    assert list(nn) == list(range(64)) and (s["types"][nn] > 0).all()
    assert (s["types"][64:] == 0).all() and (s["charges"][64:] == 0).all()
    assert abs(s["charges"][nn].sum()) < 1e-5
    # the box is 2 nm wider than the chain, the chain at its centre
    extent = x[nn].max(0) - x[nn].min(0)
    assert np.allclose(box, np.maximum(extent + 2.0, 4 * 0.31), atol=1e-5)
    assert np.allclose(x[nn].mean(0), box / 2, atol=1e-5)
    # no water within 0.3 nm of a DP atom; bonded terms inside the group
    d = x[64:, None] - x[None, nn]
    assert np.sqrt((d ** 2).sum(-1).min()) > 0.3
    assert s["bonds"].max() < 64 and s["angles"].max() < 64
