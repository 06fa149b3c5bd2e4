"""Plain ``jax.numpy`` reference of a DPA-1 / classical hybrid MD force call.

Independent of the program under test: it imports nothing from it and reads
only the benchmark's own arrays (positions, species, masses, LJ tables) and
the weights the benchmark drew from the seed.  It follows the DPA-1 model
as the configuration states it:

* environment matrix with the DeePMD smooth switch (1/r below
  ``rcut_smth``, quintic decay to 0 at ``rcut``), unit normalisation;
* type embedding, embedding net (tanh, ResNet skips where the width stays
  or doubles, linear last layer);
* ``attn_layers`` gated self-attention layers over the neighbour axis
  (se_attention_v2: softmax weights times r_hat_j . r_hat_k times both
  envelopes, residual, layer norm with eps 1e-5);
* the bilinear reduction G^T R R^T G< over ``axis_neuron`` columns, scaled
  by 1/sel, then the fitting net and a per-species bias;
* E = sum of atomic energies over the DP group, F = -dE/dx by autodiff,
  minimum image over the periodic box.

The classical side is a brute-force pair sum over every pair within the
cutoff that is not a DP-DP pair: Lennard-Jones with Lorentz-Berthelot
combination and a potential shift at the cutoff, plus reaction-field
Coulomb; forces in closed form.

``precision`` picks the arithmetic: ``"highest"`` is the configuration's
(float32, every matmul at full float32); ``"bf16_3x"`` runs every matmul as
the three bfloat16 products of the "high" setting (the control: the TPU's
own "high", emulated exactly on other backends);
``"bfloat16"`` runs the classical pair terms in bfloat16 (its control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

COULOMB = 138.935458     # kJ mol^-1 nm e^-2
R2_MIN = 1e-12           # coincident pairs clamp to r = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# --------------------------------------------------------------------------
# weights: drawn from the seed in the layout the program consumes
# --------------------------------------------------------------------------

def _mlp_init(key, sizes):
    out = []
    for k, (din, dout) in zip(jax.random.split(key, len(sizes) - 1),
                              zip(sizes[:-1], sizes[1:])):
        out.append({"w": jax.random.normal(k, (din, dout), F32) / np.sqrt(din),
                    "b": jnp.zeros((dout,), F32)})
    return out


def init_params(key, m: dict) -> dict:
    """DPA-1 weights for the model block ``m`` of a configuration file."""
    kd, kf, _ = jax.random.split(key, 3)
    k_emb, k_type, k_attn = jax.random.split(kd, 3)
    m1, h = m["neuron"][-1], m["attn_hidden"]
    layers = []
    for k in jax.random.split(k_attn, m["attn_layers"]):
        kq, kk, kv, ko = jax.random.split(k, 4)
        layers.append({
            "wq": jax.random.normal(kq, (m1, h), F32) / np.sqrt(m1),
            "wk": jax.random.normal(kk, (m1, h), F32) / np.sqrt(m1),
            "wv": jax.random.normal(kv, (m1, h), F32) / np.sqrt(m1),
            "wo": jax.random.normal(ko, (h, m1), F32) / np.sqrt(h),
            "ln": {"gamma": jnp.ones((m1,), F32),
                   "beta": jnp.zeros((m1,), F32)}})
    desc = {"type_embed": 0.1 * jax.random.normal(
                k_type, (m["ntypes"], m["type_embed_dim"]), F32),
            "embed": _mlp_init(k_emb, (1 + m["type_embed_dim"],)
                               + tuple(m["neuron"])),
            "attn": layers}
    fit = (m1 * m["axis_neuron"],) + tuple(m["fitting_neuron"]) + (1,)
    return {"descriptor": desc, "fitting": _mlp_init(kf, fit),
            "bias": jnp.zeros((m["ntypes"],), F32)}


# --------------------------------------------------------------------------
# DPA-1
# --------------------------------------------------------------------------

def _einsum(spec, a, b, precision):
    if precision == "bf16_3x" and jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGH,
                          preferred_element_type=F32)
    if precision == "bf16_3x":
        # elsewhere "high" is float32: a = a_hi + a_lo in two bfloat16
        # parts, and the three products a_hi b_hi + a_hi b_lo + a_lo b_hi,
        # exact in float32, are what the TPU's three passes compute
        def split(x):
            hi = x.astype(jnp.bfloat16).astype(F32)
            return hi, (x - hi).astype(jnp.bfloat16).astype(F32)

        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
        e = functools.partial(jnp.einsum, spec, precision=HIGHEST,
                              preferred_element_type=F32)
        return e(a_hi, b_hi) + e(a_hi, b_lo) + e(a_lo, b_hi)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def _mlp(layers, x, precision):
    for i, layer in enumerate(layers):
        y = _einsum("...i,ij->...j", x, layer["w"], precision) + layer["b"]
        if i == len(layers) - 1:
            return y
        y = jnp.tanh(y)
        din, dout = layer["w"].shape
        if dout == din:
            y = y + x
        elif dout == 2 * din:
            y = y + jnp.concatenate([x, x], axis=-1)
        x = y
    return x


def _switch(r, rs, rc):
    u = jnp.clip((r - rs) / (rc - rs), 0.0, 1.0)
    poly = u ** 3 * (-6 * u ** 2 + 15 * u - 10) + 1.0
    return jnp.where(r < rc, (1.0 / r) * jnp.where(r < rs, 1.0, poly), 0.0)


def _attention(g, r_hat, env, mask, layers, precision):
    gate = jnp.einsum("nka,nla->nkl", r_hat, r_hat, precision=HIGHEST)
    gmul = gate * (env[:, :, None] * env[:, None, :])
    gmul = gmul * (mask[:, :, None] * mask[:, None, :])
    neg = jnp.finfo(F32).min
    for p in layers:
        q = _einsum("nkm,mh->nkh", g, p["wq"], precision)
        k = _einsum("nkm,mh->nkh", g, p["wk"], precision)
        v = _einsum("nkm,mh->nkh", g, p["wv"], precision)
        s = _einsum("nkh,nlh->nkl", q, k, precision) / np.sqrt(q.shape[-1])
        s = jnp.where(mask[:, None, :] > 0, s, neg)
        w = jax.nn.softmax(s, axis=-1) * gmul
        o = _einsum("nkh,hm->nkm", _einsum("nkl,nlh->nkh", w, v, precision),
                    p["wo"], precision)
        g1 = g + o
        mu = g1.mean(-1, keepdims=True)
        var = ((g1 - mu) ** 2).mean(-1, keepdims=True)
        g = ((g1 - mu) * jax.lax.rsqrt(var + 1e-5) * p["ln"]["gamma"]
             + p["ln"]["beta"]) * mask[..., None]
    return g


def atomic_energies(params, m: dict, coords, types, rows, nbr_idx, nbr_mask,
                    box, precision: str = "highest"):
    """e_i of the atoms ``rows`` from their neighbour lists (minimum image
    in ``box``)."""
    d = params["descriptor"]
    safe = jnp.where(nbr_idx >= 0, nbr_idx, 0)
    dr = coords[safe] - coords[rows][:, None, :]
    dr = dr - box * jnp.round(dr / box)
    mask = nbr_mask.astype(F32)
    d2 = jnp.where(mask > 0, jnp.maximum((dr ** 2).sum(-1), R2_MIN), 1.0)
    dist = jnp.sqrt(d2)
    sw = _switch(dist, m["rcut_smth"], m["rcut"]) * mask
    r_hat = dr / dist[..., None] * mask[..., None]
    R = jnp.concatenate([sw[..., None], sw[..., None] * r_hat], -1)
    t_emb = d["type_embed"][jnp.clip(types[safe], 0)] * mask[..., None]
    g = _mlp(d["embed"], jnp.concatenate([sw[..., None], t_emb], -1),
             precision) * mask[..., None]
    g = _attention(g, r_hat, sw * dist, mask, d["attn"], precision)
    gr = _einsum("nkm,nka->nma", g, R, precision) / m["sel"]
    desc = _einsum("nma,npa->nmp", gr, gr[:, :m["axis_neuron"], :], precision)
    e = _mlp(params["fitting"], desc.reshape(desc.shape[0], -1),
             precision)[..., 0]
    return e + params["bias"][jnp.clip(types[rows], 0)]


def neighbor_list(coords, box, rcut: float, capacity: int):
    """Every pair within ``rcut`` (minimum image), lowest indices first.
    Returns (idx, mask, max count): a count above ``capacity`` means the
    list is incomplete."""
    n = coords.shape[0]
    dr = coords[None, :, :] - coords[:, None, :]
    dr = dr - box * jnp.round(dr / box)
    within = ((dr ** 2).sum(-1) < rcut ** 2) & ~jnp.eye(n, dtype=bool)
    score = jnp.where(within, -jnp.arange(n, dtype=F32)[None, :], -jnp.inf)
    _, idx = jax.lax.top_k(score, min(capacity, n))
    take = jnp.take_along_axis(within, idx, axis=1)
    return jnp.where(take, idx, -1), take, within.sum(1).max()


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _dp_block(params, coords, types, box, rows, valid, idx, mask, m_items,
              precision):
    """Energy of the atoms ``rows`` (those ``valid``), sum of |e_i|, and the
    gradient of that energy with respect to every coordinate."""
    m = dict(m_items)

    def total(x):
        e = atomic_energies(params, m, x, types, rows, idx, mask, box,
                            precision) * valid
        return e.sum(), jnp.abs(e).sum()

    (energy, scale), g = jax.value_and_grad(total, has_aux=True)(coords)
    return energy, g, scale


_neighbor_list = jax.jit(neighbor_list, static_argnames=("rcut", "capacity"))


def dp_energy_forces(params, model: dict, coords, types, box,
                     precision: str = "highest", block: int = 256):
    """(E, F (N, 3), sum_i |e_i|) of the DP group, in blocks of ``block``
    centre atoms (each block's energy differentiated with respect to every
    coordinate, the gradients summed), so that the attention planes of one
    block at a time are held; raises when more than ``sel`` neighbours fall
    inside the cutoff."""
    m_items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                           for k, v in model.items()))
    x = jnp.asarray(coords, F32)
    types = jnp.asarray(types)
    box = jnp.asarray(box, F32)
    n = x.shape[0]
    e_tot, scale, grad = 0.0, 0.0, np.zeros((n, 3))
    with jax.default_matmul_precision("highest"):
        idx, mask, count = _neighbor_list(x, box, rcut=model["rcut"],
                                          capacity=model["sel"])
        if int(count) > model["sel"]:
            raise RuntimeError(f"{int(count)} neighbours within r_c exceed "
                               f"sel {model['sel']}")
        for b in range(0, n, block):
            rows = np.arange(b, b + block)
            valid = rows < n
            rows = jnp.asarray(np.minimum(rows, n - 1))
            e, g, s = _dp_block(params, x, types, box, rows,
                                jnp.asarray(valid, F32), idx[rows],
                                mask[rows], m_items, precision)
            e_tot += float(e)
            scale += float(s)
            grad += np.asarray(g, np.float64)
    return e_tot, -grad, scale


# --------------------------------------------------------------------------
# classical pair sum
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cutoff", "eps_rf", "dtype"))
def _pair_block(rows, x_i, x, box, sig, eps, q, nn, valid, cutoff, eps_rf,
                dtype):
    dr = x[None, :, :] - x_i[:, None, :]
    dr = (dr - box * jnp.round(dr / box)).astype(dtype)
    r2 = (dr ** 2).sum(-1)
    n = x.shape[0]
    keep = ((r2 < cutoff ** 2) & (rows[:, None] != jnp.arange(n)[None, :])
            & ~(nn[rows][:, None] & nn[None, :]) & valid[:, None])
    r2 = jnp.where(keep, r2, 1.0)
    s = (0.5 * (sig[rows][:, None] + sig[None, :])).astype(dtype)
    e_lj = jnp.sqrt(eps[rows][:, None] * eps[None, :]).astype(dtype)
    sr6 = (s ** 2 / r2) ** 3
    src6 = (s ** 2 / cutoff ** 2) ** 3
    u = 4 * e_lj * (sr6 ** 2 - sr6) - 4 * e_lj * (src6 ** 2 - src6)
    du_r = 4 * e_lj * (6 * sr6 - 12 * sr6 ** 2) / r2      # U'(r) / r
    qq = (COULOMB * q[rows][:, None] * q[None, :]).astype(dtype)
    k_rf = (eps_rf - 1.0) / (2 * eps_rf + 1.0) / cutoff ** 3
    c_rf = 1.0 / cutoff + k_rf * cutoff ** 2
    r = jnp.sqrt(r2)
    u = u + qq * (1.0 / r + k_rf * r2 - c_rf)
    du_r = du_r + qq * (2 * k_rf - 1.0 / (r2 * r))
    u = jnp.where(keep, u, 0).astype(F32)
    f = (jnp.where(keep, du_r, 0)[..., None] * dr).astype(F32).sum(1)
    return 0.5 * u.sum(), 0.5 * jnp.abs(u).sum(), f


def classical_energy_forces(x, types, charges, nn_mask, lj_sigma, lj_epsilon,
                            box, cutoff: float, eps_rf: float,
                            precision: str = "highest", block: int = 512):
    """(E, F (N, 3), sum of |pair energies|) over all pairs within the cutoff
    that are not both DP atoms, in blocks of ``block`` rows."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else F32
    x = jnp.asarray(x, F32)
    n = x.shape[0]
    sig = jnp.asarray(np.asarray(lj_sigma)[np.asarray(types)], F32)
    eps = jnp.asarray(np.asarray(lj_epsilon)[np.asarray(types)], F32)
    q = jnp.asarray(charges, F32)
    nn = jnp.asarray(np.asarray(nn_mask) > 0)
    box = jnp.asarray(box, F32)
    e_tot, scale, forces = 0.0, 0.0, []
    for b in range(0, n, block):
        rows = np.arange(b, b + block)
        valid = rows < n
        rows = jnp.asarray(np.minimum(rows, n - 1))
        e, s, f = _pair_block(rows, x[rows], x, box, sig, eps, q, nn,
                              jnp.asarray(valid), cutoff, eps_rf, dtype)
        e_tot += float(e)
        scale += float(s)
        forces.append(np.asarray(f)[valid])
    return e_tot, np.concatenate(forces), scale
