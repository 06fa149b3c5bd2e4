"""What decides ``correct``: the forces the measured window integrated in its
last step, against the configuration's plain reference.

The engine's final state holds the positions after the last step and the
forces that step integrated, which were computed at the positions before it.
:func:`previous_positions` recovers those positions exactly from the final
state by undoing the leap-frog drift, float32 rounding included, so that
the reference is evaluated at the very coordinates the program used.  (A
thermostat that rescales the velocities after the drift would hide the
velocities the drift used to the last bit, so the traffic runs without
one.)  Where that cannot be told (an
atom that crossed into a coarser float32 spacing), the atoms within reach of
it are left out of the force comparison.
"""
from __future__ import annotations

import numpy as np

def _drift_back(x_new, v_raw, dt, box):
    """Float32 candidates ``c`` with wrap(c + v_raw dt) == x_new, per
    coordinate, for the drift computed as a multiply and an add or as one
    fused multiply-add (the compiler picks).  Returns (c, resolved mask,
    ambiguous mask): a coordinate that crossed upwards into a coarser
    float32 spacing (a power of two, or the wrap from below 0 to below the
    box length) has several such ``c``, and the one taken may be off by up
    to that spacing."""
    f32 = np.float32
    vdt = v_raw.astype(np.float64) * np.float64(f32(dt))   # exact product
    disp = vdt.astype(f32)
    y = x_new.astype(np.float64) - vdt
    y = np.where(y < 0, y + box, np.where(y >= box, y - box, y))
    base = y.astype(f32)
    out = base.copy()
    resolved = np.zeros(x_new.shape, bool)
    matches = np.zeros(x_new.shape, np.int8)
    box32 = np.broadcast_to(box.astype(f32), x_new.shape)

    def wrap(s):
        return np.where(s < 0, (s + box32).astype(f32),
                        np.where(s >= box32, (s - box32).astype(f32), s))

    for cand in (base, np.nextafter(base, f32(np.inf)),
                 np.nextafter(base, f32(-np.inf))):
        split = wrap((cand + disp).astype(f32))
        fused = wrap((cand.astype(np.float64) + vdt).astype(f32))
        match = (split == x_new) | (fused == x_new)
        matches += match
        hit = match & ~resolved
        out = np.where(hit, cand, out)
        resolved |= hit
    return out, resolved, matches > 1


# An ambiguous coordinate is off by at most the float32 spacing at its new
# value.  Up to this many nm, one atom moved that far changes the compared
# force numbers by well under the program's own readings (about 5e-7
# relative at 2.4e-7 nm in a 343-atom water box at paper widths, CPU run);
# beyond it (coordinates past 8 nm: the protein's long axis) the atoms
# within reach are left out.
MAX_AMBIGUITY_NM = 1e-6


def previous_positions(positions, velocities, box, dt: float):
    """Positions before the engine's last leap-frog step (no thermostat),
    from the state after it.  Returns (positions (N, 3) float32, number of
    coordinates no float32 candidate reproduced, (N,) mask of atoms with a
    coordinate that several candidates reproduce and that may be off by
    more than ``MAX_AMBIGUITY_NM``)."""
    x = np.asarray(positions, np.float32)
    prev, ok, ambiguous = _drift_back(
        x, np.asarray(velocities, np.float32), dt,
        np.asarray(box, np.float64))
    coarse = ambiguous & (np.spacing(x) > MAX_AMBIGUITY_NM)
    return prev, int((~ok).sum()), coarse.any(-1)


def near(x, atoms, box, reach: float):
    """(N,) mask of atoms within ``reach`` (minimum image) of any atom in
    the mask ``atoms``."""
    out = np.zeros(len(x), bool)
    for p in np.asarray(x, np.float64)[atoms]:
        d = np.asarray(x, np.float64) - p
        d -= box * np.round(d / box)
        out |= (d ** 2).sum(-1) < reach ** 2
    return out


def rel_rmse(f, f_ref) -> float:
    """RMS of the difference over RMS of the reference."""
    f = np.asarray(f, np.float64)
    f_ref = np.asarray(f_ref, np.float64)
    rms = np.sqrt((f_ref ** 2).mean())
    return float(np.sqrt(((f - f_ref) ** 2).mean()) / max(rms, 1e-30))


def atom_rel_rms(f, f_ref) -> float:
    """RMS over atoms of |f_i - f_ref_i| / (|f_ref_i| + median |f_ref|):
    each atom's error relative to its own force, floored at the typical
    force, so that every atom counts alike, one in near contact (hundreds
    of times the median force, where float32 resolves about 1e-5 of it) as
    much as one with an ordinary force."""
    f = np.asarray(f, np.float64)
    f_ref = np.asarray(f_ref, np.float64)
    mag = np.linalg.norm(f_ref, axis=-1)
    err = (np.linalg.norm(f - f_ref, axis=-1)
           / np.maximum(mag + np.median(mag), 1e-30))
    return float(np.sqrt((err ** 2).mean()))


def compare(forces, e_dp, e_cl, ref: dict, nn_idx, skip=None) -> dict:
    """The numbers compared: DP-group forces (the program's total force on
    a DP atom less the classical reference there, atom by atom,
    ``atom_rel_rms``) and energy, classical forces on the other atoms and
    the classical energy.  Atoms in the mask ``skip`` are left out of the
    force numbers; numbers that the system has nothing to compare for (no
    classical atom) are left out."""
    n = len(forces)
    dp = np.zeros(n, bool)
    dp[nn_idx] = True
    keep = np.ones(n, bool) if skip is None else ~skip
    f = np.asarray(forces, np.float64)
    f_dp = np.zeros((n, 3))
    f_dp[nn_idx] = ref["f_dp"]
    sel = dp & keep
    out = {"dp_force_atom_rel": atom_rel_rms(f[sel] - ref["f_cl"][sel],
                                             f_dp[sel]),
           "dp_energy_rel": abs(e_dp - ref["e_dp"]) / ref["e_dp_scale"]}
    if (~dp).any():
        sel = ~dp & keep
        out["cl_force_rel"] = rel_rmse(f[sel], ref["f_cl"][sel])
        out["cl_energy_rel"] = abs(e_cl - ref["e_cl"]) / ref["e_cl_scale"]
    return out


def reference_forces(ref_mod, params, cfg: dict, spec: dict, x,
                     dp_precision: str = "highest",
                     cl_precision: str = "highest") -> dict:
    """DP-group and classical reference at positions ``x``."""
    box = spec["box"]
    nn = spec["nn_idx"]
    x = np.asarray(x, np.float32)
    e_dp, f_dp, scale = ref_mod.dp_energy_forces(
        params, cfg["model"], np.mod(x[nn], box), spec["types"][nn], box,
        dp_precision)
    nn_mask = np.zeros(len(x), np.float32)
    nn_mask[nn] = 1.0
    ff = cfg["forcefield"]
    e_cl, f_cl, cl_scale = ref_mod.classical_energy_forces(
        x, spec["types"], spec["charges"], nn_mask, spec["lj_sigma"],
        spec["lj_epsilon"], box, ff["cutoff"], ff["eps_rf"], cl_precision)
    return {"e_dp": e_dp, "f_dp": f_dp, "e_dp_scale": scale, "e_cl": e_cl,
            "f_cl": f_cl, "e_cl_scale": max(cl_scale, 1e-30)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; ``correct`` when none exceeds it.  A
    number without a limit, or NaN, fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        passed = (limit is not None and value == value
                  and float(value) <= float(limit))
        ok &= passed
        table[name] = {"value": value, "limit": limit}
    return ok, table
