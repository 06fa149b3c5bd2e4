"""A tiny cell for the benchmark's tests: the protein-hybrid configuration at
reduced widths (64 DP atoms), copied with the benchmark into a temporary
directory so that a test adds files there and never to the benchmark."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402

# set as PERF.md sets a cell's limits, from CPU readings of six seeds:
# program at most 5.8e-7 / 2.3e-7 / 4.6e-7 / 3.7e-7, control at least
# 1.4e-5 / 4.9e-5 / 2.7e-2 / 6.1e-3 (DP force, DP energy, classical force,
# classical energy)
LIMITS = {"dp_force_atom_rel": 5e-6, "dp_energy_rel": 2e-6,
          "cl_force_rel": 1e-4, "cl_energy_rel": 1e-4}


def make(tmp: Path, name: str = "tiny.cell", traffic=None) -> tuple:
    """(bench_dir, benchmark dict) with the cell ``name`` added as files."""
    bd = tmp / "perfbench"
    if not bd.exists():
        shutil.copytree(harness.BENCH, bd,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((bd / "configs/dpa1_protein_hybrid_4k.json").read_text())
    cfg["model"].update(rcut=0.5, rcut_smth=0.2, sel=32, neuron=[8, 16],
                        axis_neuron=4, attn_layers=2, attn_hidden=16,
                        fitting_neuron=[16, 16])
    cfg["system"]["n_residues"] = 16
    cfg["forcefield"]["cutoff"] = 0.9
    (bd / "configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bd / "traffic/skin_reuse.json").read_text())
    mix.update(rebuild_every=5, dd_skin=0.05, **(traffic or {}))
    (bd / "traffic/tiny_mix.json").write_text(json.dumps(mix))
    wl = {"config": "tiny", "traffic": "tiny_mix", "chips": 1,
          "limits": LIMITS}
    (bd / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    bench["workloads"].append({"name": name, "config": "tiny",
                              "traffic": "tiny_mix", "chips": 1,
                              "why": "test cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return bd, bench
