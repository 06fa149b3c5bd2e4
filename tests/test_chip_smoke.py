"""``chip_smoke.py`` on the CPU: its phases at a tiny size (64-atom DP
group, reduced widths, Pallas interpret mode), and the script itself
refusing to report success without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import run_in_subprocess

from repro.dp import DPConfig, DescriptorConfig

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

TINY = DPConfig(descriptor=DescriptorConfig(
    kind="dpa1", rcut=0.5, rcut_smth=0.2, sel=32, ntypes=4, neuron=(8, 16),
    axis_neuron=4, attn_layers=2, attn_hidden=16, use_pallas=True),
    fitting_neuron=(16, 16))


@pytest.fixture(scope="module")
def setup():
    return chip_smoke.build(16, TINY, skin=0.05)


def test_tiny_setup_has_64_dp_atoms(setup):
    info = chip_smoke.describe(setup)
    assert info["dp_atoms"] == 64 and info["local_count"] == 64
    assert info["force_mode"] == "owner_full"


def test_phase_parity_passes(setup):
    out = chip_smoke.phase_parity(setup)
    assert out["force_rel"] <= chip_smoke.FORCE_RTOL
    assert out["energy_rel"] <= chip_smoke.ENERGY_RTOL


def test_phase_md_passes(setup):
    out = chip_smoke.phase_md(setup, steps_per_window=5, windows=3)
    assert out["finite"] and out["overflow"] == 0
    assert out["dd_rebuilds"] >= 1
    assert out["diagnostics"]["window_reruns"] == 0


def test_phase_four_chips_on_four_cpu_devices():
    out = run_in_subprocess(f"""
import sys
sys.path.insert(0, {str(ROOT)!r}); sys.path.insert(0, {str(ROOT / 'tests')!r})
import chip_smoke
from test_chip_smoke import TINY
out = chip_smoke.phase_four_chips(16, TINY)
print("RESULT", len(out["shards"]), out["force_rel"], out["md"]["finite"])
""", n_devices=4)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][-1]
    _, n_shards, force_rel, finite = line.split()
    assert int(n_shards) == 4 and finite == "True"
    assert float(force_rel) <= chip_smoke.FORCE_RTOL


def test_phase_kernels_rejects_interpreted_kernels(setup):
    # on the CPU the kernels run in the Pallas interpreter, so the compiled
    # step holds no Mosaic custom call and phase C must fail
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_kernels(setup)


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
