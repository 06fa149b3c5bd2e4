"""A run whose timed path is broken underneath must come out not correct:
one test for each fault a one-chip MD cell can have."""
import jax.numpy as jnp
import pytest
from perfbench_tiny import make

from perfbench import harness
from repro.core.nnpot import DeepmdForceProvider
from repro.md import MDEngine


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda jax: None)


def _run(tmp_path):
    bd, bench = make(tmp_path)
    return harness.run("tiny.cell", 11, 0.3, False, bench_dir=bd,
                       benchmark=bench, require_tpu=False)


def test_step_that_returns_its_state_unchanged(tmp_path, monkeypatch):
    monkeypatch.setattr(MDEngine, "_integrate_one",
                        lambda self, state, f, t: state)
    res = _run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["steps_missing"]["value"] > 0


def test_half_of_the_dp_group_left_out(tmp_path, monkeypatch):
    to_engine = DeepmdForceProvider._to_engine

    def half(self, e, f_nn, positions):
        n = f_nn.shape[-2]
        f_nn = f_nn.at[..., : n // 2, :].set(0.0)
        return to_engine(self, e, f_nn, positions)

    monkeypatch.setattr(DeepmdForceProvider, "_to_engine", half)
    res = _run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["dp_force_atom_rel"]["value"] > 0.1


def test_one_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    to_engine = DeepmdForceProvider._to_engine

    def altered(self, e, f_nn, positions):
        f_nn = f_nn.at[..., 7, :].multiply(jnp.float32(1.5))
        return to_engine(self, e, f_nn, positions)

    monkeypatch.setattr(DeepmdForceProvider, "_to_engine", altered)
    res = _run(tmp_path)
    assert not res["correct"]
    assert (res["checks"]["dp_force_atom_rel"]["value"]
            > res["checks"]["dp_force_atom_rel"]["limit"])
