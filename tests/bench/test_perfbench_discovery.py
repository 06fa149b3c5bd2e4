"""BENCHMARK.json against the benchmark's contract, and discovery of cells,
configurations, traffic mixes and metric readers by name."""
import json
import re

import pytest
from perfbench_tiny import ROOT, make

from perfbench import harness

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source",
                              "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}}
    for kind, keys in allowed.items():
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
        for e in BENCH[kind]:
            assert set(e) <= keys and NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for cell in CELLS:
        c = harness.load_cell(cell)
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        assert c.workload["chips"] in (1, 4)


def test_configs_files_and_references_are_found_by_name():
    for cfg in BENCH["configs"]:
        path = ROOT / cfg["file"]
        assert path.is_file() and cfg["file"].startswith("perfbench/")
        body = json.loads(path.read_text())
        assert set(cfg["reduced"]) == set(body["reduced"])
        assert (harness.BENCH / "references"
                / f"{body['reference']}.py").is_file()
        assert (harness.BENCH / "systems"
                / f"{body['system']['builder']}.py").is_file()
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_found_by_name(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    cell = harness.load_cell(m["workloads"][0])
    assert callable(cell.reader(metric).read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    # every cell the metric lists reports the end-to-end metric it moves
    moves = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moves.get("workloads", CELLS))


def test_throwaway_workload_file_is_discovered(tmp_path):
    bd, bench = make(tmp_path, name="throwaway.cell")
    cell = harness.load_cell("throwaway.cell", bd, bench)
    assert cell.config["system"]["n_residues"] == 16
    assert cell.traffic["rebuild_every"] == 5
    assert {m["name"] for m in cell.end_to_end} == {"ns_per_day", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in bench["per_layer"]}


def test_workload_must_match_its_benchmark_entry(tmp_path):
    bd, bench = make(tmp_path, name="throwaway.cell")
    bench["workloads"][-1]["traffic"] = "skin_reuse"
    with pytest.raises(SystemExit):
        harness.load_cell("throwaway.cell", bd, bench)
    with pytest.raises(SystemExit):
        harness.load_cell("no.such.cell", bd, bench)
