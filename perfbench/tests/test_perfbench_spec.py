"""BENCHMARK.json against the contract's shapes, and the harness finding
every configuration, traffic mix and metric reader by name."""
import json
import pathlib
import re
import shutil

import pytest

from perfbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_units_use_the_allowed_characters():
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    configs = [c["name"] for c in BENCH["configs"]]
    for group in (metrics, configs, CELLS):
        assert len(set(group)) == len(group)
    names = configs + CELLS + metrics
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_every_metric_has_its_bound_layer_and_move():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", []):
            assert w in CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config["policy"]["dim"] == sum(
        a * b + b for a, b in zip(c.config["policy"]["sizes"][:-1],
                                  c.config["policy"]["sizes"][1:]))
    assert set(c.config["correct"]) == {
        "reward_mean_gap", "update_var_gap", "select_row_gap", "select_rank",
        "mix_gap", "broadcast_mismatch", "repeated_iterations"}
    assert c.per_layer and {m["name"] for m in c.end_to_end} == {
        "agent_iters_per_s", "setup_s"}
    for m in c.per_layer:
        assert callable(harness.load_metric(m["name"]).read)


def test_paths_hold_the_command_and_every_file():
    assert BENCH["paths"] == ["perfbench"]
    assert (ROOT / BENCH["command"][1]).is_file()
    for c in BENCH["configs"]:
        assert c["file"].startswith("perfbench/configs/")
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


def test_a_configuration_added_alone_is_found(tmp_path):
    """A later cell needs nothing but a config file and its entries."""
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((ROOT / "perfbench/configs/er1000.json").read_text())
    conf.update(name="er2048", n_agents=2048)
    (tmp_path / "perfbench/configs/er2048.json").write_text(json.dumps(conf))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "er2048", "source": "x",
                             "file": "perfbench/configs/er2048.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "er2048.pendulum", "config": "er2048",
                               "traffic": "pendulum", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("er2048.pendulum", root=tmp_path)
    assert cell.config["n_agents"] == 2048
    assert cell.traffic["task"] == "pendulum"
    names = {m["name"] for m in cell.per_layer}
    assert "step_mfu" in names and "rollout_ms" not in names
    assert harness.load_metric("step_mfu", root=tmp_path).read


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell")
