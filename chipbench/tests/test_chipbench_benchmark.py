"""BENCHMARK.json: every cell resolves by name to its files, every name
and unit keeps to the allowed characters, and a cell can be added by
adding files."""
import json
import os
import re
import shutil

import pytest

from chipbench import HERE, ROOT, harness

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_its_files(workload):
    cell = harness.resolve(workload)
    assert cell.chips in (1, 4)
    assert cell.limits["widest_logit_gap"]["limit"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.metrics, "every cell reports a per-layer metric"
    for m in cell.metrics:
        assert m["moves"] in names
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"))
    assert os.path.isfile(os.path.join(
        HERE, "reference", cell.config["reference"] + ".py"))
    harness.arch_config(cell.config)          # the program accepts it


def test_names_and_units_keep_to_the_allowed_characters():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    names = [e["name"] for e in entries]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in BENCH[group]}) == len(BENCH[group])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_each_configuration_is_used_and_its_file_lies_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_rooflines_and_utilisations_are_percentages():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_an_added_cell_is_found_by_its_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "h2o-4b.extra", "config": "h2o-danube-3-4b",
         "traffic": "extra", "chips": 1, "why": "test"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError):
        harness.resolve("h2o-4b.extra", root=str(tmp_path))
    (tmp_path / "chipbench/traffic/extra.json").write_text(json.dumps(
        dict(harness.resolve(CELLS[0]).traffic, schedule_seed=9)))
    shutil.copy(tmp_path / f"chipbench/limits/{CELLS[0]}.json",
                tmp_path / "chipbench/limits/h2o-4b.extra.json")
    cell = harness.resolve("h2o-4b.extra", root=str(tmp_path))
    assert cell.traffic["schedule_seed"] == 9
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in BENCH["end_to_end"] if "workloads" not in m]
    with pytest.raises(KeyError):
        harness.resolve("h2o-4b.missing", root=str(tmp_path))
