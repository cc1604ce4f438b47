"""BENCHMARK.json against the rules it is checked by, and the harness
finding every configuration, mix and per-layer metric by name."""
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench"]
    assert MAN["command"][1] == "bench/run.py"
    assert (ROOT / "bench" / "run.py").is_file()


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_only_allowed_keys_and_names(section):
    names = [e["name"] for e in MAN[section]]
    assert len(names) == len(set(names))
    for e in MAN[section]:
        extra = set(e) - KEYS[section]
        assert extra <= ({"workloads"} if section in ("end_to_end",
                                                      "per_layer") else set())
        assert KEYS[section] <= set(e)
        assert NAME.match(e["name"]), e["name"]
        for text in ("why", "layer", "source"):
            if text in e:
                assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")


def test_configs_have_files_and_cells():
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert c["source"].startswith("https://")


def test_cells_find_config_traffic_and_driver():
    pairs = set()
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg = harness.config_of(ROOT, w["config"])
        traffic = harness.traffic_of(ROOT, w["traffic"])
        assert traffic["driver"] == cfg["driver"]
        assert hasattr(harness.driver_of(cfg["driver"]), "setup")
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(MAN["workloads"]) // 2)


def test_bounds_and_window():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MAN["end_to_end"])
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_cell_reports_enough():
    for w in MAN["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(MAN, w["name"],
                                                       False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(MAN, w["name"], True)


def test_per_layer_metrics_move_what_their_cells_report():
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", []):
            reported = {e["name"] for e in harness.cell_metrics(MAN, cell,
                                                                False)}
            assert m["moves"] in reported, (m["name"], cell)
        assert callable(harness.reader_of(ROOT, m["name"]))


def test_per_layer_readers_find_nothing_without_a_trace():
    run = harness.Run(trace=None, counters={}, peaks={})
    for m in MAN["per_layer"]:
        assert harness.reader_of(ROOT, m["name"])(run) is None


def test_a_new_metric_is_found_by_its_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads(json.dumps(MAN))
    cell = man["workloads"][0]["name"]
    man["per_layer"].append({"name": "dummy_ms", "unit": "ms",
                             "better": "lower", "source": "device_trace",
                             "layer": "device", "moves": "setup_s",
                             "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    (root / "bench" / "layers" / "dummy_ms.py").write_text(
        "def read(run):\n    return run.counters.get('dummy')\n")
    loaded = harness.manifest(root)
    names = [m["name"] for m in harness.cell_metrics(loaded, cell, True)]
    assert "dummy_ms" in names
    read = harness.reader_of(root, "dummy_ms")
    assert read(harness.Run(None, {"dummy": 2.5})) == 2.5
    assert read(harness.Run(None, {})) is None
