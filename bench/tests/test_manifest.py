"""BENCHMARK.json against the contract's form, and the files it names
found by name."""
import json
import re

import pytest

from bench import configs, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = harness.load_manifest()


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in MAN["configs"]]
             + [w["name"] for w in MAN["workloads"]]
             + [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in MAN["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_bounds_and_sources():
    assert 1 <= MAN["run_seconds"] <= 51
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_metric_cell_reports_the_end_to_end_metric_it_moves():
    cells = [w["name"] for w in MAN["workloads"]]
    for m in MAN["per_layer"]:
        for cell in m.get("workloads", cells):
            e2e, _ = harness.cell_metrics(MAN, cell)
            assert m["moves"] in e2e, (m["name"], cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in MAN["workloads"]:
        e2e, layer = harness.cell_metrics(MAN, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]


def test_every_named_file_is_found_by_name():
    for c in MAN["configs"]:
        data = configs.load(c["name"])
        assert f"bench/configs/{c['name']}.json" == c["file"]
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
    for w in MAN["workloads"]:
        wl = harness.load_workload(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
    for m in MAN["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)


def test_a_workload_added_as_a_file_is_found_by_name(tmp_path):
    (tmp_path / "workloads").mkdir()
    wl = {"name": "extra.cell", "config": "internlm2-1.8b", "chips": 1}
    (tmp_path / "workloads" / "extra.cell.json").write_text(json.dumps(wl))
    assert harness.load_workload("extra.cell", tmp_path) == wl
    with pytest.raises(FileNotFoundError):
        harness.load_workload("missing.cell", tmp_path)


def test_a_metric_added_as_a_file_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "extra.metric.py").write_text(
        "def read(run):\n    return 4.0\n")
    assert harness.load_metric("extra.metric", tmp_path).read(None) == 4.0


def test_peaks_are_keyed_by_device_kind():
    p = harness.load_peaks("TPU v5 lite")
    assert p["flops_per_s"]["bfloat16"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
