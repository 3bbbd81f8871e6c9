"""BENCHMARK.json against the benchmark's contract, and every cell, mix,
entry, limit and metric found by name."""
import importlib
import json
import re

import pytest

from benchmarks.chip import check, harness
from benchmarks.chip.peaks import peaks_for

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")


def test_budget_fits_a_full_check():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_entries_have_only_contract_keys_and_names():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmarks/chip/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES


def test_bounds_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_each_per_layer_cell_reports_what_the_metric_moves():
    for m in BENCH["per_layer"]:
        cells = m.get("workloads", [w["name"] for w in BENCH["workloads"]])
        for c in cells:
            e2e = {e["name"] for e in harness.cell_metrics(BENCH, c,
                                                            "end_to_end")}
            assert m["moves"] in e2e, (m["name"], c)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    cfg = harness.config_file(BENCH, w["config"])
    traffic = harness.traffic_file(w["traffic"])
    limits = harness.limits_file(w["name"])
    harness.entry(traffic["entry"])
    importlib.import_module(f"benchmarks.chip.references.{cfg['reference']}")
    for name in check.NUMBERS:
        assert limits[name]["limit"] is not None
    e2e = harness.cell_metrics(BENCH, w["name"], "end_to_end")
    per = harness.cell_metrics(BENCH, w["name"], "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert per
    for m in per:
        assert callable(harness.metric_reader(m["name"]))


def test_configs_used_and_files_distinct():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_reduced_keys_hold_the_published_value():
    for c in BENCH["configs"]:
        cfg = harness.config_file(BENCH, c["name"])
        assert set(c["reduced"]) == set(cfg["reduced_from_source"])


def test_peaks_table_refuses_an_unknown_device_kind():
    assert peaks_for("TPU v5 lite").flops_per_s == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
