"""BENCHMARK.json's cells resolve by name to their files, and every name
and unit keeps to the allowed characters."""
import json
import re

import pytest

import harness

BM = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BM["end_to_end"] + BM["per_layer"]


@pytest.mark.parametrize("wl", BM["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_by_name(wl):
    cfg = harness.config(wl["config"])
    harness.traffic(wl["traffic"])
    assert harness.system(cfg["system"]).Cell
    entry = harness.find(BM["configs"], wl["config"], "config")
    assert (harness.REPO / entry["file"]).is_file()
    e2e = harness.cell_metrics(BM, wl["name"], trace=False)
    per_layer = harness.cell_metrics(BM, wl["name"], trace=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and per_layer
    for m in e2e + per_layer:
        assert callable(harness.reader(m["name"]))
        assert m["moves"] in names if "moves" in m else True
    assert wl["chips"] == 1


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + [w["name"] for w in BM["workloads"]] \
        + [c["name"] for c in BM["configs"]]
    names += [w["config"] for w in BM["workloads"]]
    names += [w["traffic"] for w in BM["workloads"]]
    names += [k for c in BM["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_layers_are_named_once_each():
    for m in BM["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        assert {w["name"] for w in BM["workloads"]} >= set(m["workloads"])


def test_entries_have_exactly_the_contract_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BM["paths"][0] + "/")
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    texts = [e["why"] for e in BM["configs"] + BM["workloads"]]
    texts += [m["layer"] for m in BM["per_layer"]]
    texts += [c["source"] for c in BM["configs"]] + BM["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert 1 <= BM["run_seconds"] <= 51
