"""BENCHMARK.json against the limits of the benchmark's contract that a
file can be checked for, and against the files it names."""

import json
import os
import re

from conftest import BENCH_DIR, ROOT
from tvtbench.spec import Cell, available, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fp:
        return json.load(fp)


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32 and all(map(line_ok, b["command"]))
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"])
        assert line_ok(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as fp:
            doc = json.load(fp)
        assert doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert 2 <= len(b["workloads"]) <= 24
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}


def test_every_name_has_its_file_and_every_cell_its_metrics():
    b = bench()
    readers = set(available("layer_metrics", ".py"))
    assert {m["name"] for m in b["per_layer"]} <= readers
    assert {m["name"] for m in b["end_to_end"]} \
        <= set(available("end_to_end", ".py"))
    used_configs = set()
    for w in b["workloads"]:
        cell = Cell(w["name"], ROOT)
        used_configs.add(w["config"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert str(cell.chips) in cell.config["env_by_chips"]
        for m in cell.per_layer:
            assert callable(load_module("layer_metrics", m["name"]).read)
    assert used_configs == {c["name"] for c in b["configs"]}
    # nothing under benchmark/ but names made of a name's characters
    for d, _s, files in os.walk(BENCH_DIR):
        if "__pycache__" in d:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
