"""BENCHMARK.json against the contract's limits, and the rule that the
harness is driven by data: no cell, configuration, mix or metric is named
in any .py file of the benchmark."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_limits():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert len(cfgs) == len(b["configs"]) and len(cells) == len(b["workloads"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(b["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            ROOT, b["paths"][0], "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in b["workloads"]} == set(cfgs)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= set(cells)
    names = set(e2e)
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        # each listed cell reports the end-to-end metric it should move
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", list(cells))
        reader = os.path.join(ROOT, b["paths"][0], "layer_metrics",
                              m["name"].split(".")[0] + ".py")
        assert os.path.exists(reader), reader
    for w in cells:     # every cell: setup_s, one more, and a per-layer metric
        assert any(w in m.get("workloads", [w]) for m in b["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(w in m["workloads"] for m in b["per_layer"])


def test_harness_is_driven_by_data():
    """No cell, configuration, traffic mix or metric name appears in any
    .py file of the benchmark outside its tests."""
    b = _bench()
    words = ({c["name"] for c in b["configs"]}
             | {w["name"] for w in b["workloads"]}
             | {w["traffic"] for w in b["workloads"]})
    bdir = os.path.join(ROOT, b["paths"][0])
    for dirpath, _, files in os.walk(bdir):
        if os.path.basename(dirpath) in ("tests", "__pycache__"):
            continue
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    text = f.read()
                for wd in words:
                    assert wd not in text, (fn, wd)
