"""BENCHMARK.json against the contract's own limits, and against the files
it names: a file outside them is refused before a single run."""

import json
import os
import re

import run
from benchmark import layer_metrics

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p) for p in b["paths"])
    assert len(b["command"]) <= 32 and all(line_ok(w) for w in b["command"])
    # a full check with all 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_files():
    b = bench()
    assert 1 <= len(b["configs"]) <= 24
    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"]) and line_ok(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"], "the configuration states its guarantees"
        assert os.path.exists(os.path.join(
            ROOT, b["paths"][0], "circuits", cfg["circuit"]["builder"] + ".py"))


def test_workloads():
    b = bench()
    assert 1 <= len(b["workloads"]) <= 24
    names = [w["name"] for w in b["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and line_ok(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, b["paths"][0], "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(names) // 2)


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert set(e2e) == {"prove_s.p50", "prove_s.p90", "proofs_per_s",
                        "hbm_peak_gib", "setup_s"}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    assert e2e["setup_s"]["bound"] == 0.25
    assert 1 <= len(b["per_layer"]) <= 128
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    layers = {}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line_ok(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        # the metric's own data file says the same
        spec = layer_metrics.load_metric(m["name"])
        assert (spec["unit"], spec["layer"], spec["moves"], spec["better"]) == (
            m["unit"], m["layer"], m["moves"], m["better"])
        assert spec["source"]["kind"] in layer_metrics.READERS
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), "one spelling per layer"
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for c in cells:
        assert sum(c in m.get("workloads", cells) for m in b["end_to_end"]) >= 2
        assert any(c in m.get("workloads", cells) for m in b["per_layer"])


def test_every_file_under_paths_has_an_allowed_name():
    b = bench()
    for p in b["paths"]:
        for d, _s, fs in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel
