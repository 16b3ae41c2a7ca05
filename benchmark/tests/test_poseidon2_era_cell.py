"""The cell `poseidon2-era.closed-tree64k` (PR 32): its yardstick against
the program it measures, its files through `load_cell`, and what the PR that
brought it changed of the benchmark: new files and appended entries, but for
the one `workloads` list on `lookup.device_ms` (a cell with no lookup
argument has no lookup module to read)."""

import json
import os
import subprocess

import run
from benchmark import layer_metrics
from benchmark.costs import poseidon2_gate
from benchmark.costs.shapes import prove_shapes

ROOT = run.ROOT
CELL = "poseidon2-era.closed-tree64k"
PARENT = "6d19b460d8d9cdd2db8490c67987b52cd108c0b4"


def test_cost_counts_the_multiplications_of_the_gates_own_program():
    """The yardstick is counted from the permutation's structure; the
    program the sweep evaluates is captured from the gate. They agree, so
    neither can drift unseen."""
    from boojum_tpu.cs.gate_capture import capture_gate_program
    from boojum_tpu.cs.gates import Poseidon2FlattenedGate

    gate = Poseidon2FlattenedGate.instance()
    prog = capture_gate_program(gate)
    muls = sum(1 for op, *_rest in prog.ops if op == "mul")
    assert muls == poseidon2_gate.gate_muls_per_row() == 736
    assert len(prog.terms) == gate.num_terms == poseidon2_gate.terms() == 118
    assert poseidon2_gate.accumulation_muls_per_row() == 2 * len(prog.terms)
    assert poseidon2_gate.muls_per_row() == muls + 236 == 972


def test_cost_is_rows_times_cosets():
    cell = run.load_cell(CELL)
    shapes = prove_shapes(cell["config"], 1 << 18)
    assert (shapes["n"], shapes["Q"], shapes["L"]) == (1 << 18, 8, 2)
    # no lookup argument: 130 witness-oracle columns, z and 18 partial
    # products in stage 2
    assert (shapes["B_wit"], shapes["S"], shapes["B_q"]) == (130, 38, 16)
    cost = poseidon2_gate.cost(shapes)
    assert cost["ops"] == 972 * (1 << 18) * 8
    assert cost["bound"] == "arithmetic" and cost["bytes"] == 0


def test_the_cells_files_load_and_its_metrics_have_readers():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"]["circuit"]["builder"] == "poseidon2_tree"
    assert cell["traffic"]["request"] == {"leaves": 65536, "leaf_elements": 16}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"sweep.body_device_ms", "sweep.gate_ops_per_row",
            "kernel.gate_sweep_muls_per_s", "kernel.sweep_hbm_share"} <= names
    assert "lookup.device_ms" not in names
    for name in names:
        spec = layer_metrics.load_metric(name, cell["bench_dir"])
        assert spec["source"]["kind"] in layer_metrics.READERS
    # the three older cells read every metric they read before, and the two
    # new ones that every cell reports
    for other in ("sha256-lde8.closed-8k", "sha256-lde8.closed-1k",
                  "keccak256-era.closed-2k"):
        theirs = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert "lookup.device_ms" in theirs
        assert {"sweep.body_device_ms", "sweep.gate_ops_per_row"} <= theirs
        assert "kernel.gate_sweep_muls_per_s" not in theirs


def test_new_metrics_read_nothing_from_a_program_without_them():
    """On the parent commit the counter does not exist: the reader returns
    None and the line leaves the metric out."""
    spec = layer_metrics.load_metric("sweep.gate_ops_per_row")
    assert layer_metrics.read_metric(spec, {"counters": {"fri.folds": 12}}) is None
    assert layer_metrics.read_metric(
        spec, {"counters": {"quotient.gate_ops_per_row": 2036}}) == 2036.0


def _parent(path):
    try:
        return subprocess.run(
            ["git", "show", f"{PARENT}:{path}"], cwd=ROOT, check=True,
            capture_output=True,
        ).stdout
    except (OSError, subprocess.CalledProcessError):
        return None


def test_the_pr_added_files_and_entries_and_one_workloads_list():
    """Against the parent commit, where git has it: no file under
    benchmark/ that was there is changed, and BENCHMARK.json differs by
    entries appended at the end of their lists and by `lookup.device_ms`
    getting the list of the three cells that have a lookup argument."""
    import pytest

    listing = _parent("BENCHMARK.json")
    if listing is None:
        pytest.skip("the parent commit is not in this checkout's git")
    before, now = json.loads(listing), json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(before) == set(now)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert before[key] == now[key]
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 3)):
        assert len(now[key]) == len(before[key]) + added
        for old, new in zip(before[key], now[key]):
            if old.get("name") == "lookup.device_ms":
                assert new == {**old, "workloads": [
                    "sha256-lde8.closed-8k", "sha256-lde8.closed-1k",
                    "keccak256-era.closed-2k"]}
            else:
                assert old == new
    files = subprocess.run(
        ["git", "ls-tree", "-r", "--name-only", PARENT, "benchmark"], cwd=ROOT,
        check=True, capture_output=True, text=True,
    ).stdout.split()
    for path in files:
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == _parent(path), f"{path} was edited"
