"""The harness's contract, driven with stand-ins: what a run prints, when
`correct` comes out false, and that cells, configurations, mixes and
per-layer metrics are found by name from files a later PR only adds."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from fakes import FakeDevice, FakeSystem
from test_reduce_trace import hand_trace

ROOT = run.ROOT
CELL = "sha256-lde8.closed-8k"
E2E = {"prove_s.p50", "prove_s.p90", "proofs_per_s", "hbm_peak_gib", "setup_s"}


def drive(capsys, system, *extra, trace=0, root=ROOT, workload=CELL):
    argv = ["--workload", workload, "--seed", "3000000007", "--seconds", "0.05",
            "--trace", str(trace), *extra]
    rc = run.main(argv, system=system, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out


@pytest.fixture
def hand_traced(monkeypatch):
    """The traced proves, without a profiler: the hand-built trace."""
    def traced(system, n, keep):
        runs = [run.one_prove(system) for _ in range(n)]
        return [w for w, _ in runs], [b for _, b in runs], hand_trace()
    monkeypatch.setattr(run, "traced_proves", traced)


def test_on_the_cpu_the_command_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""  # no result line, no metric
    assert '"correct": false' in p.stderr and "needs a tpu device" in p.stderr


def test_untraced_last_line_has_exactly_the_contracts_keys(capsys, tmp_path):
    rc, out = drive(capsys, FakeSystem(tmp_path))
    line = json.loads(out[-1])
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == E2E
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert line["metrics"]["hbm_peak_gib"]["value"] == 5.0
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 5 * 2**30}
    # each number compared is printed beside its limit, the sample count too
    assert any(ln.startswith("check verify_first: true (limit true)") for ln in out)
    assert any(ln.startswith("window: ") for ln in out)


def test_traced_last_line(capsys, tmp_path, hand_traced):
    rc, out = drive(capsys, FakeSystem(tmp_path), trace=1)
    line = json.loads(out[-1])
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert rc == 0 and line["correct"] is True and line["attempted"] == 3
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "breakdown"}
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(line["metrics"]) <= per_layer
    assert not set(line["metrics"]) & E2E
    for must in ("host.launches", "host.blocking_syncs", "commit.device_ms",
                 "other.device_ms", "device.idle_share", "setup.synthesis_s",
                 "kernel.lde_hbm_share"):
        assert must in line["metrics"], must
    # nothing in the hand trace matches the leaf or node modules' reader?
    # it does (leaf_digests): the rate is there, and no share of a peak
    assert line["metrics"]["kernel.poseidon2_perms_per_s"]["unit"] == "perms/s"
    assert line["metrics"]["host.launches"]["value"] == 5
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 5


def test_the_setup_is_generated_once_and_kept(capsys, tmp_path):
    s1, s2, s3 = FakeSystem(tmp_path), FakeSystem(tmp_path), FakeSystem(tmp_path)
    drive(capsys, s1)
    drive(capsys, s2)
    # the library goes through the pool every time (compile, or load); the
    # setup oracle's own kernels only where the setup is generated
    assert (s1.warmed, s2.warmed) == ([False], [True])
    assert (s1.saved, getattr(s1, "loaded", 0)) == (1, 0)
    assert (getattr(s2, "saved", 0), s2.loaded) == (0, 1)
    # the kept file is named by what it depends on: a changed configuration
    # or program generates its setup again and never reads a stale one
    s3.key = "k1"
    drive(capsys, s3)
    assert (s3.warmed, s3.saved, getattr(s3, "loaded", 0)) == ([False], 1, 0)


def test_a_damaged_proof_is_not_correct(capsys, tmp_path):
    """The control: after the run's own check, the same check is handed the
    window's proofs damaged and has to come out not correct, on a line of
    its own before the run's result."""
    rc, out = drive(capsys, FakeSystem(tmp_path), "--control", "truncate_opening")
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    control = json.loads([x for x in out if x.startswith("control {")][0][8:])
    assert control["control"] == "truncate_opening"
    assert control["correct"] is False and control["failed"] == control["attempted"]
    assert "control verify_first: false (limit true)" in out
    assert "check verify_first: true (limit true)" in out


class ControlThatCannotFail(FakeSystem):
    def damage(self, blob):
        return blob


def test_a_control_that_comes_out_correct_gives_no_result(capsys, tmp_path):
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.05",
                   "--trace", "0", "--control", "truncate_opening"],
                  system=ControlThatCannotFail(tmp_path))
    cap = capsys.readouterr()
    assert rc != 0 and "came out correct" in cap.err
    assert not cap.out.strip().splitlines()[-1].startswith("{")


class OneAnswerAltered(FakeSystem):
    """The timed path broken underneath: one prove of the window answers
    differently."""

    def prove(self):
        proof = super().prove()
        if self.proves == 4:  # two warm-ups, then the window's second
            proof["queries"][0] += 1
        return proof


class EveryAnswerWrong(FakeSystem):
    def prove(self):
        proof = super().prove()
        proof["values_at_z"][0][0] += 1
        return proof


class CompilesInTheWindow(FakeSystem):
    def prove(self):
        import jax
        import jax.numpy as jnp

        jax.jit(lambda x: x + self.proves)(jnp.ones(3)).block_until_ready()
        return super().prove()


@pytest.mark.parametrize("broken, said", [
    (OneAnswerAltered, "check proofs_differing_from_first: 1 (limit 0)"),
    (EveryAnswerWrong, "check verify_last: false (limit true)"),
    (CompilesInTheWindow, None),
])
def test_a_broken_timed_path_is_not_correct(capsys, tmp_path, broken, said):
    rc, out = drive(capsys, broken(tmp_path))
    line = json.loads(out[-1])
    assert line["correct"] is False
    if said:
        assert said in out
    else:
        ln = [x for x in out if x.startswith("check compile_requests_in_window")][0]
        assert int(ln.split(": ")[1].split(" ")[0]) > 0


def test_the_u64_fallback_is_not_correct(capsys, tmp_path):
    counters = {"quotient.coset_sweeps": 8, "fri.folds": 12, "limb.splits": 3}
    rc, out = drive(capsys, FakeSystem(tmp_path, counters=counters))
    assert json.loads(out[-1])["correct"] is False
    assert any("the resident kernels did not run" in ln for ln in out)


@pytest.mark.parametrize("devices, said", [
    ([FakeDevice(platform="cpu", kind="cpu")], "needs a tpu device"),
    ([], "needs a tpu device"),
    ([FakeDevice(kind="TPU v9 imaginary")], "not in peaks.json"),
])
def test_a_wrong_or_unknown_device_gives_no_result(capsys, tmp_path, devices, said):
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.05",
                   "--trace", "0"], system=FakeSystem(tmp_path, devices=devices))
    cap = capsys.readouterr()
    assert rc != 0 and cap.out.strip() == "" and said in cap.err


def test_unknown_device_kind_raises():
    with pytest.raises(run.BenchFailure):
        run.load_peaks("TPU v9 imaginary")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_fewer_chips_than_the_cell_asks_for():
    with pytest.raises(run.BenchFailure):
        run.require_devices([FakeDevice()], 4)


def test_an_unknown_workload_gives_no_result(capsys, tmp_path):
    rc, out = drive(capsys, FakeSystem(tmp_path), workload="no-such.cell")
    assert rc != 0 and out == []


def test_new_cells_are_new_files_and_new_entries_only(capsys, tmp_path, hand_traced):
    """A later PR adds a configuration, a circuit builder, a traffic mix, a
    per-layer metric and a cell: files of their own and entries in
    BENCHMARK.json. No file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _s, fs in os.walk(root) for f in fs
    }
    b = root / "benchmark"
    (b / "configs" / "keccak-lde4.json").write_text(json.dumps({
        "name": "keccak-lde4", "source": "a later PR", "chips": 1,
        "circuit": {"builder": "keccak", "params": {
            "copy_columns": 30, "constant_columns": 4, "constraint_degree": 4,
            "lookup_width": 3, "lookup_args": 4}},
        "proof_config": {"fri_lde_factor": 4, "merkle_tree_cap_size": 16,
                         "num_queries": 80, "pow_bits": 0, "fri_final_degree": 16,
                         "quotient_degree": None, "transcript": "poseidon2"},
        "reduced": [], "assumed": [], "guarantees": []}))
    (b / "circuits" / "keccak.py").write_text(
        "def build(params, seed):\n    return ('keccak', params, seed)\n")
    (b / "traffic" / "closed-4k.json").write_text(json.dumps({
        "name": "closed-4k", "loop": "closed", "clients": 1, "mesh": False,
        "request": {"message_bytes": 4096}}))
    (b / "layer_metrics" / "fri.folds.json").write_text(json.dumps({
        "name": "fri.folds", "unit": "count", "layer": "DEEP and FRI",
        "moves": "prove_s.p50", "better": "lower", "origin": "program_counter",
        "source": {"kind": "counter", "name": "fri.folds"}}))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "keccak-lde4", "source": "a later PR",
                             "file": "benchmark/configs/keccak-lde4.json",
                             "reduced": [], "why": "lookup-heavy"})
    bench["workloads"].append({"name": "keccak-lde4.closed-4k", "config": "keccak-lde4",
                               "traffic": "closed-4k", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "fri.folds", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "DEEP and FRI",
                               "moves": "prove_s.p50",
                               "workloads": ["keccak-lde4.closed-4k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.load_cell("keccak-lde4.closed-4k", str(root))
    assert cell["config"]["circuit"]["builder"] == "keccak"
    assert cell["traffic"]["request"] == {"message_bytes": 4096}
    assert "fri.folds" in {m["name"] for m in cell["per_layer"]}
    # the metric that lists only the new cell is not read in an old one
    assert "fri.folds" not in {m["name"] for m in run.load_cell(CELL, str(root))["per_layer"]}

    # the builder is found by its file name (the real adapter's look-up)
    from benchmark.system import BoojumSystem

    kind, params, seed = BoojumSystem.load_builder(cell).build(
        {**cell["config"]["circuit"]["params"], **cell["traffic"]["request"]}, 7)
    assert kind == "keccak" and params["message_bytes"] == 4096 and seed == 7

    rc, out = drive(capsys, FakeSystem(tmp_path), trace=1, root=str(root),
                    workload="keccak-lde4.closed-4k")
    line = json.loads(out[-1])
    assert rc == 0 and line["metrics"]["fri.folds"] == {"value": 12.0, "unit": "count"}
    after = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _s, fs in os.walk(root) for f in fs if "__pycache__" not in d
    }
    assert all(after[k] == v for k, v in before.items())
