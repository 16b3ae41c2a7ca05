"""The control, kept at a size a test run can hold: the REAL prover and the
REAL verifier on the CPU, a 2^9-row lookup circuit added to a copy of the
benchmark as new files. The harness's look for a chip is skipped (a stand-in
device) and, because the CPU runs the u64 path, the native-path counters are
steered HERE, in the test; everything else is a run as the driver makes it.

Slow (XLA:CPU compiles the toy library: about two minutes cold); run by hand:
    pytest benchmark/tests/test_control.py
On the chip the control runs at the cell's own size with
`run.py --control truncate_opening` (PERF.md has the readings).
"""

import json
import os
import shutil

import pytest

import run
from fakes import NATIVE_COUNTERS, FakeDevice

ROOT = run.ROOT
CELL = "xor-tiny.closed-tiny"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "benchmark"
    (b / "circuits" / "xor.py").write_text(
        "def build(params, seed):\n"
        "    from boojum_tpu.examples import build_xor_lookup_circuit\n"
        "    cs, _acc, _out = build_xor_lookup_circuit(\n"
        "        num_lookups=int(params['num_lookups']), seed=int(seed) % 2**32)\n"
        "    return cs\n")
    (b / "configs" / "xor-tiny.json").write_text(json.dumps({
        "name": "xor-tiny", "source": "boojum_tpu.examples", "chips": 1,
        "circuit": {"builder": "xor", "params": {
            "copy_columns": 8, "constant_columns": 6, "constraint_degree": 4,
            "lookup_width": 3, "lookup_args": 2}},
        "proof_config": {"fri_lde_factor": 4, "merkle_tree_cap_size": 4,
                         "num_queries": 10, "pow_bits": 0, "fri_final_degree": 8,
                         "quotient_degree": None, "transcript": "poseidon2"},
        "reduced": [], "assumed": [], "guarantees": []}))
    (b / "traffic" / "closed-tiny.json").write_text(json.dumps({
        "name": "closed-tiny", "loop": "closed", "clients": 1, "mesh": False,
        "request": {"num_lookups": 300}}))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "xor-tiny", "source": "boojum_tpu.examples",
                             "file": "benchmark/configs/xor-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "xor-tiny",
                               "traffic": "closed-tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def tiny_system(break_prove=False):
    from benchmark.system import BoojumSystem

    class Tiny(BoojumSystem):
        def start(self):
            super().start()
            return [FakeDevice()]

        def recorded_prove(self):
            proof, counters = super().recorded_prove()
            return proof, {**counters, **NATIVE_COUNTERS}

        def prove(self):
            proof = super().prove()
            if break_prove:
                # an answer altered where it is produced: one opened value
                c0, c1 = proof.values_at_z[0]
                proof.values_at_z[0] = (int(c0) ^ 1, c1)
            return proof

    return Tiny()


def drive(capsys, root, system, seed, *extra):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0", *extra],
                  system=system, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out, json.loads(out[-1])


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_sound_runs_are_correct_and_their_controls_are_not(capsys, tiny_root, seed):
    rc, out, line = drive(capsys, tiny_root, tiny_system(), seed,
                          "--control", "truncate_opening")
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    control = json.loads([x for x in out if x.startswith("control {")][0][8:])
    assert control["correct"] is False and control["failed"] == control["attempted"]
    assert "check verify_first: true (limit true)" in out
    assert "control verify_first: false (limit true)" in out


def test_a_timed_path_broken_underneath_is_not_correct(capsys, tiny_root):
    rc, out, line = drive(capsys, tiny_root, tiny_system(break_prove=True), 13)
    assert line["correct"] is False and line["failed"] == line["attempted"]
    assert "check proofs_differing_from_first: 0 (limit 0)" in out
    assert "check verify_first: false (limit true)" in out
