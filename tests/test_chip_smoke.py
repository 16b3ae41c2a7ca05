"""chip_smoke.py's contract, kept cheap: nothing here proves.

The end-to-end rehearsals (a small example circuit through `run` on the
CPU with the device assertion steered, and the four-virtual-device mesh
phase) are made by hand — a prove costs minutes on XLA:CPU, and
tests/test_mesh_parity.py already pins shard_map against the meshless
prover.
"""

import argparse
import copy
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class _Dev:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def _opts(chips=1):
    return argparse.Namespace(sha_bytes=8192, chips=chips)


_NATIVE = {
    "quotient.coset_sweeps": 8, "quotient.resident_coset_sweeps": 8,
    "fri.folds": 12, "fri.resident_folds": 12,
    "ntt.resident_transforms": 4, "merkle.resident_commits": 9,
    "deep.resident_codewords": 1,
}
_PARITY = {
    "phase": "parity", "verify_native": True, "verify_xla": True,
    "proofs_equal": True, "native_counters": _NATIVE,
    "xla_counters": {"quotient.coset_sweeps": 8, "fri.folds": 12},
    "precompile_counters": {"precompile.kernels": 114}, "ledger_errors": [],
}
_MAIN = {
    "phase": "main", "verify": True, "warm_equals_cold": True,
    "precompile_counters": {"precompile.kernels": 59},
    "ledger_errors": [],
    "ledger_kernels": [
        "coset_sweep_terms_limbres", "wit:lde_mxu_limbres_b60_n65536_L8",
        "wit:leaf_digests_limbres", "node_layers_limbres",
        "fri_fold_limbres_k3_n524288",
    ],
    "last_warm_cache_misses": 0, "peak_bytes_in_use": 1 << 30,
    "warm_counters": _NATIVE,
}
_MESH = {
    "phase": "mesh", "verify_single": True, "verify_mesh": True,
    "proofs_equal": True,
    "mesh_counters": {"merkle.resident_commits": 4, "merkle.sm_commits": 4},
    "mesh_gauges": {"ici.all_to_all_bytes": 1.5e9},
    "peak_bytes_in_use_per_device": [1 << 30] * 4,
    "precompile_counters": {"precompile.kernels": 114}, "ledger_errors": [],
}


def _run(chips=1, n_devices=None, **overrides):
    """`run` fed recorded phase results; `overrides` maps a phase name to
    a function that damages a deep copy of its recorded line."""
    recorded = {"parity": _PARITY, "main": _MAIN, "mesh": _MESH}
    called = []

    def phase(name):
        def go():
            called.append(name)
            res = copy.deepcopy(recorded[name])
            if name in overrides:
                overrides[name](res)
            return res
        return go

    devices = [_Dev()] * (n_devices or chips)
    line = chip_smoke.run(
        _opts(chips), devices, {n: phase(n) for n in recorded}
    )
    return line, called


def test_cpu_run_fails_fast_and_loud():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "tpu" in last["error"]


def test_passing_last_line_is_exactly_the_contract(capsys):
    line, called = _run()
    assert called == ["parity", "main"]
    assert line == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    chip_smoke.emit(line)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )


def _drop(key, field="warm_counters"):
    def f(res):
        res[field].pop(key)
    return f


@pytest.mark.parametrize("phase,damage,needle", [
    ("main", _drop("quotient.resident_coset_sweeps"), "resident_coset_sweeps"),
    ("main", _drop("fri.resident_folds"), "fri.resident_folds"),
    ("main", _drop("ntt.resident_transforms"), "ntt.resident_transforms"),
    ("main", _drop("merkle.resident_commits"), "merkle.resident_commits"),
    ("main", _drop("deep.resident_codewords"), "deep.resident_codewords"),
    ("main", lambda r: r["warm_counters"].update({"limb.joins": 3}),
     "limb.joins"),
    ("main", lambda r: r["ledger_errors"].append(
        {"name": "node_layers_limbres", "error": "MosaicError('vmem')"}),
     "node_layers_limbres failed"),
    ("main", lambda r: r["precompile_counters"].update(
        {"precompile.compile_errors": 1}), "compile_errors"),
    ("main", lambda r: r["ledger_kernels"].remove("node_layers_limbres"),
     "names no 'node_layers_limbres'"),
    ("main", lambda r: r["ledger_kernels"].append("wit:leaf_digests"),
     "u64 twin"),
    ("main", lambda r: r.update(verify=False), "did not verify"),
    ("main", lambda r: r.update(last_warm_cache_misses=2), "cache misses"),
    ("parity", lambda r: r["ledger_errors"].append(
        {"name": "fri_commit_k3_n2048", "error": "XlaRuntimeError()"}),
     "fri_commit_k3_n2048 failed"),
    ("parity", lambda r: r.update(proofs_equal=False), "bytes differ"),
    ("parity", lambda r: r.update(verify_xla=False), "did not verify"),
    ("parity", _drop("fri.resident_folds", "native_counters"),
     "fri.resident_folds"),
])
def test_a_damaged_phase_result_fails_the_run(phase, damage, needle):
    line, called = _run(**{phase: damage})
    assert line["ok"] is False
    assert needle in line["error"], line
    assert line["device"]["platform"] == "tpu"
    # the run stops at the failing phase
    assert called[-1] == phase


def test_a_raising_phase_is_a_failure_not_a_crash():
    def boom(res):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    line, _ = _run(main=boom)
    assert line["ok"] is False and "RESOURCE_EXHAUSTED" in line["error"]


def test_chips_4_runs_only_the_mesh_phase():
    assert chip_smoke.plan(4, 8192) == ["mesh"]
    assert chip_smoke.plan(1, 8192) == ["parity", "main"]
    # the reduced default drops the parity phase and says so
    assert chip_smoke.plan(1, chip_smoke.DEFAULT_SHA_BYTES) == ["main"]
    line, called = _run(chips=4)
    assert called == ["mesh"]
    assert line == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }
    assert chip_smoke.parse_args(["--chips", "4"]).chips == 4
    assert chip_smoke.parse_args(["--chips", "4"]).sha_bytes == 8192
    assert chip_smoke.parse_args([]).sha_bytes == chip_smoke.DEFAULT_SHA_BYTES
    assert chip_smoke.parse_args(["--sha-bytes", "8192"]).sha_bytes == 8192


@pytest.mark.parametrize("damage,needle", [
    (lambda r: r.update(proofs_equal=False), "bytes differ"),
    (lambda r: r["mesh_gauges"].update({"ici.all_to_all_bytes": 0.0}),
     "all_to_all_bytes"),
    (lambda r: r["mesh_counters"].clear(), "resident_commits"),
    (lambda r: r["precompile_counters"].update(
        {"precompile.lower_errors": 2}), "lower_errors"),
    (lambda r: r.update(
        peak_bytes_in_use_per_device=[1 << 30, 0, 0, 0]), "every chip"),
])
def test_mesh_phase_checks(damage, needle):
    line, _ = _run(chips=4, mesh=damage)
    assert line["ok"] is False and needle in line["error"]


def test_chips_4_fails_with_fewer_devices():
    line, called = _run(chips=4, n_devices=1)
    assert called == []
    assert line["ok"] is False and "needs 4 devices" in line["error"]


_CACHE_PROBE = """
import os, sys, json
sys.path.insert(0, {repo!r})
out = {{}}
import jax
import boojum_tpu
out["package"] = jax.config.jax_compilation_cache_dir
import bench
out["bench"] = jax.config.jax_compilation_cache_dir
out["min_secs"] = jax.config.jax_persistent_cache_min_compile_time_secs
print(json.dumps(out))
"""


@pytest.mark.parametrize("preset", [None, "somewhere/else"])
def test_one_cache_rule(tmp_path, preset):
    """JAX's own variable wins untouched; unset, every entry point lands on
    <checkout>/.jax_cache — and importing bench moves nothing."""
    from boojum_tpu import compile_cache

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("BOOJUM_TPU_NO_COMPILE_CACHE", None)
    want = os.path.join(REPO, ".jax_cache")
    if preset:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / preset)
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=str(tmp_path),
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["package"] == want
    assert out["bench"] == want
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    if not preset and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # this very process came through conftest.py: same rule
        import jax

        assert jax.config.jax_compilation_cache_dir == want
