"""The cell `sha256-blake2s-lde8.closed-8k` (PR 42): its configuration
against `sha256-lde8`, the one it differs from by its hashes alone, its
files through `load_cell`, its cost function against a hand count, its four
metrics' files against the programs the library lists under a Blake2s key,
and the `workloads` lists the PR appended to or gave. It compares with no
commit."""

import json
import os
import re

import run
from benchmark import layer_metrics
from benchmark.costs import blake2s
from benchmark.costs.shapes import prove_shapes

ROOT = run.ROOT
CELL = "sha256-blake2s-lde8.closed-8k"
ACCEPTED = [
    "sha256-lde8.closed-8k", "sha256-lde8.closed-1k",
    "keccak256-era.closed-2k", "poseidon2-era.closed-tree64k",
    "recursive-verifier.closed-aggregate", "keccak256-era-512k.closed-12k",
]
NEW_METRICS = [
    "commit.hash_device_ms", "kernel.blake2s_compressions_per_s",
    "kernel.blake2s_hbm_share", "merkle.blake2s_compressions",
]


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_is_sha256_lde8_but_for_its_hashes():
    anchor, c = _config("sha256-lde8"), _config("sha256-blake2s-lde8")
    differ = {k for k in set(anchor) | set(c) if anchor.get(k) != c.get(k)}
    assert differ == {
        "name", "source", "proof_config", "field", "assumed", "assumed_why",
    }
    pc_a, pc = anchor["proof_config"], c["proof_config"]
    assert {k for k in set(pc_a) | set(pc) if pc_a.get(k) != pc.get(k)} == {
        "transcript", "tree_hasher",
    }
    assert pc["transcript"] == pc["tree_hasher"] == "blake2s"
    assert c["reduced"] == [] and c["guarantees"] == anchor["guarantees"]
    assert c["assumed"] == ["num_queries", "tree_hasher_rule", "cap_absorption"]
    assert len(c["source"]) < 200
    assert "run_sha256_prover_non_recursive" in c["source"]
    entry, = [e for e in _bench()["configs"] if e["name"] == c["name"]]
    assert entry["source"] == c["source"] and entry["reduced"] == []


def test_the_cells_files_load_and_its_metrics_have_readers():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"]["circuit"]["builder"] == "sha256"
    assert cell["traffic"]["request"] == {"message_bytes": 8192}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) | {"kernel.lde_hbm_share", "lookup.device_ms"} <= names
    # Poseidon2 permutations: nothing to count under the Blake2s hasher
    assert not {"host.transcript_perms", "host.transcript_native_perms",
                "kernel.poseidon2_perms_per_s"} & names
    for name in names:
        spec = layer_metrics.load_metric(name, cell["bench_dir"])
        assert spec["source"]["kind"] in layer_metrics.READERS
    assert {m["name"] for m in cell["end_to_end"]} == {
        "prove_s.p50", "prove_s.p90", "proofs_per_s", "hbm_peak_gib", "setup_s",
    }


def test_the_workloads_lists_appended_to_and_given():
    by_name = {m["name"]: m for m in _bench()["per_layer"]}
    for name in ("kernel.lde_hbm_share", "lookup.device_ms"):
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in ("host.transcript_perms", "host.transcript_native_perms"):
        assert by_name[name]["workloads"] == ACCEPTED, name
    assert by_name["commit.hash_device_ms"]["workloads"] == [
        "sha256-lde8.closed-8k", CELL,
    ]
    for name in NEW_METRICS[1:]:
        assert by_name[name]["workloads"] == [CELL], name
    assert [m["name"] for m in _bench()["per_layer"]][-4:] == NEW_METRICS
    assert _bench()["workloads"][-1]["name"] == CELL


def test_cost_by_hand_at_the_cells_shapes():
    shapes = prove_shapes(run.load_cell(CELL)["config"], 1 << 16)
    N = 1 << 19
    assert (shapes["N"], shapes["cap"]) == (N, 16)
    assert (shapes["B_wit"], shapes["S"], shapes["B_q"]) == (93, 46, 16)
    cost = blake2s.cost(shapes)
    # 12 + 6 + 2 blocks a leaf; N - 16 nodes a tree
    assert cost["ops"] == N * 20 + 3 * (N - 16) == 12_058_576
    # 155 columns read once, 8 bytes an element; 32 bytes a leaf and a node
    assert cost["bytes"] == 8 * 155 * N + 32 * 3 * (2 * N - 16)
    assert cost["bound"] == "arithmetic"
    assert blake2s.leaf_compressions(8, 1) == 1  # a full final block
    assert blake2s.leaf_compressions(9, 1) == 2


def test_the_metrics_name_programs_the_library_lists_under_a_blake2s_key():
    """On the 2^10 toy circuit (the SHA-256 library takes minutes to
    enumerate): the names the metrics search for are the jitted functions'
    own, under a Blake2s key and under no other."""
    import dataclasses
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from proving import fma_assembly, small_config

    from boojum_tpu.prover import enumerate_kernels

    asm = fma_assembly()
    cfg = dataclasses.replace(
        small_config(), tree_hasher="blake2s", transcript="blake2s"
    )

    def programs(c):
        return {"jit_" + getattr(s.fn, "__name__", "") for s in enumerate_kernels(asm, c)}

    b2s, plain = programs(cfg), programs(small_config())
    for name in NEW_METRICS:
        spec = layer_metrics.load_metric(name)
        assert spec["unit"] and spec["layer"] and spec["moves"] == "prove_s.p50"
        module = spec["source"].get("module")
        if module is None:
            assert spec["source"] == {
                "kind": "counter", "name": "merkle.blake2s_compressions",
            }
            continue
        hit = {p for p in b2s if re.search(module, p)}
        assert len(hit) == 2, (name, hit)  # the leaf and the node program
        if "blake2s" in module:
            assert not {p for p in plain if re.search(module, p)}, name
        else:  # the A/B of the two hashers: the Poseidon2 twins match too
            assert len({p for p in plain if re.search(module, p)}) == 2


def test_the_share_reads_under_100_at_the_predicted_time():
    shapes = prove_shapes(run.load_cell(CELL)["config"], 1 << 16)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    trace = {"proves": 3, "chips": 1, "modules": [
        {"name": "jit_leaf_digests_blake2s_planes(7)", "family": "commit",
         "count": 9, "seconds": 0.09},
        {"name": "jit_node_layers_blake2s_planes(8)", "family": "commit",
         "count": 9, "seconds": 0.06},
        {"name": "jit__fri_oracle_blake2s_p(9)", "family": "fri",
         "count": 12, "seconds": 0.5},
    ]}
    ctx = {"trace": trace, "shapes": shapes, "peaks": peaks}
    share = layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.blake2s_hbm_share"), ctx)
    assert abs(share - 100 * (750_778_880 / 819e9) / 0.05) < 1e-9
    assert 0 < share < 100
    rate = layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.blake2s_compressions_per_s"), ctx)
    assert abs(rate - 12_058_576 / 0.05) < 1e-3
    ms = layer_metrics.read_metric(
        layer_metrics.load_metric("commit.hash_device_ms"), ctx)
    assert abs(ms - 50.0) < 1e-9
    # a Poseidon2-tree trace has nothing for the Blake2s readers to read
    trace["modules"] = [
        {"name": "jit_leaf_digests_planes(7)", "family": "commit",
         "count": 9, "seconds": 0.9},
    ]
    assert layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.blake2s_hbm_share"), ctx) is None
    assert layer_metrics.read_metric(
        layer_metrics.load_metric("commit.hash_device_ms"), ctx) == 300.0
