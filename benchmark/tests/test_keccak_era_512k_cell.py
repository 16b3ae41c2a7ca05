"""The cell `keccak256-era-512k.closed-12k` (PR 39): its configuration
against the one it is cut from, its files through `load_cell`, its builder at
the traffic's size, its two cost functions against hand counts, and the two
`workloads` lists the PR gave to metrics that count a materialized prove.
It compares with no commit."""

import json
import os

import pytest

import run
from benchmark import layer_metrics
from benchmark.costs import lde, streamed_commit, streamed_lde
from benchmark.costs.shapes import prove_shapes

ROOT = run.ROOT
CELL = "keccak256-era-512k.closed-12k"
ACCEPTED = [
    "sha256-lde8.closed-8k", "sha256-lde8.closed-1k",
    "keccak256-era.closed-2k", "poseidon2-era.closed-tree64k",
    "recursive-verifier.closed-aggregate",
]


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_configuration_is_keccak256_era_but_for_the_trace():
    era, c = _config("keccak256-era"), _config("keccak256-era-512k")
    for key in ("circuit", "proof_config", "source_keys", "field", "chips",
                "assumed"):
        assert c[key] == era[key], key
    assert c["name"] == "keccak256-era-512k" and c["trace_len"] == 1 << 19
    assert c["reduced"] == ["trace_len"] and "2^20" in c["reduced_from"]["trace_len"]
    assert len(c["source"]) < 200 and "2^19" in c["source"]
    assert set(era["guarantees"]) < set(c["guarantees"])


def test_the_cells_files_load_and_its_metrics_have_readers():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"]["circuit"]["builder"] == "keccak256"
    assert cell["traffic"]["request"] == {"message_bytes": 12288}
    assert cell["traffic"]["loop"] == "closed" and cell["traffic"]["clients"] == 1
    assert cell["traffic"]["same_witness"] is True
    names = {m["name"] for m in cell["per_layer"]}
    assert {"commit.streamed_commits", "stream.regen_columns",
            "ntt.leading_outer_stages", "stream.commit_device_ms",
            "kernel.streamed_lde_hbm_share",
            "kernel.streamed_absorb_perms_per_s",
            "lookup.device_ms"} <= names  # 8 lookups of width 3: it runs
    # they count a materialized prove's one LDE pass and its leaf kernel
    assert not {"kernel.lde_hbm_share", "kernel.poseidon2_perms_per_s"} & names
    for name in names:
        spec = layer_metrics.load_metric(name, cell["bench_dir"])
        assert spec["source"]["kind"] in layer_metrics.READERS
    for m in cell["end_to_end"]:
        assert m["name"] in {"prove_s.p50", "prove_s.p90", "proofs_per_s",
                             "hbm_peak_gib", "setup_s"}


def test_the_two_materialized_metrics_list_the_five_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kernel.lde_hbm_share", "kernel.poseidon2_perms_per_s"):
        assert by_name[name]["workloads"] == ACCEPTED, name
    for name in ("stream.commit_device_ms", "kernel.streamed_lde_hbm_share",
                 "kernel.streamed_absorb_perms_per_s"):
        assert by_name[name]["workloads"] == [CELL], name
    # the lookup argument's modules run in this cell as in the 2^18 one
    assert by_name["lookup.device_ms"]["workloads"][-2:] == [
        "keccak256-era.closed-2k", CELL
    ]
    for name in ("commit.streamed_commits", "stream.regen_columns",
                 "ntt.leading_outer_stages"):
        assert "workloads" not in by_name[name], name  # 0 in the other cells


def test_costs_by_hand_at_the_cells_shapes():
    shapes = prove_shapes(run.load_cell(CELL)["config"], 1 << 19)
    assert (shapes["n"], shapes["L"], shapes["N"], shapes["Q"], shapes["cap"]) \
        == (1 << 19, 2, 1 << 20, 8, 32)
    # 130 + 8 x 3 + 1; z + 22 partials + 8 sub-arguments + 1 table, ext; 8 ext
    assert (shapes["B_wit"], shapes["S"], shapes["B_q"]) == (155, 62, 16)
    # three passes over 233 columns: n in, 2 n out, 8 bytes an element
    want = 3 * 233 * (1 << 19) * 3 * 8
    assert streamed_lde.cost(shapes) == {"bytes": want, "ops": 0, "bound": "memory"}
    assert want == 3 * lde.cost(shapes)["bytes"] == 8_795_455_488
    # absorbs: 20 + 8 + 2 chunks of 8 columns a leaf; nodes: N - 32 a tree
    N = 1 << 20
    assert streamed_commit.absorb_perms(155, N) == 20 * N
    assert streamed_commit.absorb_perms(62, N) == 8 * N
    assert streamed_commit.absorb_perms(16, N) == 2 * N
    assert streamed_commit.cost(shapes)["ops"] == 30 * N + 3 * (N - 32)
    # a ragged block that is not the last would cost more than whole rows do
    assert streamed_commit.absorb_perms(33, 1) == 4 + 1


def test_the_share_of_the_lde_modules_cannot_pass_its_floor():
    """`kernel.streamed_lde_hbm_share` through the reader: the plan's bytes
    at 819 GB/s over the lde_planes modules' time; the coset evaluations'
    twins belong to the sweep and are not read."""
    shapes = prove_shapes(run.load_cell(CELL)["config"], 1 << 19)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    trace = {"proves": 3, "chips": 1, "modules": [
        {"name": "jit__lde_planes_hybrid_outer_p(3)", "family": "commit",
         "count": 132, "seconds": 0.6},
        {"name": "jit__lde_planes_hybrid_fused_p(4)", "family": "commit",
         "count": 132, "seconds": 0.9},
        {"name": "jit__coset_eval_hybrid_fused_p(5)", "family": "sweep",
         "count": 200, "seconds": 3.0},
    ]}
    share = layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.streamed_lde_hbm_share"),
        {"trace": trace, "shapes": shapes, "peaks": peaks},
    )
    floor_s = streamed_lde.cost(shapes)["bytes"] / 819e9
    assert share == pytest.approx(100.0 * floor_s / 0.5)
    rate = layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.streamed_absorb_perms_per_s"),
        {"trace": {"proves": 3, "chips": 1, "modules": [
            {"name": "jit__absorb_cols_p(7)", "family": "commit", "count": 33,
             "seconds": 2.4},
            {"name": "jit_node_layers_planes(8)", "family": "commit",
             "count": 9, "seconds": 0.6}]},
         "shapes": shapes, "peaks": peaks},
    )
    assert rate == pytest.approx(streamed_commit.cost(shapes)["ops"] / 1.0)


@pytest.mark.slow  # 99 s in the sandbox: 91 permutations through the gadget
def test_builder_gives_2_19_rows_for_12288_bytes():
    from benchmark.system import BoojumSystem

    cell = run.load_cell(CELL)
    system = BoojumSystem()
    assert system.synthesize(cell, seed=2147539001) == 1 << 19
    assert len(system.asm.public_inputs) == 32
