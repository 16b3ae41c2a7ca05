"""Each cost function against a hand count: at the two cells' shapes the
benchmark has files for, and at the same widths under LDE 2 and cap 32 (no
cell; the counts must follow the proof settings, not only the circuit)."""

import json
import os

import pytest

from benchmark.costs import lde, poseidon2, shapes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {
    "sha256-lde8.closed-8k": ({}, 1 << 16),
    "lde2-cap32.closed-8k": ({"fri_lde_factor": 2, "merkle_tree_cap_size": 32,
                              "num_queries": 100}, 1 << 16),
    "sha256-lde8.closed-1k": ({}, 1 << 14),
}


def cell_shapes(cell):
    settings, n = CELLS[cell]
    with open(os.path.join(BENCH, "configs", "sha256-lde8.json")) as f:
        config = json.load(f)
    config["proof_config"].update(settings)
    return shapes.prove_shapes(config, n)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_shapes_by_hand(cell):
    s = cell_shapes(cell)
    # 60 copy + 8 x 4 specialized lookup columns + 1 multiplicity
    assert s["B_wit"] == 93
    # 92 columns under the copy permutation in chunks of 7 -> 14 chunks:
    # z + 13 partials + 8 lookup sub-arguments + 1 table = 23 ext = 46 base
    assert s["S"] == 46
    # degree 7 -> rate 8, 8 quotient chunks in the extension field
    assert s["Q"] == 8 and s["B_q"] == 16
    assert s["N"] == s["n"] * s["L"]


def test_lde_bytes_by_hand():
    # one column of 4 rows at LDE 2: read 4, write 8 elements of 8 bytes
    assert lde.lde_bytes(1, 4, 2) == 8 * 12
    # cell 1: (93 + 46 + 16) columns x 65536 rows x (1 + 8) x 8 bytes
    assert lde.cost(cell_shapes("sha256-lde8.closed-8k"))["bytes"] == 155 * 65536 * 9 * 8 == 731381760
    assert lde.cost(cell_shapes("lde2-cap32.closed-8k"))["bytes"] == 155 * 65536 * 3 * 8
    assert lde.cost(cell_shapes("sha256-lde8.closed-1k"))["bytes"] == 155 * 16384 * 9 * 8


def test_poseidon2_perms_by_hand():
    assert poseidon2.leaf_perms(8, 10) == 10
    assert poseidon2.leaf_perms(9, 10) == 20
    assert poseidon2.node_perms(8, 2) == 6  # 4 + 2 nodes above 8 leaves
    # cell 1: N = 2^19 leaves; ceil(93/8) + ceil(46/8) + ceil(16/8) = 20
    # permutations a leaf over the three oracles; 3 trees of N - 16 nodes
    N = 1 << 19
    assert poseidon2.cost(cell_shapes("sha256-lde8.closed-8k"))["ops"] == 20 * N + 3 * (N - 16) == 12058576
    N = 1 << 17
    assert poseidon2.cost(cell_shapes("lde2-cap32.closed-8k"))["ops"] == 20 * N + 3 * (N - 32)
    assert poseidon2.cost(cell_shapes("sha256-lde8.closed-1k"))["ops"] == 20 * N + 3 * (N - 16)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_share_from_compulsory_bytes_cannot_pass_100(cell):
    """A kernel that reads each input once and writes each output once moves
    at least these bytes, so at the published bandwidth it takes at least
    bytes / peak seconds: the share is 100 % exactly there and lower for any
    real kernel."""
    from benchmark import layer_metrics

    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    s = cell_shapes(cell)
    floor_s = lde.cost(s)["bytes"] / peaks["hbm_bytes_per_s"]
    spec = layer_metrics.load_metric("kernel.lde_hbm_share")
    for slowdown in (1.0, 1.5, 40.0):
        trace = {
            "proves": 3, "chips": 1,
            "modules": [{"name": "jit__lde_planes(1)", "family": "commit",
                         "count": 9, "seconds": 3 * floor_s * slowdown}],
        }
        share = layer_metrics.read_metric(
            spec, {"trace": trace, "shapes": s, "peaks": peaks}
        )
        assert share == pytest.approx(100.0 / slowdown)
        assert share <= 100.0 + 1e-9
