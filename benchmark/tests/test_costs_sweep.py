"""costs/sweep.py against a hand count at both configurations' shapes, and
the share it feeds: 100 % for a kernel that reads each coset evaluation once
and writes the quotient once at the published bandwidth, lower for any real
one. (A file of its own: a PR that adds a cost function edits no file the
benchmark already has.)"""

import json
import os

import pytest

from benchmark import layer_metrics
from benchmark.costs import shapes, sweep

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {
    "sha256-lde8.closed-8k": ("sha256-lde8", 1 << 16),
    "sha256-lde8.closed-1k": ("sha256-lde8", 1 << 14),
    "keccak256-era.closed-2k": ("keccak256-era", 1 << 18),
}


def cell_shapes(cell):
    config, n = CELLS[cell]
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        return shapes.prove_shapes(json.load(f), n)


def test_era_shapes_by_hand():
    s = cell_shapes("keccak256-era.closed-2k")
    # 130 copy + 8 x 3 specialized lookup columns + 1 multiplicity
    assert s["B_wit"] == 155
    # 154 columns under the copy permutation in chunks of 7 -> 22 chunks:
    # z + 21 partials + 8 lookup sub-arguments + 1 table = 31 ext = 62 base
    assert s["S"] == 62
    # degree 7 -> an 8-chunk quotient, committed at LDE 2
    assert (s["Q"], s["L"], s["B_q"]) == (8, 2, 16)
    assert s["N"] == 1 << 19 and s["cap"] == 32 and s["queries"] == 100


def test_sweep_bytes_by_hand():
    # 3 columns read and the quotient's 2 written, 4 rows, 2 cosets, 8 bytes
    assert sweep.sweep_bytes(3, 4, 2) == 8 * 5 * 4 * 2
    # sha256-lde8: 93 witness-oracle + 92 sigma + 46 stage-2 + 2 shifted
    s = cell_shapes("sha256-lde8.closed-8k")
    assert sweep.sweep_columns(s) == 233
    assert sweep.cost(s)["bytes"] == 8 * 235 * 65536 * 8 == 985661440
    assert sweep.cost(cell_shapes("sha256-lde8.closed-1k"))["bytes"] == 8 * 235 * 16384 * 8
    # keccak256-era: 155 + 154 + 62 + 2 columns on 8 cosets of 2^18 rows
    s = cell_shapes("keccak256-era.closed-2k")
    assert sweep.sweep_columns(s) == 373
    assert sweep.cost(s)["bytes"] == 8 * 375 * 262144 * 8 == 6291456000
    assert sweep.cost(s)["bound"] == "memory"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_sweep_share_cannot_pass_100(cell):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    s = cell_shapes(cell)
    floor_s = sweep.cost(s)["bytes"] / peaks["hbm_bytes_per_s"]
    spec = layer_metrics.load_metric("kernel.sweep_hbm_share")
    for slowdown in (1.0, 1.5, 40.0):
        trace = {
            "proves": 3, "chips": 1,
            "modules": [
                {"name": "jit_body_p(7)", "family": "sweep", "count": 24,
                 "seconds": 3 * floor_s * slowdown},
                # the coset evaluations are not the sweep
                {"name": "jit__coset_eval_q_p(3)", "family": "sweep",
                 "count": 96, "seconds": 1.0},
            ],
        }
        share = layer_metrics.read_metric(
            spec, {"trace": trace, "shapes": s, "peaks": peaks}
        )
        assert share == pytest.approx(100.0 / slowdown)
        assert share <= 100.0 + 1e-9


def test_new_readers_return_nothing_on_a_program_without_their_sources():
    """The parent commit has no quotient.sweep_barriers counter, and a trace
    may hold no lookup module (a circuit without lookups): the metric is
    left out of the line, nothing raises."""
    trace = {"proves": 3, "chips": 1, "modules": [
        {"name": "jit_fn(1)", "family": "sweep", "count": 3, "seconds": 0.1}]}
    ctx = {"trace": trace, "counters": {"quotient.coset_sweeps": 8},
           "shapes": cell_shapes("sha256-lde8.closed-8k"), "peaks": {}}
    for name in ("sweep.barriers", "lookup.device_ms", "kernel.sweep_hbm_share"):
        assert layer_metrics.read_metric(layer_metrics.load_metric(name), ctx) is None
    ctx["counters"]["quotient.sweep_barriers"] = 0
    assert layer_metrics.read_metric(
        layer_metrics.load_metric("sweep.barriers"), ctx) == 0.0
    trace["modules"] += [
        {"name": "jit__lookup_denominators_p(4)", "family": "sweep", "count": 3,
         "seconds": 0.03},
        {"name": "jit__lookup_denominators_inv_p(5)", "family": "sweep",
         "count": 3, "seconds": 0.06},
    ]
    assert layer_metrics.read_metric(
        layer_metrics.load_metric("lookup.device_ms"), ctx) == pytest.approx(30.0)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_lde_share_reads_the_lde_programs_of_every_size(cell):
    """`kernel.lde_hbm_share` is reported in every cell: at or below 2^16
    rows the commits' LDE is `_lde_planes`, above it the library runs it as
    a scale and the two programs of a forward transform, and gives all
    three `lde_planes` in their names. `families.json` gives them to the
    commit, and the coset evaluations' twins to the sweep."""
    from benchmark import reduce_trace
    from boojum_tpu.ntt import limb_ntt, mxu_ntt

    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    s = cell_shapes(cell)
    if s["n"] > 1 << mxu_ntt.MAX_LOG_N:
        programs = [limb_ntt._lde_planes_scale_p, *limb_ntt._LDE_FORWARD]
    else:
        programs = [mxu_ntt._lde_planes]
    names = [f"jit_{p.__name__}(1{i})" for i, p in enumerate(programs)]
    families = reduce_trace.load_families(os.path.join(BENCH, "families.json"))
    assert {reduce_trace.classify(n, "", families) for n in names} == {"commit"}
    assert {
        reduce_trace.classify(f"jit_{p.__name__}(7)", "", families)
        for p in (limb_ntt._coset_eval_scale_p, *limb_ntt._COSET_EVAL_FORWARD)
    } == {"sweep"}
    trace = {"proves": 3, "chips": 1, "modules": [
        {"name": n, "family": "commit", "count": 15, "seconds": 0.1}
        for n in names
    ] + [{"name": "jit__coset_eval_hybrid_mxu_p(5)", "family": "sweep",
          "count": 96, "seconds": 1.0}]}
    share = layer_metrics.read_metric(
        layer_metrics.load_metric("kernel.lde_hbm_share"),
        {"trace": trace, "shapes": s, "peaks": peaks},
    )
    from benchmark.costs import lde

    floor_s = lde.cost(s)["bytes"] / peaks["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * floor_s / (0.1 * len(names) / 3))
