"""The cell `recursive-verifier.closed-aggregate` (PR 34): its configuration
against the two it is made of (`poseidon2-era.json`'s outer proof settings,
`sha256-lde8.json`'s inner ones), its recorded proofs against the sha256 the
configuration names, its yardstick against a hand count and against the
programs the sweep traces, and its files through `load_cell`. Nothing here
counts `BENCHMARK.json`'s entries or names a commit (PERF.md, Open question
12)."""

import hashlib
import json
import os

import run
from benchmark import layer_metrics
from benchmark.costs import poseidon2_gate, recursion_gate
from benchmark.costs.shapes import prove_shapes

ROOT = run.ROOT
BENCH = run.BENCH
CELL = "recursive-verifier.closed-aggregate"


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _builder():
    from benchmark.system import BoojumSystem

    return BoojumSystem.load_builder(run.load_cell(CELL))


def test_outer_settings_are_poseidon2_eras_letter_for_letter():
    mine, era = _config("recursive-verifier"), _config("poseidon2-era")
    assert mine["proof_config"] == era["proof_config"]
    widths = ("copy_columns", "witness_columns", "constant_columns",
              "constraint_degree", "lookup_width", "lookup_args")
    for key in widths:
        assert mine["circuit"]["params"][key] == era["circuit"]["params"][key]
    assert mine["reduced"] == era["reduced"] == ["trace_len"]
    assert mine["circuit"]["params"]["trace_len"] == 1 << 18
    assert mine["chips"] == 1 and mine["architecture"] is None
    assert "gate_mix" not in mine["assumed"]
    for key in ("source", "deployment", "reduced_why", "assumed_why"):
        assert mine[key]
    assert len(mine["guarantees"]) >= 7


def test_inner_settings_are_read_from_sha256_lde8_and_repeated_nowhere():
    """The builder takes the inner widths, request and ProofConfig from the
    accepted cell's own files; the configuration names the cell and no
    number of it."""
    cell = run.load_cell(CELL)
    inner = cell["traffic"]["request"]["inner"]
    assert inner == "sha256-lde8.closed-8k"
    params, proof_config, builder = _builder().inner_cell(inner)
    anchor = run.load_cell(inner)
    assert builder == anchor["config"]["circuit"]["builder"] == "sha256"
    assert proof_config == anchor["config"]["proof_config"]
    assert params == {**anchor["config"]["circuit"]["params"],
                      **anchor["traffic"]["request"]}
    assert (params["copy_columns"], params["lookup_args"], params["lookup_width"],
            params["message_bytes"]) == (60, 8, 4, 8192)
    mine = cell["config"]["circuit"]["params"]
    assert not {"fri_lde_factor", "num_queries", "merkle_tree_cap_size",
                "message_bytes"} & set(mine)


def test_recorded_proofs_are_the_files_the_configuration_names():
    cfg = _config("recursive-verifier")
    params, recorded = cfg["circuit"]["params"], cfg["recorded_proofs"]
    assert recorded["tool"] == "benchmark/tools/record_inner_proofs.py"
    assert os.path.exists(os.path.join(ROOT, recorded["tool"]))
    assert sorted(recorded["sha256"]) == sorted(params["recorded"])
    assert [f"inner.{s}.json.gz" for s in recorded["seeds"]] == params["recorded"]
    for name, want in recorded["sha256"].items():
        path = os.path.join(BENCH, params["recorded_dir"], name)
        with open(path, "rb") as f:
            blob = f.read()
        assert hashlib.sha256(blob).hexdigest() == want, name
        assert len(blob) < 1 << 20, "a recorded proof is well under 1 MB"


def test_recorded_keys_carry_the_inner_cells_settings():
    """Reading the files costs a second; the host verifier over them is the
    builder's, on the chip and in tier-1's small size."""
    b = _builder()
    cfg = _config("recursive-verifier")
    params = cfg["circuit"]["params"]
    _p, inner, _b = b.inner_cell("sha256-lde8.closed-8k")
    caps = set()
    for name, seed in zip(params["recorded"], cfg["recorded_proofs"]["seeds"]):
        vk, proof, meta = b.read_recorded(
            os.path.join(BENCH, params["recorded_dir"], name))
        assert meta["seed"] == seed and meta["inner"] == "sha256-lde8.closed-8k"
        assert vk.trace_len == meta["trace_len"] == 1 << 16
        assert (vk.fri_lde_factor, vk.cap_size, vk.num_queries, vk.pow_bits,
                vk.fri_final_degree, vk.transcript) == (
            inner["fri_lde_factor"], inner["merkle_tree_cap_size"],
            inner["num_queries"], inner["pow_bits"], inner["fri_final_degree"],
            inner["transcript"])
        assert len(proof.queries) == 50 and len(proof.witness_cap) == 16
        assert hashlib.sha256(proof.to_json().encode()).hexdigest() == (
            meta["proof_sha256"])
        caps.add(json.dumps(vk.setup_merkle_cap))
    assert len(caps) == 1, "one key for every recorded proof"


def test_measured_fill_is_what_the_traffic_says():
    cfg, cell = _config("recursive-verifier"), run.load_cell(CELL)
    m = cfg["measured"]
    assert m["inner_proofs"] == cell["traffic"]["request"]["inner_proofs"] >= 8
    assert m["trace_len"] == cfg["circuit"]["params"]["trace_len"]
    assert abs(m["fill"] - m["rows"] / m["trace_len"]) < 1e-3 and m["fill"] >= 0.5
    # one more inner proof would not fit
    assert m["rows"] + m["rows_each_further_inner_proof"] > m["trace_len"]


def test_cost_against_a_hand_count():
    """130 columns: 32 fma, 130 constant, 130 boolean, 26 reduction, 32
    selection and 26 conditional-swap instances beside one permutation."""
    relation = 32 * 3 + 130 * 0 + 130 * 1 + 26 * 4 + 32 * 1 + 26 * 1
    terms = 32 + 130 + 130 + 26 + 32 + 26 * 2
    assert (relation, terms) == (388, 402)
    assert recursion_gate.narrow_muls_per_row(130) == relation + 2 * terms == 1192
    assert recursion_gate.muls_per_row(130) == 1192 + 972 == 2164
    assert poseidon2_gate.muls_per_row() == 972
    cell = run.load_cell(CELL)
    shapes = prove_shapes(cell["config"], 1 << 18)
    assert (shapes["n"], shapes["Q"], shapes["L"], shapes["B_wit"]) == (
        1 << 18, 8, 2, 130)
    cost = recursion_gate.cost(shapes)
    assert cost == {"ops": 2164 * (1 << 18) * 8, "bytes": 0, "bound": "arithmetic"}


def test_cost_counts_the_multiplications_of_the_gates_own_programs():
    """The yardstick is counted from the gates' definitions; the programs the
    sweep traces are captured from the gates. They agree gate by gate."""
    from boojum_tpu.cs import gates as G
    from boojum_tpu.cs.gate_capture import capture_gate_program

    by_name = {
        "fma": G.FmaGate, "constant": G.ConstantsAllocatorGate,
        "boolean": G.BooleanConstraintGate, "reduction4": G.ReductionGate,
        "selection": G.SelectionGate, "conditional_swap": G.ConditionalSwapGate,
    }
    assert set(by_name) == set(recursion_gate.NARROW_GATES)
    for name, (width, muls, terms) in recursion_gate.NARROW_GATES.items():
        gate = by_name[name].instance()
        prog = capture_gate_program(gate)
        assert gate.name == name and gate.principal_width == width
        assert sum(1 for op, *_rest in prog.ops if op == "mul") == muls, name
        assert len(prog.terms) == gate.num_terms == terms, name


def test_the_cells_files_load_and_its_metrics_have_readers():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["config"]["circuit"]["builder"] == "recursive_verifier"
    assert cell["traffic"]["loop"] == "closed" and cell["traffic"]["clients"] == 1
    assert cell["traffic"]["mesh"] is False and cell["traffic"]["same_witness"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"sweep.selector_gates", "kernel.verifier_sweep_muls_per_s",
            "sweep.body_device_ms", "sweep.gate_ops_per_row",
            "kernel.sweep_hbm_share", "setup.synthesis_s"} <= names
    assert not {"lookup.device_ms", "kernel.gate_sweep_muls_per_s"} & names
    for name in names:
        spec = layer_metrics.load_metric(name, cell["bench_dir"])
        assert spec["source"]["kind"] in layer_metrics.READERS
    bench = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        theirs = {m["name"] for m in run.load_cell(w["name"])["per_layer"]}
        assert "sweep.selector_gates" in theirs
        assert ("kernel.verifier_sweep_muls_per_s" in theirs) == (w["name"] == CELL)


def test_new_metrics_read_nothing_from_a_program_without_them():
    """On the parent commit the counter does not exist: the reader returns
    None and the line leaves the metric out."""
    spec = layer_metrics.load_metric("sweep.selector_gates")
    assert layer_metrics.read_metric(spec, {"counters": {"fri.folds": 12}}) is None
    assert layer_metrics.read_metric(
        spec, {"counters": {"quotient.selector_tree_gates": 7}}) == 7.0
    rate = layer_metrics.load_metric("kernel.verifier_sweep_muls_per_s")
    assert layer_metrics.read_metric(rate, {"trace": None, "shapes": {}}) is None
