"""The benchmark configuration `poseidon2-era`, held to its references on
the CPU: an in-circuit Poseidon2 Merkle tree (upstream's flattened gate,
one permutation a row of 130 columns) on the geometry and proof settings of
the golden Era `vk.json` / `proof.json` (LDE 2 under an 8-chunk quotient,
cap 32, 100 queries, final degree 16; `tests/test_golden.py` records them),
with no lookup argument.

Four readings, each against code that shares nothing with what it checks:

- the builder's vectorised numpy reference (`reference_root` and its parts)
  against `Poseidon2SpongeHost` (pure Python on ints), leaf by leaf and node
  by node;
- the gadget through the benchmark's own builder against the same host
  sponge, and the builder's own assertion;
- the proof settings through the normal `prove()` and the host verifier
  `verify()` on a 2^10-row tree (256 leaves of 16 elements: 767 gate rows);
- the same settings through the plain numpy prover in the reference's
  transcript dialect and `verify_reference_proof`, the verifier that accepts
  the golden Era proof byte for byte, with the full quotient identity.

The gate inside the fused limb-plane sweep is held to the u64 sweep in
`tests/test_limb_sweep.py::test_gate_terms_kernel_parity`.
"""

import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from boojum_tpu.compat.prove_reference import prove_reference_dialect
from boojum_tpu.compat.verifier import verify_reference_proof
from boojum_tpu.field import gl
from boojum_tpu.hashes.poseidon2 import Poseidon2SpongeHost
from boojum_tpu.prover import (
    ProofConfig,
    generate_setup,
    precompile,
    prove,
    verify,
)
from boojum_tpu.prover.satisfiability import check_if_satisfied
from boojum_tpu.utils import report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SMALL_LEAVES = 256  # x 16 elements: 767 gate rows, a 2^10-row trace


def _config():
    with open(os.path.join(BENCH, "configs", "poseidon2-era.json")) as f:
        return json.load(f)


def _builder():
    spec = importlib.util.spec_from_file_location(
        "benchmark_circuit_poseidon2_tree",
        os.path.join(BENCH, "circuits", "poseidon2_tree.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _params(leaves, leaf_elements=16):
    return {**_config()["circuit"]["params"], "leaves": leaves,
            "leaf_elements": leaf_elements}


def _host_root(values):
    layer = [Poseidon2SpongeHost.hash_leaf([int(v) for v in leaf])
             for leaf in values]
    while len(layer) > 1:
        layer = [Poseidon2SpongeHost.hash_node(layer[i], layer[i + 1])
                 for i in range(0, len(layer), 2)]
    return layer[0]


# -- the configuration file against the golden artifacts' records -----------


def test_config_is_the_golden_geometry_and_settings():
    """What tests/test_golden.py reads from vk.json / proof.json, less the
    lookup columns this body has no table for: 130 columns under copy
    permutation, LDE 2, cap 32, 100 queries, 16 final monomials, an 8-chunk
    quotient, PoW 0; and `keccak256-era`'s ProofConfig to the letter."""
    c = _config()
    p, pc = c["circuit"]["params"], c["proof_config"]
    assert p["copy_columns"] == 130 and p["witness_columns"] == 0
    assert (p["lookup_width"], p["lookup_args"]) == (0, 0)
    assert p["constraint_degree"] == 7 and p["constant_columns"] == 8
    assert pc["fri_lde_factor"] == 2 and pc["merkle_tree_cap_size"] == 32
    assert pc["num_queries"] == 100 and pc["pow_bits"] == 0
    assert pc["fri_final_degree"] == 16 and pc["transcript"] == "poseidon2"
    assert pc["quotient_degree"] is None and pc["fri_folding_schedule"] is None
    with open(os.path.join(BENCH, "configs", "keccak256-era.json")) as f:
        assert pc == json.load(f)["proof_config"]
    assert len(c["source"]) <= 200 and c["architecture"] is None
    # nothing but the trace length is reduced, the published size is named
    # beside the cut, and every size set here is under `assumed`
    assert c["reduced"] == ["trace_len"]
    assert "2^20" in c["reduced_from"]["trace_len"]
    assert c["assumed"] == ["gate_mix", "lookup", "constant_columns",
                            "leaves", "leaf_elements"]
    assert all(f"{k}:" in c["assumed_why"] or f"{k}," in c["assumed_why"]
               for k in c["assumed"])
    # the gate's row is exactly the geometry's columns
    from boojum_tpu.cs.gates import Poseidon2FlattenedGate

    assert Poseidon2FlattenedGate.principal_width == p["copy_columns"]
    assert Poseidon2FlattenedGate.max_degree == p["constraint_degree"]


def test_cell_fills_three_quarters_of_its_trace():
    with open(os.path.join(BENCH, "traffic", "closed-tree64k.json")) as f:
        request = json.load(f)["request"]
    rows = _builder().gate_rows(request["leaves"], request["leaf_elements"])
    assert rows == 131072 + 65535 == 196607
    assert 1 << (rows).bit_length() == 1 << 18


# -- the vectorised reference against the host sponge ------------------------


@pytest.mark.parametrize("leaf_elements", [3, 8, 11, 16, 20])
def test_reference_leaf_digests_match_the_host_sponge(leaf_elements):
    b = _builder()
    values = b.leaf_values(8, leaf_elements, 2147520101 + leaf_elements)
    got = b.reference_leaf_digests(values)
    for k, leaf in enumerate(values):
        assert [int(v) for v in got[k]] == Poseidon2SpongeHost.hash_leaf(
            [int(v) for v in leaf]
        ), (leaf_elements, k)


def test_reference_node_digests_match_the_host_sponge():
    b = _builder()
    rng = np.random.default_rng(2147520102)
    left = rng.integers(0, gl.P, (8, 4), dtype=np.uint64)
    right = rng.integers(0, gl.P, (8, 4), dtype=np.uint64)
    # the field's edges among the inputs
    left[0] = [0, 1, gl.P - 1, gl.P - 2]
    right[0] = [(1 << 32) - 1, 1 << 32, (1 << 63), gl.P - (1 << 32)]
    got = b.reference_node_digests(left, right)
    for k in range(8):
        assert [int(v) for v in got[k]] == Poseidon2SpongeHost.hash_node(
            [int(v) for v in left[k]], [int(v) for v in right[k]]
        ), k


def test_reference_field_multiplication_is_exact():
    b = _builder()
    rng = np.random.default_rng(2147520103)
    edge = np.array([0, 1, 2, gl.P - 1, gl.P - 2, (1 << 32) - 1, 1 << 32,
                     (1 << 32) + 1, 1 << 63, gl.P - (1 << 32)], dtype=np.uint64)
    a = np.concatenate([np.repeat(edge, len(edge)),
                        rng.integers(0, gl.P, 2000, dtype=np.uint64)])
    c = np.concatenate([np.tile(edge, len(edge)),
                        rng.integers(0, gl.P, 2000, dtype=np.uint64)])
    assert [int(v) for v in b._mul(a, c)] == [
        int(x) * int(y) % gl.P for x, y in zip(a, c)
    ]
    assert [int(v) for v in b._add(a, c)] == [
        (int(x) + int(y)) % gl.P for x, y in zip(a, c)
    ]


# -- the gadget at the Era geometry, through the benchmark's builder --------


@pytest.mark.parametrize("leaves,seed", [
    (8, 2147520111), (8, 7), (16, 2147520111), (16, 7),
])
def test_builder_root_is_the_host_sponges(leaves, seed):
    """The builder's circuit has the host sponge's Merkle root of the
    seeded leaves as its 4 public inputs and is satisfied."""
    b = _builder()
    asm = b.build(_params(leaves), seed).into_assembly()
    assert sorted(g.name for g in asm.gates) == [
        "constant", "nop", "poseidon2_flat", "public_input"
    ]
    root = _host_root(b.leaf_values(leaves, 16, seed))
    assert [v for (_c, _r, v) in asm.public_inputs] == root
    assert root == b.reference_root(b.leaf_values(leaves, 16, seed))
    assert asm.trace_len == 1 << b.gate_rows(leaves, 16).bit_length()
    assert not asm.lookups_enabled
    assert check_if_satisfied(asm)


def test_builder_refuses_a_wrong_root(monkeypatch):
    """The assertion inside build() is live: with the reference made to
    answer another root, synthesis fails."""
    b = _builder()
    monkeypatch.setattr(b, "reference_root", lambda values: [0, 0, 0, 0])
    with pytest.raises(AssertionError, match="reference Poseidon2 Merkle root"):
        b.build(_params(8), 1)


# -- the proof settings through prove() and the host verifier ----------------


@pytest.fixture(scope="module")
def small_assembly():
    asm = _builder().build(_params(SMALL_LEAVES), 2147520121).into_assembly()
    assert asm.trace_len == 1 << 10
    return asm


@pytest.fixture(scope="module")
def recorded(small_assembly):
    """(assembly, setup, proof, flight report) of the 2^10-row tree at the
    configuration's ProofConfig, through the normal generate_setup() and
    prove()."""
    cfg = ProofConfig(**_config()["proof_config"])
    # the kernel library on a pool first, as the benchmark's set-up does
    precompile(small_assembly, cfg, max_workers=os.cpu_count() or 4)
    setup = generate_setup(small_assembly, cfg)
    with report.flight_recording(label="poseidon2_era") as rec:
        proof = prove(small_assembly, setup, cfg)
    return small_assembly, setup, proof, report.build_report(rec)


@pytest.fixture(scope="module")
def proved(recorded):
    return recorded[:3]


def test_era_settings_prove_and_verify(proved):
    asm, setup, proof = proved
    pc = _config()["proof_config"]
    assert setup.vk.fri_lde_factor == 2
    assert setup.vk.quotient_degree == 8
    assert len(proof.queries) == pc["num_queries"]
    assert len(proof.witness_cap) == pc["merkle_tree_cap_size"]
    assert len(proof.queries[0].quotient.leaf_values) == 2 * 8
    # no lookup columns and no multiplicity column: the witness oracle is
    # the gate's 130 columns, and stage 2 is z and 18 partial products
    assert len(proof.queries[0].witness.leaf_values) == 130
    assert len(proof.queries[0].stage2.leaf_values) == 2 * (1 + 18)
    assert len(proof.values_at_0) == 0
    assert len(proof.final_fri_monomials) == pc["fri_final_degree"]
    assert len(proof.public_inputs) == 4
    assert verify(setup.vk, proof, asm.gates)


def test_recorder_says_what_the_gates_cost(recorded):
    """`quotient.gate_ops_per_row` is the gates' captured programs times
    their repetitions, the flattened gate's 2,036 operations first; on the
    u64 path, which the CPU runs, that one gate is replayed from its packed
    program (`quotient.packed_gates`), and its tracing has a span."""
    from boojum_tpu.cs.gate_capture import capture_gate_program

    asm, _setup, _proof, rep = recorded
    counters = rep["metrics"]["counters"]
    ops = {
        g.name: len(capture_gate_program(g).ops) * g.num_repetitions(asm.geometry)
        for g in asm.gates if g.num_terms
    }
    assert ops["poseidon2_flat"] == 2036
    assert counters["quotient.gate_ops_per_row"] == sum(ops.values())
    assert counters["quotient.packed_gates"] == 1
    # the flattened gate and the constants allocator share the trace: two
    # gates with terms under the selector tree (public_input, nop: none)
    assert counters["quotient.selector_tree_gates"] == 2


def test_altered_opening_is_rejected(proved):
    asm, setup, proof = proved
    bad = copy.deepcopy(proof)
    c0, c1 = bad.values_at_z[0]
    bad.values_at_z[0] = ((c0 + 1) % gl.P, c1)
    assert not verify(setup.vk, bad, asm.gates)


def test_altered_root_is_rejected(proved):
    """The public inputs are the root: another root does not verify."""
    asm, setup, proof = proved
    bad = copy.deepcopy(proof)
    bad.public_inputs[0] = (int(bad.public_inputs[0]) + 1) % gl.P
    assert not verify(setup.vk, bad, asm.gates)


# -- the independent reference: the numpy prover in the reference's dialect --


@pytest.fixture(scope="module")
def reference_artifacts(small_assembly):
    pc = _config()["proof_config"]
    return prove_reference_dialect(
        small_assembly,
        fri_lde_factor=pc["fri_lde_factor"],
        cap_size=pc["merkle_tree_cap_size"],
        security_level=100,
        pow_bits=pc["pow_bits"],
    )


def test_reference_dialect_accepts_these_settings(reference_artifacts):
    """Committed at LDE 2 under an 8-chunk quotient, as the golden proof is;
    accepted with the full quotient identity at z, whose gate terms are the
    flattened gate's 118."""
    art = reference_artifacts
    pc = _config()["proof_config"]
    assert art.vk.quotient_degree == 8
    assert len(art.proof.queries_per_fri_repetition) == pc["num_queries"]
    assert len(art.proof.final_fri_monomials[0]) == pc["fri_final_degree"]
    assert len(art.proof.witness_oracle_cap) == pc["merkle_tree_cap_size"]
    assert verify_reference_proof(
        art.vk, art.proof, art.config, check_quotient_identity=True
    )


def test_reference_dialect_rejects_an_altered_opening(reference_artifacts):
    art = reference_artifacts
    bad = copy.deepcopy(art.proof)
    c0, c1 = bad.values_at_z[0]
    bad.values_at_z[0] = ((c0 + 1) % gl.P, c1)
    assert not verify_reference_proof(art.vk, bad, art.config)
