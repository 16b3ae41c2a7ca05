"""Recursive verification tests (reference test model:
recursive_verifier.rs:2213 — prove a circuit, synthesize the verifier circuit
over the proof, check satisfiability)."""

from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.cs.types import CSGeometry
from boojum_tpu.field import gl
from boojum_tpu.gadgets.recursion import recursive_verify
from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify
from boojum_tpu.prover.proof import Proof
from boojum_tpu.prover.satisfiability import check_if_satisfied

from test_e2e import GEOM as INNER_GEOM, build_fibonacci_circuit

RECURSION_GEOM = CSGeometry(
    num_columns_under_copy_permutation=130,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)

INNER_CONFIG = ProofConfig(
    fri_lde_factor=8,
    merkle_tree_cap_size=4,
    num_queries=8,
    pow_bits=0,
    fri_final_degree=4,
)


def _prove_inner():
    cs, _ = build_fibonacci_circuit(steps=20)
    asm = cs.into_assembly()
    setup = generate_setup(asm, INNER_CONFIG)
    proof = prove(asm, setup, INNER_CONFIG)
    assert verify(setup.vk, proof, asm.gates)
    return setup.vk, proof, asm.gates


def test_recursive_verifier_satisfiable():
    vk, proof, gates = _prove_inner()
    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    pi_vars, _cap_vars = recursive_verify(outer, vk, proof, gates)
    assert [outer.get_value(v) for v in pi_vars] == list(proof.public_inputs)
    outer_asm = outer.into_assembly()
    assert check_if_satisfied(outer_asm, verbose=True)


def test_recursive_verifier_lookup_pow():
    """The in-circuit verifier's lookup-argument and PoW branches, exercised
    with the geometry real Era-style circuits use (lookups on, pow_bits>0):
    satisfiable on the honest proof, unsatisfiable on a tampered lookup
    opening and on a tampered PoW nonce."""
    from boojum_tpu.examples import build_xor_lookup_circuit

    cfg = ProofConfig(
        fri_lde_factor=8,
        merkle_tree_cap_size=4,
        num_queries=4,
        pow_bits=4,
        fri_final_degree=4,
    )
    cs, _, _ = build_xor_lookup_circuit(num_lookups=8)
    asm = cs.into_assembly()
    setup = generate_setup(asm, cfg)
    proof = prove(asm, setup, cfg)
    assert verify(setup.vk, proof, asm.gates)

    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    pi_vars, _cap = recursive_verify(outer, setup.vk, proof, asm.gates)
    assert [outer.get_value(v) for v in pi_vars] == list(proof.public_inputs)
    assert check_if_satisfied(outer.into_assembly(), verbose=True)

    # tampered lookup sum opening (values at 0) must be unsatisfiable
    bad = Proof.from_json(proof.to_json())
    v = list(bad.values_at_0[0])
    v[0] = (v[0] + 1) % gl.P
    bad.values_at_0[0] = tuple(v)
    outer2 = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    recursive_verify(outer2, setup.vk, bad, asm.gates)
    assert not check_if_satisfied(outer2.into_assembly())

    # tampered PoW nonce must be unsatisfiable
    bad2 = Proof.from_json(proof.to_json())
    bad2.pow_challenge += 1
    outer3 = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    recursive_verify(outer3, setup.vk, bad2, asm.gates)
    assert not check_if_satisfied(outer3.into_assembly())


def test_recursive_verifier_rejects_bad_proof():
    vk, proof, gates = _prove_inner()
    bad = Proof.from_json(proof.to_json())
    bad.public_inputs[0] = (bad.public_inputs[0] + 1) % gl.P
    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    recursive_verify(outer, vk, bad, gates)
    outer_asm = outer.into_assembly()
    assert not check_if_satisfied(outer_asm)


import pytest


# 348 s cold alone (PR 24): the 130-column outer circuit's own kernel set.
# Tier-1 keeps prove() on that geometry (test_poseidon2_gate's
# test_gate_proves_e2e) and the verifier circuit itself
# (test_recursive_verifier_satisfiable and the three tests below).
@pytest.mark.slow
def test_recursive_proof_proves_and_verifies():
    """The counterpart of the reference's recursive bench
    (sha256_bench_recursive_poseidon2.sh / recursive_verifier.rs:2213
    proving config): the 130-column recursive-verifier circuit itself goes
    through setup -> prove -> verify, so a proof-of-a-proof exists."""
    import time

    from boojum_tpu.cs.gates import PublicInputGate

    vk, proof, gates = _prove_inner()
    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    pi_vars, _cap = recursive_verify(outer, vk, proof, gates)
    # surface the inner public inputs as the outer circuit's own
    for v in pi_vars:
        PublicInputGate.place(outer, v)
    outer_asm = outer.into_assembly()
    outer_cfg = ProofConfig(
        # the degree-aware selector tree keeps the degree-7 flattened
        # Poseidon2 gate at depth 1, so LDE 8 suffices
        fri_lde_factor=8,
        merkle_tree_cap_size=8,
        num_queries=4,
        pow_bits=0,
        fri_final_degree=16,
    )
    t0 = time.time()
    outer_setup = generate_setup(outer_asm, outer_cfg)
    outer_proof = prove(outer_asm, outer_setup, outer_cfg)
    wall = time.time() - t0
    assert verify(outer_setup.vk, outer_proof, outer_asm.gates), (
        "recursive proof must verify"
    )
    print(f"recursive prove wall: {wall:.1f}s, trace {outer_asm.trace_len}")
    # the outer proof's public inputs surface the inner ones
    surfaced = [pi[2] for pi in outer_asm.public_inputs[: len(pi_vars)]]
    assert surfaced == list(proof.public_inputs)


def test_recursive_verifier_general_lookup_mode():
    """In-circuit verification of a GENERAL-purpose-columns lookup proof
    (reference lookup_placement.rs:21 + recursive_verifier.rs:380): the
    A-relations are gated by the marker gate's selector at z and the table
    id comes from the marker row's constant. Satisfiable on the honest
    proof; unsatisfiable when a lookup opening is tampered."""
    import sys as _sys

    _sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_lookup_general import CONFIG as GL_CONFIG, build_circuit

    cs, _ = build_circuit(num_lookups=12)
    asm = cs.into_assembly()
    setup = generate_setup(asm, GL_CONFIG)
    proof = prove(asm, setup, GL_CONFIG)
    assert verify(setup.vk, proof, asm.gates)

    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    pi_vars, _cap = recursive_verify(outer, setup.vk, proof, asm.gates)
    assert [outer.get_value(v) for v in pi_vars] == list(proof.public_inputs)
    assert check_if_satisfied(outer.into_assembly(), verbose=True)

    # tampered lookup A-opening must be unsatisfiable
    bad = Proof.from_json(proof.to_json())
    num_chunks = 2  # 8 copy cols at max degree 4 -> 2 chunks
    ab_off_abs = (
        2 * setup.vk.num_copy_cols
        + setup.vk.num_wit_cols
        + 1  # multiplicities column opening
        + setup.vk.geometry.num_constant_columns
        + (setup.vk.lookup_params.width + 1)
        + 2 * (1 + (num_chunks - 1))
    )
    c0, c1 = bad.values_at_z[ab_off_abs]
    bad.values_at_z[ab_off_abs] = ((c0 + 1) % gl.P, c1)
    outer2 = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    recursive_verify(outer2, setup.vk, bad, asm.gates)
    assert not check_if_satisfied(outer2.into_assembly())


def test_recursive_verifier_legacy_poseidon_transcript():
    """Legacy-recursion-mode transcript (reference recursive_transcript.rs is
    generic over the round function; the legacy mode drives it with
    PoseidonFlattenedGate): an inner proof drawn with
    ProofConfig(transcript="poseidon") replays in-circuit through the
    legacy-Poseidon sponge gadget. Satisfiable on the honest proof;
    unsatisfiable on a tampered public input (which shifts every legacy
    transcript challenge)."""
    cfg = ProofConfig(
        fri_lde_factor=8,
        merkle_tree_cap_size=4,
        num_queries=8,
        pow_bits=0,
        fri_final_degree=4,
        transcript="poseidon",
    )
    cs, _ = build_fibonacci_circuit(steps=20)
    asm = cs.into_assembly()
    setup = generate_setup(asm, cfg)
    assert setup.vk.transcript == "poseidon"
    proof = prove(asm, setup, cfg)
    assert verify(setup.vk, proof, asm.gates)

    outer = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    pi_vars, _cap = recursive_verify(outer, setup.vk, proof, asm.gates)
    assert [outer.get_value(v) for v in pi_vars] == list(proof.public_inputs)
    assert check_if_satisfied(outer.into_assembly(), verbose=True)

    bad = Proof.from_json(proof.to_json())
    bad.public_inputs[0] = (bad.public_inputs[0] + 1) % gl.P
    outer2 = ConstraintSystem(RECURSION_GEOM, 1 << 15)
    recursive_verify(outer2, setup.vk, bad, asm.gates)
    assert not check_if_satisfied(outer2.into_assembly())
