"""The reference-DIALECT prover closes the bit-parity loop on own circuits.

`compat.prove_reference.prove_reference_dialect` produces proofs in the
reference's transcript dialect; `compat.verifier.verify_reference_proof` —
the same byte-level reimplementation of the reference verification algorithm
(verifier.rs:888) that validates the golden Era artifacts — must accept them
INCLUDING the full quotient identity at z (which the golden Era circuit
cannot check, its gate config living in an external crate). Tampering with
any committed value must reject.
"""

import copy
import json

import numpy as np
import pytest

from boojum_tpu.compat.prove_reference import prove_reference_dialect
from boojum_tpu.compat.verifier import verify_reference_proof
from boojum_tpu.cs.gates import ConstantsAllocatorGate, FmaGate, PublicInputGate
from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.examples import EXAMPLE_GEOMETRY


def _fma_assembly(n_gates=300, capacity=1 << 9):
    cs = ConstraintSystem(EXAMPLE_GEOMETRY, capacity)
    a = ConstantsAllocatorGate.allocate_constant(cs, 3)
    b = ConstantsAllocatorGate.allocate_constant(cs, 5)
    out = a
    for _ in range(n_gates):
        out = FmaGate.fma(cs, out, b, a, 7, 11)
    PublicInputGate.place(cs, out)
    return cs.into_assembly()


def test_reference_dialect_fma_circuit_full_identity():
    asm = _fma_assembly()
    art = prove_reference_dialect(
        asm, fri_lde_factor=4, cap_size=8, security_level=40, pow_bits=0
    )
    assert verify_reference_proof(
        art.vk, art.proof, art.config, check_quotient_identity=True
    )
    # artifacts already round-tripped through the golden-artifact serde
    # loaders inside prove_reference_dialect; pin that the JSON is complete
    assert json.dumps(art.proof_json) and json.dumps(art.vk_json)


def test_reference_dialect_lookup_circuit_full_identity():
    from boojum_tpu.examples import build_xor_lookup_circuit

    cs, _, _ = build_xor_lookup_circuit(num_lookups=16, capacity=1 << 9)
    asm = cs.into_assembly()
    art = prove_reference_dialect(
        asm, fri_lde_factor=4, cap_size=8, security_level=40, pow_bits=0
    )
    assert verify_reference_proof(
        art.vk, art.proof, art.config, check_quotient_identity=True
    )


def test_reference_dialect_tamper_rejected():
    asm = _fma_assembly(n_gates=120)
    art = prove_reference_dialect(
        asm, fri_lde_factor=4, cap_size=8, security_level=40, pow_bits=0
    )
    # tampered opening at z
    p = copy.deepcopy(art.proof)
    c0, c1 = p.values_at_z[0]
    p.values_at_z[0] = ((c0 + 1) % ((1 << 64) - (1 << 32) + 1), c1)
    assert not verify_reference_proof(art.vk, p, art.config)
    # tampered public input
    p = copy.deepcopy(art.proof)
    p.public_inputs[0] = (p.public_inputs[0] + 1) % (
        (1 << 64) - (1 << 32) + 1
    )
    assert not verify_reference_proof(art.vk, p, art.config)
    # tampered FRI leaf
    p = copy.deepcopy(art.proof)
    q = p.queries_per_fri_repetition[0]
    q.fri[0].leaf_elements[0] = (q.fri[0].leaf_elements[0] + 1) % (
        (1 << 64) - (1 << 32) + 1
    )
    assert not verify_reference_proof(art.vk, p, art.config)


def test_reference_dialect_pow_grinding():
    asm = _fma_assembly(n_gates=60)
    # pow_bits=3 exercises the schedule's pow adjustment (raw=37 is not a
    # multiple of rate_log=2, so compute_fri_schedule lowers it to 2; the
    # recorded proof_config must carry the adjusted fixed point)
    art = prove_reference_dialect(
        asm, fri_lde_factor=4, cap_size=8, security_level=40, pow_bits=3
    )
    assert art.proof.proof_config["pow_bits"] == 2
    assert verify_reference_proof(
        art.vk, art.proof, art.config, check_quotient_identity=True
    )
    p = copy.deepcopy(art.proof)
    p.pow_challenge += 1
    assert not verify_reference_proof(art.vk, p, art.config)
