"""SHA-256 gadget tests: digest parity vs hashlib + full e2e prove/verify
(reference test model: gadgets/sha256/mod.rs:160 parity test, :296 e2e)."""

import hashlib

import pytest

from boojum_tpu.cs.types import CSGeometry, LookupParameters
from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.gadgets import allocate_u8_input, sha256, sha256_digest_bytes
from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify
from boojum_tpu.prover.satisfiability import check_if_satisfied

GEOM = CSGeometry(
    num_columns_under_copy_permutation=60,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)

LOOKUP = LookupParameters(width=4, num_repetitions=8)

CONFIG = ProofConfig(
    fri_lde_factor=8,
    merkle_tree_cap_size=16,
    num_queries=30,
    pow_bits=0,
    fri_final_degree=16,
)


def build_sha_circuit(data: bytes):
    cs = ConstraintSystem(GEOM, 1 << 15, lookup_params=LOOKUP)
    inp = allocate_u8_input(cs, data)
    digest = sha256(cs, inp)
    return cs, digest


def test_sha256_parity_one_block():
    data = b"abc"
    cs, digest = build_sha_circuit(data)
    got = sha256_digest_bytes(cs, digest)
    assert got == hashlib.sha256(data).digest()


def test_sha256_parity_two_blocks():
    data = bytes(range(100))
    cs, digest = build_sha_circuit(data)
    got = sha256_digest_bytes(cs, digest)
    assert got == hashlib.sha256(data).digest()


def test_sha256_satisfiable():
    data = b"tpu-native boojum"
    cs, _ = build_sha_circuit(data)
    asm = cs.into_assembly()
    assert check_if_satisfied(asm, verbose=True)


# 285 s cold alone, 298 to 404 s among six workers (PR 24), where the
# per-test limit is 420 s: the 60-column, 8-lookup kernel set at LDE 8.
# Tier-1 keeps the gadget (the three tests above), prove() with
# specialized lookup columns (test_lookup's e2e) and this geometry's whole
# kernel library lowering (test_precompile's SHA enumeration).
@pytest.mark.slow
def test_sha256_e2e_prove_verify():
    data = b"abc"
    cs, digest = build_sha_circuit(data)
    got = sha256_digest_bytes(cs, digest)
    assert got == hashlib.sha256(data).digest()
    asm = cs.into_assembly()
    setup = generate_setup(asm, CONFIG)
    proof = prove(asm, setup, CONFIG)
    assert verify(setup.vk, proof, asm.gates), "SHA-256 proof must verify"
