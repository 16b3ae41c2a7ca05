"""Keccak-256 gadget tests: digest parity vs a host implementation + known
vectors + satisfiability (reference test model: gadgets/keccak256/mod.rs:136).
"""

import pytest

from boojum_tpu.cs.implementations import ConstraintSystem
from boojum_tpu.cs.types import CSGeometry, LookupParameters
from boojum_tpu.gadgets import allocate_u8_input
from boojum_tpu.gadgets.keccak256 import keccak256, keccak256_digest_bytes
from boojum_tpu.prover.satisfiability import check_if_satisfied

GEOM = CSGeometry(
    num_columns_under_copy_permutation=60,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)

LOOKUP = LookupParameters(width=4, num_repetitions=8)


# -- host reference (original Keccak, 0x01 padding — Ethereum keccak256) -----

from boojum_tpu.hashes.keccak_host import keccak256 as host_keccak256


def test_host_keccak_known_vectors():
    assert host_keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert host_keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )


def build_keccak_circuit(data: bytes):
    cs = ConstraintSystem(GEOM, 1 << 18, lookup_params=LOOKUP)
    # the range check rides the gadget's own xor8 table, two bytes a lookup
    inp = allocate_u8_input(cs, data, range_check="xor8")
    digest = keccak256(cs, inp)
    return cs, digest


def test_keccak256_parity_short():
    data = b"hello TPU keccak"
    cs, digest = build_keccak_circuit(data)
    assert keccak256_digest_bytes(cs, digest) == host_keccak256(data)


def test_keccak256_parity_two_blocks():
    data = bytes(range(150))
    cs, digest = build_keccak_circuit(data)
    assert keccak256_digest_bytes(cs, digest) == host_keccak256(data)


def test_keccak256_satisfiable():
    data = b"graft"
    cs, digest = build_keccak_circuit(data)
    asm = cs.into_assembly()
    assert check_if_satisfied(asm, verbose=True)


def test_input_range_check_uses_only_the_keccak_tables():
    """With `range_check="xor8"` the circuit holds the gadget's nine tables
    and none of SHA-256's: what a geometry with width-3 lookups needs."""
    cs, _ = build_keccak_circuit(b"abc")
    assert sorted(t.name for t in cs.lookup_tables) == sorted(
        ["xor8", "and8"] + [f"byte_split_at{k}" for k in range(1, 8)]
    )
    assert max(t.width for t in cs.lookup_tables) == 3


@pytest.mark.parametrize("range_check", ["xor8", "trixor4"])
def test_input_allocation_refuses_a_value_that_is_no_byte(range_check):
    cs = ConstraintSystem(GEOM, 1 << 18, lookup_params=LOOKUP)
    # a lookup miss: KeyError from the Python resolver, RuntimeError from
    # the native one; trixor4's recomposition fails as an AssertionError
    with pytest.raises((KeyError, AssertionError, RuntimeError)):
        allocate_u8_input(cs, [7, 256], range_check=range_check)
        cs.into_assembly()
