"""Upstream's Keccak-256 gadget (reference src/gadgets/keccak256/mod.rs:56,
BASELINE.json configs[2]) as a circuit of its own: a message of
`message_bytes` bytes drawn from the seed, its bytes range-checked through
the gadget's own 8-bit xor table (a geometry with width-3 lookups holds no
wider one), the sponge, and the 32 digest bytes as public inputs.

The plain reference of the circuit's semantics is
`boojum_tpu.hashes.keccak_host.keccak256` (pure Python on ints, none of the
gadget's code): `build` asserts that the digest in the witness equals it, so
a run whose proofs verify has proved the host digest of the seeded message.

The xor8 and and8 tables have 65,536 rows each and the seven byte-split
tables 256: 132,864 table rows put the trace at 2^18 rows for any message up
to some 80 rate blocks (a permutation adds about 3,100 lookup rows and 170
gate rows on 130 columns with 8 lookups a row).
"""

from __future__ import annotations

import numpy as np

RATE_BYTES = 136
# rows a permutation needs at most on the narrowest geometry this builder
# is given (130 columns, 8 lookups a row): a capacity bound only
ROWS_PER_PERMUTATION = 4096
TABLE_ROWS = 2 * 65536 + 7 * 256


def message(num_bytes: int, seed: int) -> bytes:
    """The request's payload: `num_bytes` bytes drawn from the seed."""
    return np.random.default_rng(int(seed)).bytes(int(num_bytes))


def build(params: dict, seed: int):
    """`params` is the configuration's `circuit.params` merged with the
    traffic mix's `request`. Returns the synthesized ConstraintSystem."""
    from boojum_tpu.cs.gates import PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.types import CSGeometry, LookupParameters
    from boojum_tpu.gadgets import allocate_u8_input, keccak256
    from boojum_tpu.gadgets.keccak256 import keccak256_digest_bytes
    from boojum_tpu.hashes.keccak_host import keccak256 as host_keccak256

    num_bytes = int(params["message_bytes"])
    geometry = CSGeometry(
        num_columns_under_copy_permutation=int(params["copy_columns"]),
        num_witness_columns=int(params.get("witness_columns", 0)),
        num_constant_columns=int(params["constant_columns"]),
        max_allowed_constraint_degree=int(params["constraint_degree"]),
    )
    lookup = LookupParameters(
        width=int(params["lookup_width"]),
        num_repetitions=int(params["lookup_args"]),
    )
    # a capacity bound: pad_and_shrink rounds the trace to the smallest
    # power of two that fits, and the tables alone need 2^18
    permutations = num_bytes // RATE_BYTES + 1
    rows = max(TABLE_ROWS, permutations * ROWS_PER_PERMUTATION)
    cs = ConstraintSystem(geometry, 1 << (rows - 1).bit_length(),
                          lookup_params=lookup)
    data = message(num_bytes, seed)
    digest = keccak256(cs, allocate_u8_input(cs, data, range_check="xor8"))
    for v in digest:
        PublicInputGate.place(cs, v)
    got = keccak256_digest_bytes(cs, digest)
    assert got == host_keccak256(data), (
        f"the gadget's digest {got.hex()} is not the host Keccak-256 "
        f"{host_keccak256(data).hex()} of the seeded message"
    )
    return cs
