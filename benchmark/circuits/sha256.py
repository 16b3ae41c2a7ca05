"""Upstream's SHA-256 benchmark circuit (reference src/gadgets/sha256/mod.rs:269
and README "For curions in benchmarks"): the lookup-table SHA-256 gadget over a
message of `message_bytes` bytes. A copy of
`boojum_tpu.examples.build_sha256_bench_circuit`, with the geometry and lookup
parameters taken from the configuration file and the message drawn from the
seed, so that the program receives only the generated input.

An 8 kB message fills a 2^16-row trace; the lookup tables keep anything up to
1 kB at 2^14 rows.
"""

from __future__ import annotations

import numpy as np


def message(num_bytes: int, seed: int) -> bytes:
    """The request's payload: `num_bytes` bytes drawn from the seed."""
    return np.random.default_rng(int(seed)).bytes(int(num_bytes))


def build(params: dict, seed: int):
    """`params` is the configuration's `circuit.params` merged with the
    traffic mix's `request`. Returns the synthesized ConstraintSystem."""
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.types import CSGeometry, LookupParameters
    from boojum_tpu.gadgets import allocate_u8_input, sha256

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
    # power of two that fits (8 kB fills 2^16, 128 kB 2^20)
    capacity = 1 << max(17, (num_bytes // 8192).bit_length() + 16)
    cs = ConstraintSystem(geometry, capacity, lookup_params=lookup)
    sha256(cs, allocate_u8_input(cs, message(num_bytes, seed)))
    return cs
