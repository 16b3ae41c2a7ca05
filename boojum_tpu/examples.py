"""Small example circuits shared by tests, the driver entry, and docs.

The xor4 lookup circuit mirrors the shape of the reference's small lookup
tests (specialized columns, two tables, an FMA accumulator and one public
input) at toy scale; it exercises every prover round incl. the lookup paths.
"""

from __future__ import annotations

import numpy as np

from .cs.types import CSGeometry, LookupParameters
from .cs.implementations import ConstraintSystem
from .cs.lookup_table import LookupTable, range_check_table
from .cs.gates import FmaGate, PublicInputGate
from .cs.gates.simple import (
    MatrixMultiplicationGate,
    SimpleNonlinearityGate,
)

EXAMPLE_GEOMETRY = CSGeometry(
    num_columns_under_copy_permutation=8,
    num_witness_columns=0,
    num_constant_columns=6,
    max_allowed_constraint_degree=4,
)

EXAMPLE_LOOKUP = LookupParameters(width=3, num_repetitions=2)


def xor4_table() -> LookupTable:
    a = np.arange(16, dtype=np.uint64).repeat(16)
    b = np.tile(np.arange(16, dtype=np.uint64), 16)
    return LookupTable("xor4", 2, 1, np.stack([a, b, a ^ b], axis=1))


def build_xor_lookup_circuit(
    num_lookups: int = 30,
    geometry: CSGeometry = EXAMPLE_GEOMETRY,
    lookup_params: LookupParameters = EXAMPLE_LOOKUP,
    capacity: int = 1 << 10,
    seed: int = 7,
):
    """xor4 lookups + range checks chained through an FMA accumulator.

    Returns (cs, acc_var, last_lookup_out_var).
    """
    cs = ConstraintSystem(geometry, capacity, lookup_params=lookup_params)
    xor_id = cs.add_lookup_table(xor4_table())
    rc_id = cs.add_lookup_table(range_check_table(4))
    rng = np.random.default_rng(seed)
    acc = cs.alloc_variable_with_value(1)
    last_out = None
    for _ in range(num_lookups):
        a = cs.alloc_variable_with_value(int(rng.integers(16)))
        b = cs.alloc_variable_with_value(int(rng.integers(16)))
        (out,) = cs.perform_lookup(xor_id, [a, b])
        cs.enforce_lookup(rc_id, [out, cs.zero_var()])
        acc = FmaGate.fma(cs, acc, out, a, 1, 1)
        last_out = out
    PublicInputGate.place(cs, acc)
    return cs, acc, last_out


def build_fma_chain_circuit(
    num_rows: int = (1 << 10) - 8,
    geometry: CSGeometry = EXAMPLE_GEOMETRY,
    capacity: int = 1 << 10,
):
    """A Fibonacci-style fma chain with one public input: the minimal
    every-round circuit (no lookups). Field-agnostic arithmetic — the
    canonical e2e leg for alternative field backends (ISSUE 20).

    Returns (cs, out_var).
    """
    cs = ConstraintSystem(geometry, capacity)
    a = cs.alloc_variable_with_value(1)
    b = cs.alloc_variable_with_value(2)
    per_row = FmaGate.instance().num_repetitions(geometry)
    for _ in range(num_rows * per_row):
        a, b = b, FmaGate.fma(cs, a, b, a, 1, 1)
    PublicInputGate.place(cs, b)
    return cs, b


def build_poseidon_rf_circuit(
    num_rounds: int = 48,
    geometry: CSGeometry = EXAMPLE_GEOMETRY,
    capacity: int = 1 << 10,
    seed: int = 11,
):
    """A toy Poseidon-style round function: width-3 state, per round a
    degree-7 S-box with a round constant followed by a circulant MDS mix
    (SimpleNonlinearityGate + MatrixMultiplicationGate — the same gate
    shapes real Poseidon circuits use). Degree-7 constraints push the
    quotient degree to 8, exercising the decoupled sweep rate; all
    arithmetic fits any backend field (ISSUE 20's poseidon-rf e2e leg).

    Returns (cs, out_var).
    """
    cs = ConstraintSystem(geometry, capacity)
    rng = np.random.default_rng(seed)
    mds = MatrixMultiplicationGate(
        "rf3", [[2, 1, 1], [1, 2, 1], [1, 1, 2]]
    )
    state = [cs.alloc_variable_with_value(int(v)) for v in (3, 5, 7)]
    for _ in range(num_rounds):
        sboxed = [
            SimpleNonlinearityGate.apply(cs, x, int(rng.integers(1, 997)))
            for x in state
        ]
        state = mds.apply(cs, sboxed)
    PublicInputGate.place(cs, state[0])
    return cs, state[0]


# ---------------------------------------------------------------------------
# Bench-scale circuits (bench.py, chip_smoke.py, scripts/)
# ---------------------------------------------------------------------------

SHA256_BENCH_GEOMETRY = CSGeometry(
    num_columns_under_copy_permutation=60,
    num_witness_columns=0,
    num_constant_columns=8,
    max_allowed_constraint_degree=7,
)
SHA256_BENCH_LOOKUP = LookupParameters(width=4, num_repetitions=8)


def build_sha256_bench_circuit(num_bytes: int = 8192):
    """Upstream's SHA-256 benchmark at its own widths (reference
    src/gadgets/sha256/mod.rs:269 and README "For curions in benchmarks"):
    60 copy columns, 8 constant columns, 8 width-4 lookup sub-arguments.
    An 8 kB message fills a 2^16-row trace; the lookup tables keep anything
    up to 1 kB at 2^14. Returns the ConstraintSystem."""
    from .gadgets import allocate_u8_input, sha256

    # a CAPACITY bound — pad_and_shrink rounds the trace to the smallest
    # power of two that fits: 8 kB fills 2^16, the north-star 128 kB 2^20
    capacity = 1 << max(17, (num_bytes // 8192).bit_length() + 16)
    cs = ConstraintSystem(
        SHA256_BENCH_GEOMETRY, capacity, lookup_params=SHA256_BENCH_LOOKUP
    )
    data = bytes(i % 255 for i in range(num_bytes))
    sha256(cs, allocate_u8_input(cs, data))
    return cs


def build_fma_bench_circuit(log_n: int):
    """A 2^log_n-row fma chain over 16 copy columns. Degree-3 chunks keep
    every relation at degree <= 4, so the whole pipeline runs at LDE
    factor 4 (half the memory of the SHA geometry). Returns the
    ConstraintSystem."""
    geom = CSGeometry(
        num_columns_under_copy_permutation=16,
        num_witness_columns=0,
        num_constant_columns=6,
        max_allowed_constraint_degree=3,
    )
    cs = ConstraintSystem(geom, 1 << log_n)
    a = cs.alloc_variable_with_value(1)
    b = cs.alloc_variable_with_value(2)
    per_row = FmaGate.instance().num_repetitions(geom)
    steps = ((1 << log_n) - 8) * per_row
    for _ in range(steps):
        a, b = b, FmaGate.fma(cs, a, b, a, 1, 1)
    PublicInputGate.place(cs, b)
    return cs
