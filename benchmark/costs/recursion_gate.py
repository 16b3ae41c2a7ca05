"""Goldilocks multiplications the recursive verifier circuit's gate terms
need in one prove, from the gates' own definitions (reference
src/cs/gates/*.rs) and not from the program: the flattened Poseidon2 gate's
972 a row (costs/poseidon2_gate.py, from the permutation's structure) plus,
for each narrow gate, the multiplications of its relation and 2 a term for
the accumulation by an extension-field challenge power, times the
repetitions that fit the geometry's columns under copy permutation:

  gate               columns  relation                          muls  terms
  fma                4        c0*a*b + c1*c - d                 3     1
  constant           1        x - c                             0     1
  boolean            1        x*x - x                           1     1
  reduction4         5        sum of 4 c_i*x_i - out            4     1
  selection          4        sel*(a - b) + b - out             1     1
  conditional_swap   5        d = sel*(b - a); a + d - x;       1     2
                              b - d - y

At 130 columns: 32 x 5 + 130 x 2 + 130 x 3 + 26 x 6 + 32 x 3 + 26 x 5 =
1,192, and with the permutation's 972, 2,164 a row. Every gate's terms are
evaluated on every row of every coset the sweep visits, whatever the row
holds (a selector masks each gate's sum): n x Q rows a prove. The selector
products are not in the count, as they are not in `sweep.gate_ops_per_row`:
the tree's shape is the prover's choice, not the circuit's.
`benchmark/tests/test_recursive_verifier_cell.py` holds the count to a hand
count and to the multiplications of the programs the sweep traces.

The cell has no lookup argument and no witness columns, so the shapes'
`B_wit` (witness-oracle columns) is the columns under copy permutation.

Bound: arithmetic; a rate and no share, because no integer-VPU peak is
published for the v5e (peaks.json), as for costs/poseidon2_gate.py.
"""

from __future__ import annotations

from . import poseidon2_gate

EXT_DEGREE = 2
# name: (columns an instance takes, multiplications of its relation, terms)
NARROW_GATES = {
    "fma": (4, 3, 1),
    "constant": (1, 0, 1),
    "boolean": (1, 1, 1),
    "reduction4": (5, 4, 1),
    "selection": (4, 1, 1),
    "conditional_swap": (5, 1, 2),
}


def narrow_muls_per_row(copy_columns: int) -> int:
    return sum(
        (copy_columns // width) * (muls + EXT_DEGREE * terms)
        for width, muls, terms in NARROW_GATES.values()
    )


def muls_per_row(copy_columns: int) -> int:
    return poseidon2_gate.muls_per_row() + narrow_muls_per_row(copy_columns)


def cost(shapes: dict) -> dict:
    """Per prove: Q coset sweeps of n rows."""
    return {
        "ops": muls_per_row(int(shapes["B_wit"])) * int(shapes["n"])
        * int(shapes["Q"]),
        "bytes": 0,
        "bound": "arithmetic",
    }
