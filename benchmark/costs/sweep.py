"""Compulsory HBM bytes of the fused quotient sweep of one prove.

The sweep runs once over each of the Q cosets of the trace domain (n rows
each). On a coset it reads the coset evaluation of every oracle column once
and writes the coset's share of the quotient's two base columns (one
extension-field column) once. The oracle columns `shapes` can name:

  B_wit      the witness oracle (copy, lookup, witness and multiplicity columns)
  B_wit - 1  the setup oracle's sigma columns, one for each column under the
             copy permutation (every witness-oracle column but the
             multiplicity column, in a configuration with no plain witness
             columns, which is every configuration the benchmark has)
  S          the stage-2 oracle (z, partial products, lookup polynomials)
  2          z at the shifted point

The setup oracle's constant and table columns, the domain tables (x, L_0,
1 / Z_H) and the challenge table are read too but cannot be worked out from
`shapes`; they are left out, so the bytes are a floor and the share reads a
little lower than the whole truth (about 3 % of the columns at the Era
geometry), never higher. A Goldilocks element is 8 bytes however it is
laid out.

Bound: memory. There is no published integer-VPU peak for the v5e
(peaks.json), so no share of a compute peak can be given; the sweep's
arithmetic (gate terms, 22 copy-permutation chunks, 9 lookup terms a row at
the Era geometry) is what a share far below 100 % points at. A kernel that
reads each coset evaluation once and writes the quotient once moves at
least these bytes, so the share cannot pass 100 %.
"""

from __future__ import annotations

FIELD_BYTES = 8
QUOTIENT_BASE_COLUMNS = 2


def sweep_columns(shapes: dict) -> int:
    """Oracle columns one coset's sweep reads, as far as `shapes` names them."""
    b_wit = int(shapes["B_wit"])
    return b_wit + (b_wit - 1) + int(shapes["S"]) + 2


def sweep_bytes(columns: int, n: int, cosets: int) -> int:
    """`columns` read and the quotient's two base columns written, on each
    of `cosets` cosets of `n` rows."""
    return FIELD_BYTES * (int(columns) + QUOTIENT_BASE_COLUMNS) * int(n) * int(cosets)


def cost(shapes: dict) -> dict:
    """Per prove: Q coset sweeps."""
    return {
        "bytes": sweep_bytes(sweep_columns(shapes), shapes["n"], shapes["Q"]),
        "ops": 0,
        "bound": "memory",
    }
