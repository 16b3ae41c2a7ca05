"""Compulsory HBM bytes of the low-degree extensions of one STREAMED prove.

A streamed commit keeps no rate-L storage: the oracle's columns are
evaluated from their monomials once to be hashed, once more for round 5's
DEEP sum and once more for the query phase's leaf values. Each pass reads
every input column once (n field elements) and writes every output column
once (n * L field elements), 8 bytes an element however it is laid out:
`lde.lde_bytes`, three times over the witness, stage-2 and quotient oracles.
The setup oracle is committed by generate_setup and, where it is decided
alone to stay materialized, never transformed inside a prove. Round 5's
few single columns (the shifted z, the lookup sums, the public inputs'
columns) are transformed once more and are NOT counted: the share is a
floor, and cannot pass 100 % for kernels that read and write each array
once a pass.
"""

from __future__ import annotations

from .lde import lde_bytes
from .shapes import prove_commits

PASSES = 3  # the commit, DEEP's regeneration, the queries' regeneration


def cost(shapes: dict) -> dict:
    """Per prove: every streamed oracle's columns, once a pass."""
    total = PASSES * sum(
        lde_bytes(b, shapes["n"], shapes["L"]) for b in prove_commits(shapes)
    )
    return {"bytes": total, "ops": 0, "bound": "memory"}
