"""Goldilocks multiplications the flattened Poseidon2 gate's quotient terms
need in one prove, from the permutation's structure (Poseidon2 paper, eprint
2023/323; t = 12, x^7, 4 + 22 + 4 rounds) and not from the program:

  s-boxes        8 full rounds x 12 + 22 partial rounds x 1 = 118, each x^7
                 in 4 multiplications (x^2, x^3, x^4, x^7)
  internal       22 partial rounds x 12 multiplications by the matrix's
  matrix         diagonal (the external matrix is additions and doublings)
  accumulation   118 terms (106 degree resets + 12 outputs), each times its
                 extension-field challenge power: 2 multiplications

472 + 264 + 236 = 972 a row. The gate sits on every row of every coset the
sweep visits, whatever the row holds (a selector masks the sum): n x Q rows
a prove. `benchmark/tests/test_costs_poseidon2_gate.py` holds the count to
the multiplications of the program the sweep replays.

Bound: arithmetic; a rate and no share, because no integer-VPU peak is
published for the v5e (peaks.json), as for costs/poseidon2.py.
"""

from __future__ import annotations

STATE_WIDTH = 12
FULL_ROUNDS = 8
PARTIAL_ROUNDS = 22
SBOX_MULS = 4
EXT_DEGREE = 2


def sboxes() -> int:
    return FULL_ROUNDS * STATE_WIDTH + PARTIAL_ROUNDS


def terms() -> int:
    """A degree reset before every s-box layer but the first, and one for
    each s-box of a partial round, then the 12 outputs."""
    return (FULL_ROUNDS - 1) * STATE_WIDTH + PARTIAL_ROUNDS + STATE_WIDTH


def gate_muls_per_row() -> int:
    return sboxes() * SBOX_MULS + PARTIAL_ROUNDS * STATE_WIDTH


def accumulation_muls_per_row() -> int:
    return EXT_DEGREE * terms()


def muls_per_row() -> int:
    return gate_muls_per_row() + accumulation_muls_per_row()


def cost(shapes: dict) -> dict:
    """Per prove: Q coset sweeps of n rows."""
    return {
        "ops": muls_per_row() * int(shapes["n"]) * int(shapes["Q"]),
        "bytes": 0,
        "bound": "arithmetic",
    }
