"""The prover's array shapes for one cell, worked out from the
configuration's widths and the trace length alone (boojum's protocol,
reference prover.rs): nothing here is read from the program.

  n       trace length (rows), a power of two
  L       fri_lde_factor; N = n * L is the committed domain
  Q       quotient rate: the configuration's quotient_degree, or, when that
          is null, the next power of two at or above the constraint degree
          (decoupled from L, as in the reference)
  B_wit   witness oracle columns: copy columns + specialized lookup columns
          (lookup_args * lookup_width) + witness columns + 1 multiplicity
  S       stage-2 oracle columns, extension field (2 base columns each):
          1 grand product z + (chunks - 1) partial products + lookup_args
          sub-argument polys + 1 table poly
  B_q     quotient oracle columns: Q chunks in the extension field
"""

from __future__ import annotations


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def prove_shapes(config: dict, trace_len: int) -> dict:
    p = config["circuit"]["params"]
    pc = config["proof_config"]
    n = int(trace_len)
    L = int(pc["fri_lde_factor"])
    degree = int(p["constraint_degree"])
    Q = int(pc.get("quotient_degree") or _next_pow2(degree))
    copy = int(p["copy_columns"])
    lk_args = int(p.get("lookup_args", 0))
    lk_width = int(p.get("lookup_width", 0))
    lookup_cols = lk_args * lk_width
    wit = int(p.get("witness_columns", 0))
    under_copy = copy + lookup_cols
    # the copy-permutation grand product is split into chunks of `degree`
    # columns so that each relation stays within the constraint degree
    chunks = -(-under_copy // degree)
    s2_ext = 1 + (chunks - 1) + ((lk_args + 1) if lk_args else 0)
    return {
        "n": n,
        "L": L,
        "N": n * L,
        "Q": Q,
        "cap": int(pc["merkle_tree_cap_size"]),
        "B_wit": copy + lookup_cols + wit + (1 if lk_args else 0),
        "S": 2 * s2_ext,
        "B_q": 2 * Q,
        "queries": int(pc["num_queries"]),
    }


def prove_commits(shapes: dict) -> list[int]:
    """Column counts of the oracles one prove commits through the LDE, leaf
    and node kernels: witness, stage 2, quotient. (The setup oracle is
    committed by generate_setup, outside the prove; the FRI oracles are
    committed inside the fri_commit kernels.)"""
    return [shapes["B_wit"], shapes["S"], shapes["B_q"]]
