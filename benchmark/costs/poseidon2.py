"""Poseidon2 permutations the Merkle commits of one prove need.

A leaf of an oracle with B columns absorbs B field elements at the sponge's
rate of 8 per permutation: ceil(B / 8) permutations for each of the N
leaves. The binary tree above N leaves down to a cap of `cap` nodes has
N - cap internal nodes, one permutation each.
"""

from __future__ import annotations

from .shapes import prove_commits

RATE = 8


def leaf_perms(columns: int, leaves: int) -> int:
    return int(leaves) * -(-int(columns) // RATE)


def node_perms(leaves: int, cap: int) -> int:
    return max(0, int(leaves) - int(cap))


def cost(shapes: dict) -> dict:
    """Per prove: witness, stage-2 and quotient commits."""
    N, cap = shapes["N"], shapes["cap"]
    perms = sum(
        leaf_perms(b, N) + node_perms(N, cap) for b in prove_commits(shapes)
    )
    return {"ops": perms, "bytes": 0, "bound": "none"}
