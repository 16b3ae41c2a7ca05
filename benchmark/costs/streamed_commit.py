"""Poseidon2 permutations the streamed Merkle commits of one prove need.

A streamed commit absorbs an oracle's columns a block at a time into a
sponge state carried for every leaf: a block of b columns costs each of the
N leaves ceil(b / 8) permutations at the sponge's rate of 8. Blocks are
COL_BLOCK = 32 columns, a multiple of the rate, and only an oracle's last
block may be ragged, so an oracle of B columns costs N * ceil(B / 8), what a
sponge over whole rows costs. The binary tree above N leaves down to a cap
of `cap` nodes has N - cap internal nodes, one permutation each.
"""

from __future__ import annotations

from .shapes import prove_commits

RATE = 8
COL_BLOCK = 32  # the program's streamed block, restated: a multiple of RATE


def absorb_perms(columns: int, leaves: int) -> int:
    blocks = [
        min(COL_BLOCK, int(columns) - i) for i in range(0, int(columns), COL_BLOCK)
    ]
    return int(leaves) * sum(-(-b // RATE) for b in blocks)


def node_perms(leaves: int, cap: int) -> int:
    return max(0, int(leaves) - int(cap))


def cost(shapes: dict) -> dict:
    """Per prove: witness, stage-2 and quotient commits, all streamed."""
    N, cap = shapes["N"], shapes["cap"]
    perms = sum(
        absorb_perms(b, N) + node_perms(N, cap) for b in prove_commits(shapes)
    )
    return {"ops": perms, "bytes": 0, "bound": "none"}
