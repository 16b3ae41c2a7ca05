"""Blake2s compressions and compulsory bytes of one prove's Merkle commits
under the Blake2s tree hasher.

A leaf of an oracle with B columns is the Blake2s-256 hash of B field
elements, 8 bytes each: ceil(B / 8) compressions of a 64-byte block for each
of the N leaves (the last block zero-padded). A node hashes its two
children's 32-byte digests, one full block: the binary tree above N leaves
down to a cap of `cap` nodes has N - cap of them.

Compulsory bytes: each committed column's LDE is read once (B * N * 8) and
every leaf and every node writes its 32-byte digest once. A node's reading
of its children, layout changes and the padded tiles of the (N, 4) digest
planes are an implementation's traffic and are not counted.

Bound: arithmetic. A compression is some 1,100 32-bit additions, xors and
rotates for 64 bytes read, and the chip publishes no integer-VPU peak, so
the only share there is to take is of the HBM bandwidth: it is small, and
says how far from memory-bound the hash is, not how well it is written.
"""

from __future__ import annotations

from .shapes import prove_commits

BLOCK_ELEMS = 8
FIELD_BYTES = 8
DIGEST_BYTES = 32


def leaf_compressions(columns: int, leaves: int) -> int:
    return int(leaves) * -(-int(columns) // BLOCK_ELEMS)


def node_compressions(leaves: int, cap: int) -> int:
    return max(0, int(leaves) - int(cap))


def commit_bytes(columns: int, leaves: int, cap: int) -> int:
    return (
        FIELD_BYTES * int(columns) * int(leaves)
        + DIGEST_BYTES * (int(leaves) + node_compressions(leaves, cap))
    )


def cost(shapes: dict) -> dict:
    """Per prove: witness, stage-2 and quotient commits."""
    N, cap = shapes["N"], shapes["cap"]
    commits = prove_commits(shapes)
    return {
        "ops": sum(
            leaf_compressions(b, N) + node_compressions(N, cap)
            for b in commits
        ),
        "bytes": sum(commit_bytes(b, N, cap) for b in commits),
        "bound": "arithmetic",
    }
