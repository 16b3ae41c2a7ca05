"""The Blake2s Merkle tree with a cap, in `hashlib` and numpy alone.

The plain reference of the `tree_hasher="blake2s"` deployments (upstream's
`TreeHasher for Blake2s256`, `src/cs/oracle/mod.rs:84`, written from the
rule and not from the file, which is not in this repository):

  leaf    Blake2s-256 of the leaf's field elements, 8 bytes little-endian
          each, in column order
  node    Blake2s-256 of `left || right`, 32 bytes each
  digest  as four u64 words: the 32 bytes read little-endian, 8 a word

`prover/verifier.py::verify` and `compat/verifier.py::_verify_merkle_path`
check every opened leaf and path of a Blake2s key through this file, so the
verdict on a proof never runs the device hash that made it
(`hashes/blake2s.py`); the tests hold both to each other word for word.
"""

from __future__ import annotations

import hashlib

import numpy as np


def _words(block: bytes) -> tuple:
    return tuple(
        int.from_bytes(block[i : i + 8], "little") for i in range(0, 32, 8)
    )


def digest_bytes(digest) -> bytes:
    return b"".join(int(w).to_bytes(8, "little") for w in digest)


def leaf_digest(elements) -> tuple:
    data = b"".join(int(e).to_bytes(8, "little") for e in elements)
    return _words(hashlib.blake2s(data).digest())


def node_digest(left, right) -> tuple:
    return _words(
        hashlib.blake2s(digest_bytes(left) + digest_bytes(right)).digest()
    )


def tree_layers(rows, cap_size: int) -> list:
    """`rows`: (num_leaves, columns) u64 elements, a row a leaf. Returns
    the digest layers, leaves first and the cap last, each (n, 4) u64."""
    rows = np.ascontiguousarray(np.asarray(rows, dtype="<u8"))
    n = rows.shape[0]
    assert n & (n - 1) == 0 and cap_size & (cap_size - 1) == 0
    assert n >= cap_size
    cur = [hashlib.blake2s(rows[i].tobytes()).digest() for i in range(n)]
    layers = [cur]
    while len(cur) > cap_size:
        cur = [
            hashlib.blake2s(cur[i] + cur[i + 1]).digest()
            for i in range(0, len(cur), 2)
        ]
        layers.append(cur)
    return [
        np.frombuffer(b"".join(layer), dtype="<u8").reshape(-1, 4)
        for layer in layers
    ]


def cap_of(layers) -> list:
    return [tuple(int(w) for w in row) for row in layers[-1]]


def path_of(layers, leaf_idx: int) -> list:
    """The siblings from the leaf layer up to the layer below the cap."""
    path = []
    idx = int(leaf_idx)
    for layer in layers[:-1]:
        path.append(tuple(int(w) for w in layer[idx ^ 1]))
        idx >>= 1
    return path


def verify_path(leaf_elements, path, cap, leaf_idx: int) -> bool:
    digest = leaf_digest(leaf_elements)
    idx = int(leaf_idx)
    for sibling in path:
        if idx & 1:
            digest = node_digest(sibling, digest)
        else:
            digest = node_digest(digest, sibling)
        idx >>= 1
    return tuple(digest) == tuple(int(w) for w in cap[idx])
