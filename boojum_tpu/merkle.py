"""Merkle tree with cap — device construction, under the key's tree hasher.

Counterpart of `/root/reference/src/cs/oracle/merkle_tree.rs:17` (construct
`:78`, get_proof `:462`, verify_proof_over_cap `:482`). Leaves are rows of a
(num_leaves, leaf_width) device array (all committed columns evaluated at one
LDE point, in full-domain bit-reversed enumeration); leaf hashing is one
batched sponge over the whole array, node layers are batched 2-to-1 hashes.
The cap (top 2^k nodes) replaces the single root. Query-path extraction
gathers from the stored device layers on host at query time (queries are rare:
~100 per proof).

Two tree hashers (`ProofConfig.tree_hasher`, kept in the key): Poseidon2
over Goldilocks, the default and the one a circuit can verify, and
Blake2s-256 (`hashes/blake2s.py`), upstream's for proofs nothing recurses
over. A digest is four u64 words either way, so the trees, caps, paths and
proofs keep their shapes. `tree_hasher(name)` hands a prove its hasher's
programs, once; the Poseidon2 record holds the functions below themselves.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .field import limbs as _limbs
from .hashes import blake2s as _b2s
from .hashes.poseidon2 import (
    Poseidon2SpongeHost,
    leaf_hash,
    leaf_hash_planes,
    node_hash,
    node_hash_planes,
)
from .parallel.sharding import host_np as _host_np
from .utils import metrics as _metrics


# Levels at or below this node count are fused into one compiled graph:
# the tail of a tree is ~log2(N) tiny dispatches whose launch overhead
# dominates their compute, while the big bottom levels amortize their
# dispatch over real compute (and fusing THEM produced modules that took
# minutes to compile).
_FUSE_THRESHOLD = 1 << 12


@partial(jax.jit, static_argnums=(1,))
def _tree_tail_layers(digests, cap_size: int):
    """All remaining (small) node layers in one compiled graph."""
    layers = []
    cur = digests
    while cur.shape[0] > cap_size:
        cur = node_hash(cur[0::2], cur[1::2])
        layers.append(cur)
    return tuple(layers)


def _node_layers(digests, cap_size: int):
    """Digest layers from leaf digests up to the cap (shared by the
    materialized and streamed-commit paths)."""
    layers = [digests]
    while (
        layers[-1].shape[0] > cap_size
        and layers[-1].shape[0] > _FUSE_THRESHOLD
    ):
        cur = layers[-1]
        layers.append(node_hash(cur[0::2], cur[1::2]))
    if layers[-1].shape[0] > cap_size:
        layers.extend(_tree_tail_layers(layers[-1], cap_size))
    return tuple(layers)


def _tree_layers(leaf_values, cap_size: int):
    return _node_layers(leaf_hash(leaf_values), cap_size)


# ---------------------------------------------------------------------------
# Shape-keyed commit kernels (the compile-bill split, ISSUE 1)
# ---------------------------------------------------------------------------
# The fused one-graph-per-commit form (`_commit_fused`) paid a 200s+ remote
# compile PER ORACLE SHAPE because the NTTs, the leaf sponge and the node
# layers all landed in one module. Split, each sub-graph compiles in well
# under a minute AND the node-layer stack — keyed only on (num_leaves, cap),
# not on the oracle's column count — is compiled ONCE and shared by the
# witness/stage-2/quotient/setup commits and the streamed-digest path.


@jax.jit
def leaf_digests_device(lde_cols):
    """(B, ...) committed columns -> (N, 4) leaf digests, one dispatch.

    Accepts the prover's (B, L, n) LDE stacks or already-flat (B, N)
    columns; the leaf-major transpose happens inside the graph so no
    intermediate (N, B) matrix is ever dispatched eagerly. Keyed on the
    column stack shape."""
    B = lde_cols.shape[0]
    return leaf_hash(lde_cols.reshape(B, -1).T)


@partial(jax.jit, static_argnums=(1,))
def node_layers_device(digests, cap_size: int):
    """(N, 4) leaf digests -> all node layers up to the cap, one dispatch.

    Keyed only on (N, cap): every oracle of the same domain size reuses the
    same executable regardless of how many columns it commits."""
    return _node_layers(digests, cap_size)


def commit_layers_device(lde_cols, cap_size: int):
    """Column stack -> digest layers (leaves first, cap last) as two
    shape-keyed dispatches: leaf sponge + shared node stack."""
    _metrics.count("merkle.commit_layer_builds")
    return node_layers_device(leaf_digests_device(lde_cols), cap_size)


# ---------------------------------------------------------------------------
# Limb-plane commit kernels + tree (ISSUE 10): digests stay (lo, hi) u32
# plane pairs on device end-to-end; u64 exists only on HOST — the cap join
# and query-path joins happen in numpy at the transcript/query API edge.
# ---------------------------------------------------------------------------


@jax.jit
def leaf_digests_planes(lde_p):
    """Plane twin of leaf_digests_device: (B, ...) column planes ->
    (N, 4) digest planes, one dispatch."""
    lo, hi = lde_p
    B = lo.shape[0]
    return leaf_hash_planes((lo.reshape(B, -1).T, hi.reshape(B, -1).T))


@partial(jax.jit, static_argnums=(1,))
def _tree_tail_layers_planes(digests_p, cap_size: int):
    layers = []
    cur = digests_p
    while cur[0].shape[0] > cap_size:
        cur = node_hash_planes(
            (cur[0][0::2], cur[1][0::2]), (cur[0][1::2], cur[1][1::2])
        )
        layers.append(cur)
    return tuple(layers)


def _node_layers_planes_body(digests_p, cap_size: int):
    layers = [digests_p]
    while (
        layers[-1][0].shape[0] > cap_size
        and layers[-1][0].shape[0] > _FUSE_THRESHOLD
    ):
        cur = layers[-1]
        layers.append(
            node_hash_planes(
                (cur[0][0::2], cur[1][0::2]), (cur[0][1::2], cur[1][1::2])
            )
        )
    if layers[-1][0].shape[0] > cap_size:
        layers.extend(_tree_tail_layers_planes(layers[-1], cap_size))
    return tuple(layers)


@partial(jax.jit, static_argnums=(1,))
def node_layers_planes(digests_p, cap_size: int):
    """Plane twin of node_layers_device (same shared-executable keying)."""
    return _node_layers_planes_body(digests_p, cap_size)


def commit_layers_planes(lde_p, cap_size: int):
    """Plane twin of commit_layers_device."""
    _metrics.count("merkle.commit_layer_builds")
    return node_layers_planes(leaf_digests_planes(lde_p), cap_size)


class TreeHasher(NamedTuple):
    """One tree hasher's commit programs, by representation."""

    name: str
    leaf_digests_device: Callable
    node_layers_device: Callable
    commit_layers_device: Callable
    leaf_digests_planes: Callable
    node_layers_planes: Callable
    commit_layers_planes: Callable

    @property
    def tag(self) -> str:
        """What the kernel library's names carry: nothing for Poseidon2,
        whose names are what they were."""
        return "" if self.name == "poseidon2" else f"_{self.name}"


POSEIDON2 = TreeHasher(
    "poseidon2",
    leaf_digests_device, node_layers_device, commit_layers_device,
    leaf_digests_planes, node_layers_planes, commit_layers_planes,
)


# ---------------------------------------------------------------------------
# Blake2s commit kernels: the same two shape-keyed dispatches a commit, on
# the word vectors of hashes/blake2s.py (plain XLA: add, xor, rotate). The
# columns are the message as they lie: no leaf-major transpose, no reshape.
# Made when a Blake2s key first asks (`tree_hasher`), not at import: the
# programs are jitted for the backend that is then known (_b2s.jit).
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _blake2s_hasher() -> TreeHasher:
    @_b2s.jit
    def leaf_digests_blake2s_planes(lde_p):
        """(B, ...) column planes -> (N, 4) Blake2s digest planes."""
        return _b2s.leaf_hash_planes(*lde_p)

    @partial(_b2s.jit, static_argnums=(1,))
    def node_layers_blake2s_planes(digests_p, cap_size: int):
        """(N, 4) digest planes -> every layer down to the cap."""
        return _b2s.node_layers_planes(digests_p, cap_size)

    @_b2s.jit
    def leaf_digests_blake2s_device(lde_cols):
        """The u64 twin (split and joined inside the program)."""
        return _b2s.leaf_hash_u64(lde_cols)

    @partial(_b2s.jit, static_argnums=(1,))
    def node_layers_blake2s_device(digests, cap_size: int):
        return _b2s.node_layers_u64(digests, cap_size)

    def counted(leaf_digests, node_layers):
        def commit_layers(lde, cap_size: int):
            shape = (lde[0] if isinstance(lde, tuple) else lde).shape
            _metrics.count("merkle.commit_layer_builds")
            _metrics.count(
                "merkle.blake2s_compressions",
                _b2s.compressions(
                    shape[0], int(np.prod(shape[1:])), cap_size
                ),
            )
            return node_layers(leaf_digests(lde), cap_size)

        return commit_layers

    return TreeHasher(
        "blake2s",
        leaf_digests_blake2s_device, node_layers_blake2s_device,
        counted(leaf_digests_blake2s_device, node_layers_blake2s_device),
        leaf_digests_blake2s_planes, node_layers_blake2s_planes,
        counted(leaf_digests_blake2s_planes, node_layers_blake2s_planes),
    )


def tree_hasher(name: str = "poseidon2") -> TreeHasher:
    """The hasher a key names (`vk.tree_hasher`; a key from before the
    field is a Poseidon2 key)."""
    if name == "poseidon2":
        return POSEIDON2
    if name == "blake2s":
        return _blake2s_hasher()
    raise ValueError(f"unknown tree hasher: {name!r}")


def _paths_by_query(levels, num_queries: int):
    """The gathered sibling levels, each (queries, 4) u64 on the host, as
    one path of digest tuples a query. The conversion to Python ints is
    numpy's own loop (`tolist`): the device is idle while the host builds
    the openings, so this sits on a prove's critical path."""
    rows = [np.asarray(level).tolist() for level in levels]
    return [[tuple(r[q]) for r in rows] for q in range(num_queries)]


def _cap_host_from_planes(cap_p):
    cap = _limbs.join_np(_host_np(cap_p[0]), _host_np(cap_p[1]))
    return [tuple(int(x) for x in row) for row in cap]


class PlaneMerkleTree:
    """MerkleTreeWithCap twin whose digest layers stay u32 plane pairs.

    Caps and query paths leave the device as planes and join on HOST
    (numpy) — the representation's API edge. Digest VALUES are identical
    to the u64 tree's, so transcripts and proofs are unchanged."""

    @classmethod
    def from_layers(cls, layers, cap_size: int) -> "PlaneMerkleTree":
        tree = cls.__new__(cls)
        tree.cap_size = cap_size
        tree.num_leaves = int(layers[0][0].shape[0])
        _metrics.count("merkle.tree_builds")
        _metrics.count("merkle.plane_tree_builds")
        tree.layers = list(layers)
        tree._cap_host = _cap_host_from_planes(tree.layers[-1])
        return tree

    @classmethod
    def from_digests(cls, digests_p, cap_size: int) -> "PlaneMerkleTree":
        n = int(digests_p[0].shape[0])
        assert n & (n - 1) == 0 and cap_size & (cap_size - 1) == 0
        assert n >= cap_size
        return cls.from_layers(
            list(node_layers_planes(digests_p, cap_size)), cap_size
        )

    def get_cap(self):
        return list(self._cap_host)

    def proof_gather_plans(self, leaf_indices):
        """Like MerkleTreeWithCap.proof_gather_plans, but each level
        contributes TWO plans (lo, hi); assemble() joins pairs on host."""
        idxs = np.array(list(leaf_indices), dtype=np.int64)
        plans = []
        cur = idxs
        for lo, hi in self.layers[:-1]:
            sib = cur ^ 1
            plans.append((lo, sib))
            plans.append((hi, sib))
            cur = cur >> 1

        def assemble(levels):
            joined = [
                _limbs.join_np(levels[2 * i], levels[2 * i + 1])
                for i in range(len(levels) // 2)
            ]
            return _paths_by_query(joined, len(idxs))

        return plans, assemble


class MerkleTreeWithCap:
    def __init__(self, leaf_values, cap_size: int, num_elems_per_leaf: int = 1):
        """leaf_values: (num_leaves, leaf_width) uint64 device array.

        num_elems_per_leaf > 1 groups that many adjacent rows into one leaf
        (used by FRI intermediate oracles, mirroring the reference's
        `num_elements_per_leaf`); leaf width becomes width*num_elems.
        """
        assert cap_size & (cap_size - 1) == 0
        n = leaf_values.shape[0]
        if num_elems_per_leaf > 1:
            leaf_values = leaf_values.reshape(
                n // num_elems_per_leaf, -1
            )
        self.num_leaves = leaf_values.shape[0]
        assert self.num_leaves & (self.num_leaves - 1) == 0, "leaf count must be 2^k"
        assert self.num_leaves >= cap_size
        self.cap_size = cap_size
        _metrics.count("merkle.tree_builds")
        self.layers = list(_tree_layers(leaf_values, cap_size))
        self._cap_host = [
            tuple(int(x) for x in row) for row in _host_np(self.layers[-1])
        ]

    @classmethod
    def from_digests(cls, digests, cap_size: int) -> "MerkleTreeWithCap":
        """Build the node layers over precomputed (num_leaves, 4) leaf
        digests — the streamed-commit path hashes leaves in column blocks
        (absorbing 8 columns at a time into a carried sponge state) and
        hands the finished digests here, so a full (num_leaves, width)
        leaf matrix never materializes."""
        tree = cls.__new__(cls)
        n = int(digests.shape[0])
        assert n & (n - 1) == 0, "leaf count must be 2^k"
        assert cap_size & (cap_size - 1) == 0 and n >= cap_size
        tree.cap_size = cap_size
        tree.num_leaves = n
        _metrics.count("merkle.tree_builds")
        tree.layers = list(node_layers_device(digests, cap_size))
        tree._cap_host = [
            tuple(int(x) for x in row) for row in _host_np(tree.layers[-1])
        ]
        return tree

    @classmethod
    def from_layers(cls, layers, cap_size: int) -> "MerkleTreeWithCap":
        """Rebuild a tree from precomputed digest layers (setup fast
        deserialization — no rehashing, reference fast_serialization.rs)."""
        tree = cls.__new__(cls)
        tree.cap_size = cap_size
        tree.num_leaves = int(layers[0].shape[0])
        _metrics.count("merkle.tree_builds")
        tree.layers = list(layers)
        tree._cap_host = [
            tuple(int(x) for x in row) for row in _host_np(layers[-1])
        ]
        return tree

    def get_cap(self):
        return list(self._cap_host)

    def proof_gather_plans(self, leaf_indices):
        """Like proof_gathers, but returns (layer, sibling-index) PLANS
        without dispatching any device op — the prover executes every
        oracle's plans in one fused gather (see prover._gather_flat_fused)."""
        idxs = np.array(list(leaf_indices), dtype=np.int64)
        plans = []
        cur = idxs
        for layer in self.layers[:-1]:
            plans.append((layer, cur ^ 1))
            cur = cur >> 1

        def assemble(levels):
            return _paths_by_query(levels, len(idxs))

        return plans, assemble

    def proof_gathers(self, leaf_indices):
        """Dispatch the per-level sibling gathers WITHOUT transferring:
        returns (lazy device arrays, assemble(levels) -> paths)."""
        plans, assemble = self.proof_gather_plans(leaf_indices)
        pending = [layer[jnp.asarray(ix)] for layer, ix in plans]
        return pending, assemble

    def get_proofs(self, leaf_indices):
        """Batched path extraction for many queries: ONE device gather per
        tree level (a (num_queries, 4) slice) instead of per-query
        per-level element reads: fewer, larger device-to-host transfers.
        Returns a list of paths aligned with leaf_indices."""
        pending, assemble = self.proof_gathers(leaf_indices)
        levels = [_host_np(x) for x in pending]
        return assemble(levels)

    def get_proof(self, leaf_idx: int):
        """Single-query path (see get_proofs for the batched form)."""
        return self.get_proofs([leaf_idx])[0]


def verify_proof_over_cap(
    leaf_values, path, cap, leaf_idx: int, hasher: str = "poseidon2"
) -> bool:
    """Host-side path verification (python ints), reference `:482`
    semantics; a Blake2s path through `hashlib` (compat/blake2s_tree.py),
    never through the device hash."""
    if hasher == "blake2s":
        from .compat.blake2s_tree import verify_path

        return verify_path(leaf_values, path, cap, leaf_idx)
    assert hasher == "poseidon2", hasher
    digest = Poseidon2SpongeHost.hash_leaf([int(v) for v in leaf_values])
    idx = leaf_idx
    for sib in path:
        if idx & 1:
            digest = Poseidon2SpongeHost.hash_node(sib, digest)
        else:
            digest = Poseidon2SpongeHost.hash_node(digest, sib)
        idx >>= 1
    return tuple(digest) == tuple(cap[idx])
