"""An in-circuit Poseidon2 Merkle tree: what the recursion's tree hasher
does inside a verifier circuit (reference src/gadgets/poseidon2/mod.rs over
src/cs/gates/poseidon2.rs's flattened gate, and
gadgets/recursion/recursive_tree_hasher.rs; BASELINE.json configs[1]).
`leaves` leaves of `leaf_elements` field elements drawn from the seed, each
hashed by the overwrite sponge (rate 8, capacity 4), then node hashes layer
by layer (two 4-element digests, one permutation) to one root, whose 4
elements are the public inputs. Every permutation is one row of the
130-column `Poseidon2FlattenedGate`: `leaves` x ceil(`leaf_elements` / 8)
+ `leaves` - 1 gate rows.

The plain reference of the circuit's semantics is `reference_root` below:
the Poseidon2 permutation of the paper (eprint 2023/323) over Goldilocks in
numpy on whole layers at once, written from `hashes/poseidon2_params.py`'s
constants alone: none of the gadget's, the gate's or the prover's hashing
code. `build` asserts that the root in the witness equals it, so a run whose
proofs verify has proved the reference root of the seeded leaves.
`tests/test_poseidon2_era.py` holds the reference to `Poseidon2SpongeHost`
leaf by leaf and node by node.
"""

from __future__ import annotations

import numpy as np

P = (1 << 64) - (1 << 32) + 1
RATE = 8
CAPACITY = 4
WIDTH = RATE + CAPACITY
HALF_FULL_ROUNDS = 4
PARTIAL_ROUNDS = 22
_EPS = np.uint64((1 << 32) - 1)
_MASK32 = np.uint64(0xFFFFFFFF)
_S32 = np.uint64(32)
_P = np.uint64(P)


# -- Goldilocks on uint64 arrays (canonical in, canonical out) ---------------


def _add(a, b):
    s = a + b
    # a carry out of 64 bits is worth 2^64 = 2^32 - 1 (mod p)
    s = np.where(s < a, s + _EPS, s)
    return np.where(s >= _P, s - _P, s)


def _mul(a, b):
    a0, a1 = a & _MASK32, a >> _S32
    b0, b1 = b & _MASK32, b >> _S32
    ll, lh, hl, hh = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (ll >> _S32) + (lh & _MASK32) + (hl & _MASK32)
    lo = (ll & _MASK32) | (mid << _S32)
    hi = hh + (lh >> _S32) + (hl >> _S32) + (mid >> _S32)
    # 2^64 = 2^32 - 1 and 2^96 = -1 (mod p)
    hi_lo, hi_hi = hi & _MASK32, hi >> _S32
    t = lo - hi_hi
    t = np.where(lo < hi_hi, t - _EPS, t)
    u = hi_lo * _EPS
    s = t + u
    s = np.where(s < u, s + _EPS, s)
    return np.where(s >= _P, s - _P, s)


def _pow7(x):
    x2 = _mul(x, x)
    x3 = _mul(x2, x)
    return _mul(_mul(x2, x2), x3)


# -- the permutation on (12, N) states ---------------------------------------

_M4 = ((5, 7, 1, 3), (4, 6, 1, 1), (1, 3, 5, 7), (1, 1, 4, 6))


def _times_small(x, k):
    """k * x for k in 1..7 by additions."""
    acc = None
    while k:
        if k & 1:
            acc = x if acc is None else _add(acc, x)
        k >>= 1
        if k:
            x = _add(x, x)
    return acc


def _external_matrix(s):
    """circ(2 M4, M4, M4): M4 on each block of four, plus the sum of the
    three blocks' results."""
    blocks = []
    for b in range(3):
        x = s[4 * b : 4 * b + 4]
        rows = []
        for r in range(4):
            acc = None
            for c in range(4):
                term = _times_small(x[c], _M4[r][c])
                acc = term if acc is None else _add(acc, term)
            rows.append(acc)
        blocks.append(rows)
    sums = [_add(_add(blocks[0][i], blocks[1][i]), blocks[2][i]) for i in range(4)]
    return np.stack([_add(blocks[b][i], sums[i]) for b in range(3) for i in range(4)])


def _internal_matrix(s, diagonal):
    total = s[0]
    for i in range(1, WIDTH):
        total = _add(total, s[i])
    return _add(_mul(s, diagonal[:, None]), total[None, :])


def permutation(state: np.ndarray) -> np.ndarray:
    """Poseidon2 over Goldilocks, t = 12, x^7, 4 + 22 + 4 rounds, on a
    (12, N) uint64 array of N states."""
    from boojum_tpu.hashes import poseidon2_params as params

    rc = np.array(params.ALL_ROUND_CONSTANTS, dtype=np.uint64).reshape(-1, WIDTH)
    diagonal = np.array(params.M_I_DIAGONAL, dtype=np.uint64)
    s = _external_matrix(np.asarray(state, dtype=np.uint64))
    for r in range(HALF_FULL_ROUNDS):
        s = _external_matrix(_pow7(_add(s, rc[r][:, None])))
    for r in range(HALF_FULL_ROUNDS, HALF_FULL_ROUNDS + PARTIAL_ROUNDS):
        s = s.copy()
        s[0] = _pow7(_add(s[0], rc[r][0]))
        s = _internal_matrix(s, diagonal)
    for r in range(HALF_FULL_ROUNDS + PARTIAL_ROUNDS,
                   2 * HALF_FULL_ROUNDS + PARTIAL_ROUNDS):
        s = _external_matrix(_pow7(_add(s, rc[r][:, None])))
    return s


# -- the sponge and the tree --------------------------------------------------


def reference_leaf_digests(leaves: np.ndarray) -> np.ndarray:
    """(N, L) leaves -> (N, 4) digests: each chunk of 8 overwrites the rate
    part of the state and is permuted; a last partial chunk is padded with
    zeros."""
    leaves = np.asarray(leaves, dtype=np.uint64)
    n, length = leaves.shape
    state = np.zeros((WIDTH, n), dtype=np.uint64)
    for at in range(0, length, RATE):
        chunk = leaves[:, at : at + RATE].T
        state[: chunk.shape[0]] = chunk
        state[chunk.shape[0] : RATE] = 0
        state = permutation(state)
    return state[:CAPACITY].T.copy()


def reference_node_digests(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """Two (N, 4) digest arrays -> (N, 4) parents: one permutation of
    [left, right, 0, 0, 0, 0]."""
    n = left.shape[0]
    state = np.zeros((WIDTH, n), dtype=np.uint64)
    state[:CAPACITY] = np.asarray(left, dtype=np.uint64).T
    state[CAPACITY:RATE] = np.asarray(right, dtype=np.uint64).T
    return permutation(state)[:CAPACITY].T.copy()


def reference_root(leaves: np.ndarray) -> list[int]:
    layer = reference_leaf_digests(leaves)
    while layer.shape[0] > 1:
        layer = reference_node_digests(layer[0::2], layer[1::2])
    return [int(v) for v in layer[0]]


def leaf_values(num_leaves: int, leaf_elements: int, seed: int) -> np.ndarray:
    """The request's payload: (`num_leaves`, `leaf_elements`) field elements
    drawn from the seed."""
    return np.random.default_rng(int(seed)).integers(
        0, P, size=(int(num_leaves), int(leaf_elements)), dtype=np.uint64
    )


def gate_rows(num_leaves: int, leaf_elements: int) -> int:
    return num_leaves * -(-leaf_elements // RATE) + num_leaves - 1


def build(params: dict, seed: int):
    """`params` is the configuration's `circuit.params` merged with the
    traffic mix's `request`. Returns the synthesized ConstraintSystem."""
    from boojum_tpu.cs.gates import PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.types import CSGeometry
    from boojum_tpu.gadgets.poseidon2_rf import circuit_merkle_root

    num_leaves, leaf_elements = int(params["leaves"]), int(params["leaf_elements"])
    assert num_leaves & (num_leaves - 1) == 0 and num_leaves > 0, num_leaves
    assert int(params.get("lookup_args", 0)) == 0, "this body has no table"
    geometry = CSGeometry(
        num_columns_under_copy_permutation=int(params["copy_columns"]),
        num_witness_columns=int(params.get("witness_columns", 0)),
        num_constant_columns=int(params["constant_columns"]),
        max_allowed_constraint_degree=int(params["constraint_degree"]),
    )
    # a capacity bound: pad_and_shrink rounds the trace to the smallest
    # power of two that holds the gate rows and the public-input row
    rows = gate_rows(num_leaves, leaf_elements) + 1
    cs = ConstraintSystem(geometry, 1 << (rows - 1).bit_length())
    values = leaf_values(num_leaves, leaf_elements, seed)
    leaf_vars = [
        [cs.alloc_variable_with_value(int(v)) for v in leaf] for leaf in values
    ]
    root = circuit_merkle_root(cs, leaf_vars)
    for v in root:
        PublicInputGate.place(cs, v)
    got = [int(cs.get_value(v)) for v in root]
    want = reference_root(values)
    assert got == want, (
        f"the gadget's root {got} is not the reference Poseidon2 Merkle root "
        f"{want} of the seeded leaves"
    )
    return cs
