"""A plain reference of the streamed commit: monomials -> the values on the
rate-L domain -> leaf digests -> the binary tree's cap, and the opened leaf
rows at given indices.

Numpy and Python ints only. It shares nothing with what it checks: no
module of boojum_tpu/prover, boojum_tpu/ntt or boojum_tpu/merkle is
imported, the field multiplication below is written out here, the transform
is the textbook iterative radix-2 NTT (or, for a few points, Horner's rule
on Python ints), and the hash is a sponge over the one host permutation
every reference of the repo runs (`hashes/poseidon2.py::
poseidon2_permutation_host`, Python ints).

The domain. A column's polynomial f of degree below n is committed on the
coset g * <w_N> of size N = L * n, g the field's multiplicative generator
7. Leaf i holds f(g * w_N^brev(i)) for every column, brev the reversal of
log2(N) bits: the whole coset in bit-reversed order (which is L cosets of
the size-n subgroup one after another, each in bit-reversed order, as the
prover lays its storages out). A leaf's digest is the overwrite-mode sponge
of rate 8 and capacity 4 over its B values, the last chunk padded with
zeros; a node is the sponge over its two children's digests (8 elements,
one permutation); the cap is the layer with `cap` nodes.
"""

from __future__ import annotations

import numpy as np

P = (1 << 64) - (1 << 32) + 1
GENERATOR = 7  # the coset shift, and a generator of the field's units
_MASK32 = np.uint64(0xFFFFFFFF)
_EPS = np.uint64(0xFFFFFFFF)  # 2^64 mod P


# ---------------------------------------------------------------------------
# Goldilocks on numpy uint64, written out (no field module imported)
# ---------------------------------------------------------------------------


def add_mod(a, b):
    a, b = np.asarray(a, np.uint64), np.asarray(b, np.uint64)
    with np.errstate(over="ignore"):
        s = a + b
        s = np.where(s < a, s + _EPS, s)  # wrapped past 2^64: add 2^64 mod P
    return np.where(s >= np.uint64(P), s - np.uint64(P), s)


def sub_mod(a, b):
    a, b = np.asarray(a, np.uint64), np.asarray(b, np.uint64)
    with np.errstate(over="ignore"):
        d = a - b
        return np.where(a < b, d + np.uint64(P), d)


def mul_mod(a, b):
    """a * b mod P for canonical uint64 arrays: the 128-bit product from
    four 32-bit pieces, then 2^64 = 2^32 - 1 and 2^96 = -1 (mod P)."""
    a, b = np.broadcast_arrays(np.asarray(a, np.uint64), np.asarray(b, np.uint64))
    with np.errstate(over="ignore"):
        a0, a1 = a & _MASK32, a >> np.uint64(32)
        b0, b1 = b & _MASK32, b >> np.uint64(32)
        ll, lh, hl, hh = a0 * b0, a0 * b1, a1 * b0, a1 * b1
        mid = (ll >> np.uint64(32)) + (lh & _MASK32) + (hl & _MASK32)
        lo = (ll & _MASK32) | ((mid & _MASK32) << np.uint64(32))
        hi = hh + (lh >> np.uint64(32)) + (hl >> np.uint64(32)) \
            + (mid >> np.uint64(32))
        # x = lo + 2^64 * hi_lo + 2^96 * hi_hi = lo - hi_hi + (2^32 - 1) * hi_lo
        hi_lo, hi_hi = hi & _MASK32, hi >> np.uint64(32)
        t = lo - hi_hi
        t = np.where(lo < hi_hi, t - _EPS, t)  # borrowed 2^64: take 2^64 mod P off
        u = hi_lo * _EPS  # below 2^64
        r = t + u
        r = np.where(r < t, r + _EPS, r)
    return np.where(r >= np.uint64(P), r - np.uint64(P), r)


def root_of_unity(log_n: int) -> int:
    """A primitive 2^log_n-th root: 7^((P - 1) / 2^log_n)."""
    return pow(GENERATOR, (P - 1) >> log_n, P)


def powers(base: int, count: int) -> np.ndarray:
    """base^0 .. base^(count - 1), by doubling."""
    out = np.ones(count, np.uint64)
    have, step = 1, int(base) % P
    while have < count:
        take = min(have, count - have)
        out[have : have + take] = mul_mod(out[:take], np.uint64(step))
        have += take
        step = step * step % P
    return out


def bit_reverse(i, bits: int):
    i = np.asarray(i, np.int64)
    out = np.zeros_like(i)
    for _ in range(bits):
        out = (out << 1) | (i & 1)
        i = i >> 1
    return out


# ---------------------------------------------------------------------------
# The transform
# ---------------------------------------------------------------------------


def ntt(coeffs: np.ndarray) -> np.ndarray:
    """(.., m) coefficients -> (.., m) values f(w^k), k = 0 .. m - 1 in
    natural order: the textbook iterative Cooley-Tukey (decimation in time
    over bit-reversed input)."""
    a = np.array(coeffs, np.uint64)
    m = a.shape[-1]
    bits = m.bit_length() - 1
    assert m == 1 << bits
    a = a[..., bit_reverse(np.arange(m), bits)]
    w = powers(root_of_unity(bits), m // 2)
    half = 1
    while half < m:
        tw = w[:: m // (2 * half)][:half]
        blocks = a.reshape(a.shape[:-1] + (m // (2 * half), 2, half))
        u, v = blocks[..., 0, :], mul_mod(blocks[..., 1, :], tw)
        a = np.stack([add_mod(u, v), sub_mod(u, v)], axis=-2).reshape(a.shape)
        half *= 2
    return a


def intt(values: np.ndarray) -> np.ndarray:
    """(.., m) values over the subgroup in natural order -> coefficients."""
    v = np.asarray(values, np.uint64)
    m = v.shape[-1]
    out = ntt(v)
    # the inverse transform is the forward one read backwards, over m
    out = np.concatenate([out[..., :1], out[..., :0:-1]], axis=-1)
    return mul_mod(out, np.uint64(pow(m, P - 2, P)))


def lde_values(mono: np.ndarray, L: int) -> np.ndarray:
    """(B, n) monomial coefficients -> (B, N) values, leaf order."""
    mono = np.asarray(mono, np.uint64)
    B, n = mono.shape
    N = n * L
    shifted = mul_mod(mono, powers(GENERATOR, n)[None, :])  # f(g x)
    padded = np.zeros((B, N), np.uint64)
    padded[:, :n] = shifted
    natural = ntt(padded)
    return natural[:, bit_reverse(np.arange(N), N.bit_length() - 1)]


def leaf_point(i: int, N: int) -> int:
    """The domain point leaf i is evaluated at."""
    bits = N.bit_length() - 1
    return GENERATOR * pow(root_of_unity(bits), int(bit_reverse(i, bits)), P) % P


def evaluate_rows(mono: np.ndarray, L: int, indices) -> np.ndarray:
    """(len(indices), B) leaf rows by Horner's rule on Python ints: the
    direct evaluation, for a few points of a small polynomial."""
    mono = np.asarray(mono, np.uint64)
    N = mono.shape[1] * L
    rows = []
    for i in indices:
        x = leaf_point(int(i), N)
        row = []
        for col in mono:
            acc = 0
            for c in col[::-1]:
                acc = (acc * x + int(c)) % P
            row.append(acc)
        rows.append(row)
    return np.array(rows, np.uint64)


# ---------------------------------------------------------------------------
# The sponge and the tree
# ---------------------------------------------------------------------------


def leaf_digest(row) -> tuple:
    """Overwrite-mode sponge, rate 8, capacity 4, zero-padded last chunk."""
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation_host

    row = [int(v) for v in row]
    state = [0] * 12
    for k in range(0, max(len(row), 1), 8):
        chunk = row[k : k + 8]
        state[:8] = chunk + [0] * (8 - len(chunk))
        state = poseidon2_permutation_host(state)
    return tuple(state[:4])


def node_digest(left, right) -> tuple:
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation_host

    state = [int(v) for v in left] + [int(v) for v in right] + [0] * 4
    return tuple(poseidon2_permutation_host(state)[:4])


def tree_cap(digests: list, cap: int) -> list:
    layer = list(digests)
    assert len(layer) >= cap and cap & (cap - 1) == 0
    while len(layer) > cap:
        layer = [
            node_digest(layer[i], layer[i + 1]) for i in range(0, len(layer), 2)
        ]
    return layer


def cap_from_path(digest, index: int, path, num_leaves: int, cap: int) -> tuple:
    """Walk a leaf's digest up its authentication path: (cap index, node)."""
    node, i = tuple(int(v) for v in digest), int(index)
    for sibling in path:
        sibling = tuple(int(v) for v in sibling)
        node = node_digest(sibling, node) if i & 1 else node_digest(node, sibling)
        i >>= 1
    assert num_leaves >> len(path) == cap
    return i, node


def commit(mono: np.ndarray, L: int, cap: int) -> list:
    """The cap of the commitment to the rate-L values of `mono`."""
    values = lde_values(mono, L)
    return tree_cap([leaf_digest(values[:, i]) for i in range(values.shape[1])],
                    cap)


def opened_rows(mono: np.ndarray, L: int, indices) -> np.ndarray:
    """(len(indices), B) leaf values at `indices`, through the transform."""
    return lde_values(mono, L)[:, np.asarray(indices, np.int64)].T
