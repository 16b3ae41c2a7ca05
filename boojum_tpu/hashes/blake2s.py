"""Blake2s-256 over batches of leaves, as add, xor and rotate on u32 lanes.

The tree hasher of upstream's non-recursive benches (`TreeHasher for
Blake2s256`, `/root/reference/src/cs/oracle/mod.rs:84`): a leaf is the
hash of its field elements, 8 bytes little-endian each, in column order;
a node is the hash of `left || right`. The limb planes ARE the message:
an element's `(lo, hi)` pair is two little-endian 32-bit words of the
64-byte block, and the 32-byte digest is four little-endian u64 words,
that is an `(N, 4)` plane pair with `lo[:, j] = h[2j]`, `hi[:, j] =
h[2j + 1]`. A digest word is any 64-bit value (not below p).

Every function here works on WORD VECTORS: a state or message word is one
u32 array with an element a leaf (shaped as the columns lie: `(L, n)` for
an LDE storage, so that no column is reshaped, which on the chip is a copy
of it), so a compression is some 1,100 elementwise operations and nothing
is transposed inside it. The IV, the
message schedule and the rotations are Python ints at trace time: no
device array exists before a program is called. `compat/blake2s_tree.py`
(`hashlib`) is what the verifier runs and what the tests hold these to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_u32 = jnp.uint32

IV = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)
# digest_length 32, no key, fanout 1, depth 1 (the parameter block's first
# word) folded into the chaining value every hash starts from
H0 = (IV[0] ^ 0x01010020,) + IV[1:]
SIGMA = (
    (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    (14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3),
    (11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4),
    (7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8),
    (9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13),
    (2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9),
    (12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11),
    (13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10),
    (6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5),
    (10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0),
)
BLOCK_ELEMS = 8  # field elements a 64-byte block


def jit(fn, **kwargs):
    """`jax.jit` for a program of this file, on the backend as it is when
    the program is made (a Blake2s key's first use, never an import). On
    the CPU without XLA:CPU's fusion emitters: with them (jax 0.9.0) a
    consumer that regroups a compression's words (the stack into digest
    planes) takes the whole compression into each operand and the program
    runs for hours; the same integers either way (CHANGES.md, PR 24)."""
    if jax.default_backend() == "cpu":
        kwargs["compiler_options"] = {"xla_cpu_use_fusion_emitters": False}
    return jax.jit(fn, **kwargs)


def blocks_per_leaf(columns: int) -> int:
    return -(-int(columns) // BLOCK_ELEMS)


def _rotr(x, r: int):
    return (x >> _u32(r)) | (x << _u32(32 - r))


def _g(v, a, b, c, d, x, y):
    v[a] = v[a] + v[b] + x
    v[d] = _rotr(v[d] ^ v[a], 16)
    v[c] = v[c] + v[d]
    v[b] = _rotr(v[b] ^ v[c], 12)
    v[a] = v[a] + v[b] + y
    v[d] = _rotr(v[d] ^ v[a], 8)
    v[c] = v[c] + v[d]
    v[b] = _rotr(v[b] ^ v[c], 7)


def compress(h, m, t, final: bool):
    """One compression: `h` 8 chaining words, `m` 16 message words (word
    vectors or scalars), `t` the byte counter (below 2^32: an int or a
    traced u32 scalar), `final` the last-block flag."""
    v = list(h) + [_u32(c) for c in IV]
    v[12] = v[12] ^ (_u32(t) if isinstance(t, int) else t)
    if final:
        v[14] = _u32(IV[6] ^ 0xFFFFFFFF)
    for s in SIGMA:
        _g(v, 0, 4, 8, 12, m[s[0]], m[s[1]])
        _g(v, 1, 5, 9, 13, m[s[2]], m[s[3]])
        _g(v, 2, 6, 10, 14, m[s[4]], m[s[5]])
        _g(v, 3, 7, 11, 15, m[s[6]], m[s[7]])
        _g(v, 0, 5, 10, 15, m[s[8]], m[s[9]])
        _g(v, 1, 6, 11, 12, m[s[10]], m[s[11]])
        _g(v, 2, 7, 8, 13, m[s[12]], m[s[13]])
        _g(v, 3, 4, 9, 14, m[s[14]], m[s[15]])
    # the barrier keeps a compression a fusion of its own: a consumer that
    # regroups the words (the stack into digest planes, the next layer's
    # strided halves) is not handed the compression once an operand
    return jax.lax.optimization_barrier(
        tuple(h[i] ^ v[i] ^ v[i + 8] for i in range(8))
    )


def _block_words(lo8, hi8):
    """Message words of one block from its (<= 8, N) element planes: the
    elements' words interleaved, zeros past the last element."""
    zero = jnp.zeros(lo8.shape[1:], _u32)
    m = []
    for i in range(BLOCK_ELEMS):
        if i < lo8.shape[0]:
            m += [lo8[i], hi8[i]]
        else:
            m += [zero, zero]
    return m


def leaf_words(lo, hi):
    """(B, ...) column-major element planes -> the 8 digest words, each
    shaped as one column.

    All blocks but the last run in one `fori_loop` (one traced compression
    however wide the leaf); the last carries the final flag, the byte
    counter `8 B` and the zero padding, also when it is full."""
    B = lo.shape[0]
    last = blocks_per_leaf(B) - 1
    h = tuple(jnp.full(lo.shape[1:], c, _u32) for c in H0)

    def body(c, h):
        lo8 = jax.lax.dynamic_slice_in_dim(lo, BLOCK_ELEMS * c, BLOCK_ELEMS, 0)
        hi8 = jax.lax.dynamic_slice_in_dim(hi, BLOCK_ELEMS * c, BLOCK_ELEMS, 0)
        t = ((c + 1) * 64).astype(_u32)
        return compress(h, _block_words(lo8, hi8), t, False)

    if last > 0:
        h = jax.lax.fori_loop(0, last, body, h)
    tail = BLOCK_ELEMS * last
    return compress(h, _block_words(lo[tail:], hi[tail:]), 8 * B, True)


def node_words(w):
    """The 8 digest words of n nodes -> the words of their n / 2 parents:
    one full block `left || right` a parent. Siblings are the even and odd
    elements of the LEADING axis: rows of a 2-D word vector, lanes of a
    1-D one."""
    m = [x[0::2] for x in w] + [x[1::2] for x in w]
    h = tuple(jnp.full(m[0].shape, c, _u32) for c in H0)
    return compress(h, m, 64, True)


def words_to_planes(w):
    """8 digest words -> the (N, 4) digest plane pair."""
    return (
        jnp.stack([w[0], w[2], w[4], w[6]], axis=-1).reshape(-1, 4),
        jnp.stack([w[1], w[3], w[5], w[7]], axis=-1).reshape(-1, 4),
    )


# Lanes of a node layer's word vectors. Siblings 2i, 2i + 1 as the even and
# odd LANES of a 1-D vector are a lane shuffle a word a layer: the stack
# above 2^19 leaves took 125.6 ms a call that way, 3.06 ms this way (0.62 ms
# of device time a launch in the cell's trace; my chip run, PR 42). So a
# layer of n nodes is held as (n / A, A) with node a * (n / A) + r at
# [r, a]: siblings are rows 2r and 2r + 1 of one lane, their parent is row
# r of the layer above, and the one transpose a word is at the stack's
# entry (and one a layer on its way out, into node order). Rows in
# bit-reversed order, so that siblings are the two halves of the rows and
# no slice has a stride, read 2.94 ms: not worth its gathers.
NODE_LANES = 128


def _rows_to_planes(t):
    """8 digest words as (rows, A) -> the (rows * A, 4) plane pair in node
    order (node a * rows + r at [r, a])."""
    def plane(words):
        return jnp.stack(words, axis=-1).transpose(1, 0, 2).reshape(-1, 4)

    return plane(t[0::2]), plane(t[1::2])


def leaf_hash_planes(lo, hi):
    """(B, ...) column-major element planes -> (N, 4) digest planes."""
    return words_to_planes(leaf_words(lo, hi))


def node_layers_planes(digests_p, cap_size: int):
    """(N, 4) leaf digest planes -> every layer down to the cap, the leaf
    layer first."""
    lo, hi = digests_p
    n = lo.shape[0]
    lanes = min(NODE_LANES, n)
    layers = [digests_p]
    t = []
    for j in range(4):
        t += [lo[:, j].reshape(lanes, -1).T, hi[:, j].reshape(lanes, -1).T]
    while t[0].shape[0] > 1 and t[0].size > cap_size:
        t = node_words(t)
        layers.append(_rows_to_planes(t))
    # one row left: the lanes are the nodes, in order
    w = tuple(x.reshape(-1) for x in t)
    while w[0].shape[0] > cap_size:
        w = node_words(w)
        layers.append(words_to_planes(w))
    return tuple(layers)


# -- the u64 forms: the same programs between a split and a join ----------
# (written out here, not through field/limbs.py: those charge the counters
# that guard the resident prover's interior against conversions)


def split_u64(x):
    return (
        (x & jnp.uint64(0xFFFFFFFF)).astype(_u32),
        (x >> jnp.uint64(32)).astype(_u32),
    )


def join_u64(pair):
    lo, hi = pair
    return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))


def leaf_hash_u64(values_cm):
    """(B, ...) column-major u64 elements -> (N, 4) u64 digests."""
    return join_u64(leaf_hash_planes(*split_u64(values_cm)))


def node_layers_u64(digests, cap_size: int):
    above = node_layers_planes(split_u64(digests), cap_size)[1:]
    return (digests,) + tuple(join_u64(layer) for layer in above)


def compressions(columns: int, leaves: int, cap_size: int) -> int:
    """Compressions of one commit: the leaves' blocks and the nodes."""
    return int(leaves) * blocks_per_leaf(columns) + int(leaves) - int(cap_size)
