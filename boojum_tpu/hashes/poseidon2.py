"""Poseidon2 permutation (Goldilocks, t=12, x^7) — batched device + host scalar.

Algorithm per the Poseidon2 paper (eprint 2023/323), parameter-compatible with
the reference implementation (`/root/reference/src/implementations/poseidon2/
state_generic_impl.rs:222` poseidon2_permutation: pre-multiply by the external
matrix, 4 full rounds, 22 partial rounds with the internal matrix, 4 full
rounds). The external matrix is circ(2·M4, M4, M4); we evaluate it with the
shift-free add/double chain so the whole permutation is VPU-friendly modular
adds + the x^7 sbox muls, batched over an arbitrary leading leaf axis.

Sponge semantics (rate 8 / capacity 4, overwrite mode) follow
`/root/reference/src/algebraic_props/sponge.rs` so leaf/node/transcript hashing
is bit-compatible with the reference tree hasher.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..field import gl
from ..field import goldilocks as gf
from . import poseidon2_params as params

_RC = np.array(params.ALL_ROUND_CONSTANTS, dtype=np.uint64).reshape(30, 12)
_DIAG = np.array(params.M_I_DIAGONAL, dtype=np.uint64)


def _sbox7(x):
    x2 = gf.sqr(x)
    x3 = gf.mul(x2, x)
    x4 = gf.sqr(x2)
    return gf.mul(x4, x3)


def _block_m4(x0, x1, x2, x3):
    """M4 = [[5,7,1,3],[4,6,1,1],[1,3,5,7],[1,1,4,6]] via add/double chain."""
    t0 = gf.add(x0, x1)
    t1 = gf.add(x2, x3)
    t2 = gf.add(gf.double(x1), t1)
    t3 = gf.add(gf.double(x3), t0)
    t4 = gf.add(gf.double(gf.double(t1)), t3)
    t5 = gf.add(gf.double(gf.double(t0)), t2)
    t6 = gf.add(t3, t5)
    t7 = gf.add(t2, t4)
    return t6, t5, t7, t4


def _external_mds(state):
    """state (..., 12) -> circ(2*M4, M4, M4) · state."""
    cols = [state[..., i] for i in range(12)]
    blocks = []
    for b in range(3):
        blocks.append(_block_m4(*cols[4 * b : 4 * b + 4]))
    out = []
    for i in range(4):
        s = gf.add(gf.add(blocks[0][i], blocks[1][i]), blocks[2][i])
        out.append(s)
    new_cols = []
    for b in range(3):
        for i in range(4):
            new_cols.append(gf.add(blocks[b][i], out[i]))
    return jnp.stack(new_cols, axis=-1)


def _internal_mds(state):
    """M_I = all-ones + diag(d): out_i = d_i·x_i + sum_j x_j."""
    total = state[..., 0]
    for i in range(1, 12):
        total = gf.add(total, state[..., i])
    scaled = gf.mul(state, jnp.asarray(_DIAG))
    return gf.add(scaled, total[..., None])


@jax.jit
def poseidon2_permutation_xla(state: jax.Array) -> jax.Array:
    """Batched Poseidon2 permutation on (..., 12) uint64 arrays.

    Rounds run under `lax.fori_loop` (compiler-friendly control flow): the
    compiled graph is one round body per phase instead of 30 unrolled rounds,
    which keeps XLA compile time flat while the loop itself is negligible
    next to the field ops."""
    rc = jnp.asarray(_RC)

    def full_round(r, s):
        s = gf.add(s, rc[r])
        s = _sbox7(s)
        return _external_mds(s)

    def partial_round(r, s):
        el0 = _sbox7(gf.add(s[..., 0], rc[r, 0]))
        s = jnp.concatenate([el0[..., None], s[..., 1:]], axis=-1)
        return _internal_mds(s)

    state = _external_mds(state)
    state = jax.lax.fori_loop(0, 4, full_round, state)
    state = jax.lax.fori_loop(4, 26, partial_round, state)
    state = jax.lax.fori_loop(26, 30, full_round, state)
    return state


# ---------------------------------------------------------------------------
# Device sponge helpers (rate 8, cap 4, overwrite mode)
# ---------------------------------------------------------------------------


def _sponge_hash_device(values: jax.Array, permutation) -> jax.Array:
    """Overwrite-mode sponge over (..., L) -> (..., 4) for any width-12
    permutation: each full 8-chunk overwrites the rate portion then
    permutes; a trailing partial chunk is zero-padded (finalize semantics
    of the reference sponge)."""
    lead = values.shape[:-1]
    L = values.shape[-1]
    state = jnp.zeros(lead + (12,), jnp.uint64)
    full = L // 8
    # fori_loop + dynamic slice: an unrolled chunk loop would trace the
    # permutation `full` times in every graph that inlines this sponge
    # (see the pallas kernel's identical note)

    def _absorb(c, st):
        chunk = jax.lax.dynamic_slice_in_dim(values, 8 * c, 8, axis=-1)
        st = jnp.concatenate([chunk, st[..., 8:]], axis=-1)
        return permutation(st)

    if full > 0:  # fori traces the body even for a 0-trip count
        state = jax.lax.fori_loop(0, full, _absorb, state)
    rem = L - 8 * full
    if rem > 0:
        chunk = values[..., 8 * full :]
        pad = jnp.zeros(lead + (8 - rem,), jnp.uint64)
        state = jnp.concatenate([chunk, pad, state[..., 8:]], axis=-1)
        state = permutation(state)
    return state[..., :4]


@jax.jit
def leaf_hash_xla(values: jax.Array) -> jax.Array:
    """Hash (..., L) field values into (..., 4) leaf digests."""
    return _sponge_hash_device(values, poseidon2_permutation_xla)


@jax.jit
def node_hash_xla(left: jax.Array, right: jax.Array) -> jax.Array:
    """Hash two (..., 4) digests into a (..., 4) parent digest."""
    state = jnp.concatenate(
        [left, right, jnp.zeros(left.shape[:-1] + (4,), jnp.uint64)], axis=-1
    )
    return poseidon2_permutation_xla(state)[..., :4]


# ---------------------------------------------------------------------------
# Limb-plane forms (ISSUE 10): the SAME sponge semantics over (lo, hi) u32
# plane pairs in the u64 layouts — the resident prover's hashing never
# leaves the plane representation. The XLA bodies reuse the fused kernel's
# limb round functions (pallas_poseidon2._permutation_planes_stacked) as
# plain jnp, so there is exactly one limb implementation of the rounds.
# ---------------------------------------------------------------------------


@jax.jit
def poseidon2_permutation_planes_xla(state_p):
    """Batched permutation on (..., 12) limb planes (XLA path)."""
    from . import pallas_poseidon2 as pp2

    rc = jnp.asarray(pp2.rc_table())
    lo = jnp.moveaxis(state_p[0], -1, 0)
    hi = jnp.moveaxis(state_p[1], -1, 0)
    olo, ohi = pp2._permutation_planes_stacked(rc, lo, hi)
    return jnp.moveaxis(olo, 0, -1), jnp.moveaxis(ohi, 0, -1)


def _sponge_hash_planes_device(values_p, permutation_p):
    """Overwrite-mode sponge over (..., L) planes -> (..., 4) planes
    (the `_sponge_hash_device` twin, same chunk/finalize semantics)."""
    vlo, vhi = values_p
    lead = vlo.shape[:-1]
    L = vlo.shape[-1]
    state = (
        jnp.zeros(lead + (12,), jnp.uint32),
        jnp.zeros(lead + (12,), jnp.uint32),
    )
    full = L // 8

    def _absorb(c, st):
        clo = jax.lax.dynamic_slice_in_dim(vlo, 8 * c, 8, axis=-1)
        chi = jax.lax.dynamic_slice_in_dim(vhi, 8 * c, 8, axis=-1)
        st = (
            jnp.concatenate([clo, st[0][..., 8:]], axis=-1),
            jnp.concatenate([chi, st[1][..., 8:]], axis=-1),
        )
        return permutation_p(st)

    if full > 0:
        state = jax.lax.fori_loop(0, full, _absorb, state)
    rem = L - 8 * full
    if rem > 0:
        pad = jnp.zeros(lead + (8 - rem,), jnp.uint32)
        state = (
            jnp.concatenate(
                [vlo[..., 8 * full :], pad, state[0][..., 8:]], axis=-1
            ),
            jnp.concatenate(
                [vhi[..., 8 * full :], pad, state[1][..., 8:]], axis=-1
            ),
        )
        state = permutation_p(state)
    return state[0][..., :4], state[1][..., :4]


@jax.jit
def leaf_hash_planes_xla(values_p):
    return _sponge_hash_planes_device(
        values_p, poseidon2_permutation_planes_xla
    )


@jax.jit
def node_hash_planes_xla(left_p, right_p):
    z = jnp.zeros(left_p[0].shape[:-1] + (4,), jnp.uint32)
    state = (
        jnp.concatenate([left_p[0], right_p[0], z], axis=-1),
        jnp.concatenate([left_p[1], right_p[1], z], axis=-1),
    )
    out = poseidon2_permutation_planes_xla(state)
    return out[0][..., :4], out[1][..., :4]


def poseidon2_permutation_planes(state_p):
    """Plane twin of `poseidon2_permutation` (fused kernel on TPU)."""
    if state_p[0].ndim == 2 and _pallas_ready(state_p[0].shape[0]):
        from . import pallas_poseidon2 as pp2

        return pp2.permutation_planes(state_p)
    return poseidon2_permutation_planes_xla(state_p)


def poseidon2_permutation_planes_cm(state_p):
    """`poseidon2_permutation_planes` on a column-major (12, N) state: the
    batch along the lanes, which is the fused kernel's own layout, so on the
    TPU nothing is transposed (and no (8, 128) tile is padded out from 12
    lanes, as an (N, 12) array's are)."""
    lo, hi = state_p
    n = lo.shape[1]
    if _pallas_ready(n):
        from . import pallas_poseidon2 as pp2

        R = n // pp2._LANE
        olo, ohi = pp2._permute_planes(
            lo.reshape(12, R, pp2._LANE), hi.reshape(12, R, pp2._LANE),
            pp2.step_rows(1, R), False,
        )
        return olo.reshape(12, n), ohi.reshape(12, n)
    out = poseidon2_permutation_planes_xla((lo.T, hi.T))
    return out[0].T, out[1].T


def leaf_hash_planes(values_p):
    """Plane twin of `leaf_hash`: (N, L) planes -> (N, 4) digest planes."""
    vlo = values_p[0]
    if (
        vlo.ndim == 2
        and vlo.shape[1] <= 1024
        and _pallas_ready(vlo.shape[0])
    ):
        from . import pallas_poseidon2 as pp2

        return pp2.sponge_hash_planes(values_p)
    return leaf_hash_planes_xla(values_p)


def node_hash_planes(left_p, right_p):
    """Plane twin of `node_hash`."""
    if left_p[0].ndim == 2 and _pallas_ready(left_p[0].shape[0]):
        from . import pallas_poseidon2 as pp2

        return pp2.sponge_hash_planes(
            (
                jnp.concatenate([left_p[0], right_p[0]], axis=-1),
                jnp.concatenate([left_p[1], right_p[1]], axis=-1),
            )
        )
    return node_hash_planes_xla(left_p, right_p)


# ---------------------------------------------------------------------------
# Dispatchers: fused Pallas kernels on TPU, XLA everywhere else. Results are
# bit-identical (tests/test_pallas_kernels.py asserts parity).
# ---------------------------------------------------------------------------


def _pallas_ready(n: int) -> bool:
    from ..utils.pallas_util import pallas_enabled

    if not pallas_enabled():
        return False
    from . import pallas_poseidon2 as pp2

    return pp2.batch_fits(n)


def poseidon2_permutation(state: jax.Array) -> jax.Array:
    """Batched Poseidon2 permutation on (..., 12) uint64 arrays."""
    if state.ndim == 2 and _pallas_ready(state.shape[0]):
        from . import pallas_poseidon2 as pp2

        return pp2.permutation(state)
    return poseidon2_permutation_xla(state)


def leaf_hash(values: jax.Array) -> jax.Array:
    """Hash (..., L) field values into (..., 4) leaf digests."""
    # width cap: beyond ~1024 columns the kernel's minimum (8-row) tile no
    # longer fits the raised VMEM budget; such commits keep the XLA sponge
    if (
        values.ndim == 2
        and values.shape[1] <= 1024
        and _pallas_ready(values.shape[0])
    ):
        from . import pallas_poseidon2 as pp2

        return pp2.sponge_hash(values)
    return leaf_hash_xla(values)


def node_hash(left: jax.Array, right: jax.Array) -> jax.Array:
    """Hash two (..., 4) digests into a (..., 4) parent digest."""
    if left.ndim == 2 and _pallas_ready(left.shape[0]):
        from . import pallas_poseidon2 as pp2

        return pp2.sponge_hash(jnp.concatenate([left, right], axis=-1))
    return node_hash_xla(left, right)


# ---------------------------------------------------------------------------
# Host scalar mirror (python ints) — transcript & proof verification
# ---------------------------------------------------------------------------


# The arithmetic is written out: plain `+` and `*` on Python ints, which
# do not overflow, and one `% P` where a value is next multiplied or a row
# of a linear layer ends; a function call a field operation (about 2,600 a
# permutation) costs more than the arithmetic itself. This is the ONE
# Python implementation: the reference for the device kernels, the gate and
# native/resolver.cpp's permutation, and what runs where no library loads.

_P = gl.P
_RC_ROWS = [
    tuple(params.ALL_ROUND_CONSTANTS[12 * r : 12 * r + 12]) for r in range(30)
]
_DIAG_INTS = tuple(params.M_I_DIAGONAL)


def _external_mds_s(s):
    """circ(2*M4, M4, M4) over twelve non-negative ints of any size (no
    entry of the matrix exceeds 14); the outputs are canonical."""
    p = _P
    x0, x1, x2, x3, x4, x5, x6, x7, x8, x9, x10, x11 = s
    # M4 = [[5,7,1,3],[4,6,1,1],[1,3,5,7],[1,1,4,6]] by its add/double chain
    t0 = x0 + x1
    t1 = x2 + x3
    t2 = x1 + x1 + t1
    t3 = x3 + x3 + t0
    a3 = 4 * t1 + t3
    a1 = 4 * t0 + t2
    a0 = t3 + a1
    a2 = t2 + a3
    t0 = x4 + x5
    t1 = x6 + x7
    t2 = x5 + x5 + t1
    t3 = x7 + x7 + t0
    b3 = 4 * t1 + t3
    b1 = 4 * t0 + t2
    b0 = t3 + b1
    b2 = t2 + b3
    t0 = x8 + x9
    t1 = x10 + x11
    t2 = x9 + x9 + t1
    t3 = x11 + x11 + t0
    c3 = 4 * t1 + t3
    c1 = 4 * t0 + t2
    c0 = t3 + c1
    c2 = t2 + c3
    s0 = a0 + b0 + c0
    s1 = a1 + b1 + c1
    s2 = a2 + b2 + c2
    s3 = a3 + b3 + c3
    return [
        (a0 + s0) % p, (a1 + s1) % p, (a2 + s2) % p, (a3 + s3) % p,
        (b0 + s0) % p, (b1 + s1) % p, (b2 + s2) % p, (b3 + s3) % p,
        (c0 + s0) % p, (c1 + s1) % p, (c2 + s2) % p, (c3 + s3) % p,
    ]


def _full_round_s(s, rc):
    """Add the round's constants, x^7 on every lane (the products go
    unreduced into the linear layer, which reduces its rows), external
    matrix."""
    p = _P
    out = []
    for v, c in zip(s, rc):
        v += c
        v3 = v * v * v % p
        out.append(v3 * v3 * v)
    return _external_mds_s(out)


def poseidon2_permutation_host(state: list) -> list:
    """Twelve ints (reduced or not) in, twelve canonical ints out."""
    p = _P
    rc = _RC_ROWS
    d0, d1, d2, d3, d4, d5, d6, d7, d8, d9, d10, d11 = _DIAG_INTS
    s = _external_mds_s(state)
    for r in range(4):
        s = _full_round_s(s, rc[r])
    s0, s1, s2, s3, s4, s5, s6, s7, s8, s9, s10, s11 = s
    for r in range(4, 26):
        v = s0 + rc[r][0]
        v3 = v * v * v % p
        s0 = v3 * v3 * v % p
        # M_I = all-ones + diag(d): out_i = d_i * x_i + sum_j x_j
        t = s0 + s1 + s2 + s3 + s4 + s5 + s6 + s7 + s8 + s9 + s10 + s11
        s0 = (s0 * d0 + t) % p
        s1 = (s1 * d1 + t) % p
        s2 = (s2 * d2 + t) % p
        s3 = (s3 * d3 + t) % p
        s4 = (s4 * d4 + t) % p
        s5 = (s5 * d5 + t) % p
        s6 = (s6 * d6 + t) % p
        s7 = (s7 * d7 + t) % p
        s8 = (s8 * d8 + t) % p
        s9 = (s9 * d9 + t) % p
        s10 = (s10 * d10 + t) % p
        s11 = (s11 * d11 + t) % p
    s = [s0, s1, s2, s3, s4, s5, s6, s7, s8, s9, s10, s11]
    for r in range(26, 30):
        s = _full_round_s(s, rc[r])
    return s


class Poseidon2SpongeHost:
    """Overwrite-mode sponge over python ints (transcripts, path
    verification). Subclasses swap the permutation via _PERMUTATION."""

    RATE = 8
    CAPACITY = 4
    _PERMUTATION = staticmethod(poseidon2_permutation_host)

    def __init__(self):
        self.state = [0] * 12
        self.buffer = []

    def absorb(self, values):
        self.buffer.extend(int(v) for v in values)
        while len(self.buffer) >= 8:
            chunk, self.buffer = self.buffer[:8], self.buffer[8:]
            self.state[:8] = chunk
            self.state = self._PERMUTATION(self.state)

    def finalize(self, n=4):
        if self.buffer:
            self.state[: len(self.buffer)] = self.buffer
            for i in range(len(self.buffer), 8):
                self.state[i] = 0
            self.state = self._PERMUTATION(self.state)
            self.buffer = []
        return self.state[:n]

    @classmethod
    def hash_leaf(cls, values, n=4):
        sp = cls()
        sp.absorb(values)
        return sp.finalize(n)

    @classmethod
    def hash_node(cls, left, right):
        sp = cls()
        sp.absorb(list(left) + list(right))
        return sp.finalize(4)
