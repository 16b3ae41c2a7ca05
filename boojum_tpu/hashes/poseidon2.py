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


def _sbox7_s(x):
    x2 = gl.sqr(x)
    x3 = gl.mul(x2, x)
    return gl.mul(gl.sqr(x2), x3)


def _block_m4_s(x0, x1, x2, x3):
    t0 = gl.add(x0, x1)
    t1 = gl.add(x2, x3)
    t2 = gl.add(gl.add(x1, x1), t1)
    t3 = gl.add(gl.add(x3, x3), t0)
    t4 = gl.add(gl.add(gl.add(t1, t1), gl.add(t1, t1)), t3)
    t5 = gl.add(gl.add(gl.add(t0, t0), gl.add(t0, t0)), t2)
    return gl.add(t3, t5), t5, gl.add(t2, t4), t4


def _external_mds_s(s):
    blocks = [_block_m4_s(*s[4 * b : 4 * b + 4]) for b in range(3)]
    sums = [
        gl.add(gl.add(blocks[0][i], blocks[1][i]), blocks[2][i]) for i in range(4)
    ]
    return [gl.add(blocks[b][i], sums[i]) for b in range(3) for i in range(4)]


def _internal_mds_s(s):
    total = 0
    for v in s:
        total = gl.add(total, v)
    return [gl.add(gl.mul(s[i], params.M_I_DIAGONAL[i]), total) for i in range(12)]


def poseidon2_permutation_host(state: list) -> list:
    s = _external_mds_s(list(state))
    for r in range(4):
        s = [gl.add(v, int(_RC[r, i])) for i, v in enumerate(s)]
        s = [_sbox7_s(v) for v in s]
        s = _external_mds_s(s)
    for r in range(4, 26):
        s[0] = _sbox7_s(gl.add(s[0], int(_RC[r, 0])))
        s = _internal_mds_s(s)
    for r in range(26, 30):
        s = [gl.add(v, int(_RC[r, i])) for i, v in enumerate(s)]
        s = [_sbox7_s(v) for v in s]
        s = _external_mds_s(s)
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
