"""Poseidon2 permutation as a fused Pallas TPU kernel over u32 limb planes.

The TPU counterpart of the reference's AVX-512 Poseidon2 state
(`/root/reference/src/implementations/poseidon2/state_avx512.rs`): where that
packs the width-12 state into 512-bit registers and keeps a whole permutation
in-register, this kernel keeps a (12, TILE, 128) tile of states on the core
for all 30 rounds: one HBM read and one write per permutation batch, instead
of one round-trip per round (what the staged XLA version pays when the fused
graph exceeds the fusion horizon). Every tile gives that; what the tile
decides is whether the rounds run in the vector registers or through VMEM.
At 8 rows a plane of the state is 12 vregs and the (lo, hi) state 24 of the
core's 64; at 256 rows it is 768, and every one of a permutation's ~94 k u32
operations loads and stores VMEM. `step_rows` therefore picks the smallest
legal tile for every call (PERF.md section 6, PR 29, has the sweep: the rate
is set by the tile, whatever the chunk count), which is also the fastest to
compile: Mosaic unrolls each operation over TILE / 8 vregs a plane row.

Layout: the batch axis is tiled (rows x 128 lanes); the state axis (12) and
the limb axis (2) are leading dims, so every field op is an elementwise VPU op
over (TILE, 128) tiles. Round constants live in SMEM as u32 limb pairs and are
broadcast per round inside `fori_loop`s (4 full / 22 partial / 4 full — the
same phase structure as `poseidon2.py`). The internal matrix's diagonal is
powers of two: its product is a static shift per row and one short reduction
(`limbs.mul_pow2`), no field multiply and no table row.

Used by `poseidon2.py:poseidon2_permutation` when running on TPU
(`pallas_util.force_xla()` pins the XLA twin); bit-parity with the XLA path is asserted in
tests/test_pallas_kernels.py (interpret mode on CPU + real kernels on TPU).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field import limbs
from . import poseidon2_params as params

# M_I's diagonal is powers of two by design (upstream multiplies by shifts):
# the kernel needs only the exponents, and takes them from the one copy of
# the constants.
assert all(
    0 < d < 1 << 32 and d & (d - 1) == 0 for d in params.M_I_DIAGONAL
), params.M_I_DIAGONAL
_M_I_DIAG_LOG2 = tuple(d.bit_length() - 1 for d in params.M_I_DIAGONAL)


@lru_cache(maxsize=None)
def rc_table(layout: str = "lohi24") -> np.ndarray:
    """Round-constant limbs in one kernel-variant-keyed spec cache.

    (30, 12) limb pairs -> (30, 24) u32: [lo(12) | hi(12)] per round —
    pallas kernels cannot close over array constants, so the round
    constants ride an SMEM table (M_I's diagonal is static shifts and
    needs none). Built at first kernel build (NOT import time) and keyed
    by the variant's constant layout, so the resident and converting
    kernel variants can never share a stale layout (ISSUE 10 satellite)."""
    assert layout == "lohi24", layout
    rc = np.array(params.ALL_ROUND_CONSTANTS, dtype=np.uint64).reshape(30, 12)
    return np.concatenate(limbs.split_np(rc), axis=1)


def _sbox7(x):
    x2 = limbs.sqr(x)
    x3 = limbs.mul(x2, x)
    x4 = limbs.sqr(x2)
    return limbs.mul(x4, x3)


# The whole permutation is VECTORIZED over the state axis: every step is a
# limbs op on stacked (12, T, 128) (or (3, T, 128) group) planes. A
# per-element formulation traced ~800 jaxpr eqns PER ROUND BODY, and every
# graph that inlines a commit re-traced it — minutes of pure tracing per
# fresh process. Element order and add association match the per-element
# form exactly; field ops are exact mod p, so results are bit-identical.


def _external_mds_planes(lo, hi):
    """M_E on stacked (12, T, 128) limb planes: 3 groups x the width-4 M4
    block, then the cross-group sums (same 4b+i element order as the
    reference's per-element loop)."""
    add, dbl = limbs.add, limbs.double
    tail = lo.shape[1:]
    glo = lo.reshape((3, 4) + tail)
    ghi = hi.reshape((3, 4) + tail)
    X = [(glo[:, i], ghi[:, i]) for i in range(4)]  # (3, T, 128) pairs
    t0 = add(X[0], X[1])
    t1 = add(X[2], X[3])
    t2 = add(dbl(X[1]), t1)
    t3 = add(dbl(X[3]), t0)
    t4 = add(dbl(dbl(t1)), t3)
    t5 = add(dbl(dbl(t0)), t2)
    B = [add(t3, t5), t5, add(t2, t4), t4]  # block outputs per position
    out_lo, out_hi = [], []
    for i in range(4):
        blo, bhi = B[i]
        s = add(add((blo[0], bhi[0]), (blo[1], bhi[1])), (blo[2], bhi[2]))
        o = add(B[i], s)  # (3,T,128) + (T,128) broadcast
        out_lo.append(o[0])
        out_hi.append(o[1])
    olo = jnp.stack(out_lo, axis=1).reshape((12,) + tail)
    ohi = jnp.stack(out_hi, axis=1).reshape((12,) + tail)
    return olo, ohi


def _internal_mds_planes(lo, hi):
    """M_I = all-ones + diag(2^k) on stacked planes: row i becomes
    x_i·2^k_i + sum(x), the shifts unrolled per row and the sum folded into
    the one reduction of the restacked planes."""
    total = (lo[0], hi[0])
    for i in range(1, 12):
        total = limbs.add(total, (lo[i], hi[i]))
    return limbs.mul_pow2((lo, hi), _M_I_DIAG_LOG2, plus=total)


def _rc_row(rc_ref, r, like):
    """Row-r constants from SMEM as (12, T, 128) planes (stacked full
    tiles: Mosaic rejects reshaping a 1-D vector into broadcastable 3-D)."""
    rlo = jnp.stack(
        [jnp.full_like(like, rc_ref[r, i]) for i in range(12)]
    )
    rhi = jnp.stack(
        [jnp.full_like(like, rc_ref[r, 12 + i]) for i in range(12)]
    )
    return rlo, rhi


def _permutation_planes_stacked(rc_ref, lo, hi):
    """All 30 rounds on stacked (12, T, 128) limb planes."""
    carry = _external_mds_planes(lo, hi)

    def full_round(r, carry):
        lo, hi = carry
        s = limbs.add((lo, hi), _rc_row(rc_ref, r, lo[0]))
        return _external_mds_planes(*_sbox7(s))

    def partial_round(r, carry):
        lo, hi = carry
        rc0 = (
            jnp.full_like(lo[0], rc_ref[r, 0]),
            jnp.full_like(hi[0], rc_ref[r, 12]),
        )
        el = _sbox7(limbs.add((lo[0], hi[0]), rc0))
        lo = jnp.concatenate([el[0][None], lo[1:]], axis=0)
        hi = jnp.concatenate([el[1][None], hi[1:]], axis=0)
        return _internal_mds_planes(lo, hi)

    carry = jax.lax.fori_loop(0, 4, full_round, carry)
    carry = jax.lax.fori_loop(4, 26, partial_round, carry)
    carry = jax.lax.fori_loop(26, 30, full_round, carry)
    return carry


def _perm_kernel(rc_ref, lo_ref, hi_ref, out_lo_ref, out_hi_ref):
    lo, hi = _permutation_planes_stacked(rc_ref, lo_ref[:], hi_ref[:])
    out_lo_ref[:] = lo
    out_hi_ref[:] = hi


def _sponge_kernel(num_chunks: int, rc_ref, vlo_ref, vhi_ref, olo_ref, ohi_ref):
    """Overwrite-mode sponge over (L, T, 128) leaf-value planes -> (4, T, 128).

    L is padded to 8*num_chunks with zeros by the wrapper; each chunk
    overwrites the rate portion (state[0:8]) then permutes.

    The chunk loop is a fori_loop with a dynamic leading-axis slice into
    the value refs: a Python-unrolled loop would trace num_chunks copies
    of the whole permutation — for wide leaves that is tens of thousands
    of jaxpr eqns PER GRAPH that inlines this kernel, minutes of pure
    tracing in every fresh process (the round-3 'compile bill' mystery)."""
    import jax.lax as lax

    zero12 = jnp.zeros((12,) + vlo_ref.shape[1:], jnp.uint32)

    def chunk_body(c, carry):
        lo, hi = carry
        # i32 offset arithmetic: under the global x64 flag a bare 8*c is
        # i64 and Mosaic's muli verifier rejects the mixed-width product
        off = jnp.int32(8) * c
        rlo = vlo_ref[pl.ds(off, 8)]
        rhi = vhi_ref[pl.ds(off, 8)]
        lo = jnp.concatenate([rlo, lo[8:]], axis=0)
        hi = jnp.concatenate([rhi, hi[8:]], axis=0)
        return _permutation_planes_stacked(rc_ref, lo, hi)

    lo, hi = lax.fori_loop(
        jnp.int32(0), jnp.int32(num_chunks), chunk_body, (zero12, zero12)
    )
    olo_ref[:] = lo[:4]
    ohi_ref[:] = hi[:4]


from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from ..utils.pallas_util import imap32  # noqa: E402

# an 8-row step of the widest leaf (1024 values, both planes, two buffers
# each) is 16 MiB of blocks alone: over the default scoped-vmem budget
from ..utils.pallas_util import tpu_compiler_params  # noqa: E402

_CP = tpu_compiler_params(64 * 1024 * 1024)


def _smem_spec():
    # explicit block + index map: the default index map traces i64 under the
    # global x64 flag, which Mosaic cannot legalize
    return pl.BlockSpec(
        (30, 24), imap32(lambda *_: (0, 0)), memory_space=pltpu.SMEM
    )


@partial(jax.jit, static_argnums=(2, 3))
def _permute_planes(lo, hi, tile_rows: int, interpret: bool):
    """(12, R, 128) u32 limb planes -> permuted, grid over R tiles."""
    R = lo.shape[1]
    grid = (R // tile_rows,)
    spec = pl.BlockSpec(
        (12, tile_rows, 128),
        imap32(lambda r: (0, r, 0)),
        memory_space=pltpu.VMEM,
    )
    out_shape = jax.ShapeDtypeStruct((12, R, 128), jnp.uint32)
    return pl.pallas_call(
        _perm_kernel,
        grid=grid,
        out_shape=[out_shape, out_shape],
        in_specs=[_smem_spec(), spec, spec],
        out_specs=[spec, spec],
        interpret=interpret,
        compiler_params=None if interpret else _CP,
    )(jnp.asarray(rc_table()), lo, hi)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _sponge_planes(vlo, vhi, num_chunks: int, tile_rows: int, interpret: bool):
    """(8*chunks, R, 128) value planes -> (4, R, 128) digest planes."""
    L, R, _ = vlo.shape
    grid = (R // tile_rows,)
    in_spec = pl.BlockSpec(
        (L, tile_rows, 128),
        imap32(lambda r: (0, r, 0)),
        memory_space=pltpu.VMEM,
    )
    out_spec = pl.BlockSpec(
        (4, tile_rows, 128),
        imap32(lambda r: (0, r, 0)),
        memory_space=pltpu.VMEM,
    )
    out_shape = jax.ShapeDtypeStruct((4, R, 128), jnp.uint32)
    return pl.pallas_call(
        partial(_sponge_kernel, num_chunks),
        grid=grid,
        out_shape=[out_shape, out_shape],
        in_specs=[_smem_spec(), in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        interpret=interpret,
        compiler_params=None if interpret else _CP,
    )(jnp.asarray(rc_table()), vlo, vhi)


# tile legality (divisor-of-R, multiple-of-8 sublane rule) is shared with
# the limb-sweep kernel family
from ..utils.pallas_util import pick_tile as _pick_tile  # noqa: E402


_LANE = 128
_MIN_BATCH = 1024  # below this the XLA path wins (kernel launch overhead)


def step_rows(num_chunks: int, R: int) -> int:
    """Rows of one grid step, for a call that absorbs `num_chunks` chunks
    (1 for the bare permutation and for a node) over R sublane rows: the
    one rule behind every call of `_sponge_planes` and `_permute_planes`.

    8 rows, the smallest legal sublane tile, whenever 8 divides R: a plane
    of the state is then 12 vregs and the rounds run in registers (module
    docstring; the rate falls with every doubling of the step, at every
    chunk count). Where no multiple of 8 divides R the whole axis is the
    one legal block, and the VMEM budget bounds it as it bounded every
    tile before: `pick_tile` raises past it."""
    if R % 8 == 0:
        return 8
    return _pick_tile(R, max(8, (2 << 20) // (8 * num_chunks * _LANE * 8)))


def batch_fits(n: int) -> bool:
    # n % 1024: the row count is a multiple of 8, so `step_rows` has its
    # 8-row sublane tile
    return n >= _MIN_BATCH and n % (8 * _LANE) == 0


# The kernels' NATIVE interface takes (lo, hi) u32 planes directly (ISSUE
# 10: the former u64 wrappers' split/join at every call were the interior
# boundary tax the resident mode deletes); `permutation`/`sponge_hash`
# survive as thin u64 conversion shims for the converting path.


def permutation_planes(state_p, interpret: bool = False):
    """Batched Poseidon2 permutation on (N, 12) u32 limb planes."""
    slo, shi = state_p
    n = slo.shape[0]
    assert n % _LANE == 0
    R = n // _LANE
    # (N, 12) -> (12, R, 128) plane layout
    lo = slo.T.reshape(12, R, _LANE)
    hi = shi.T.reshape(12, R, _LANE)
    olo, ohi = _permute_planes(lo, hi, step_rows(1, R), interpret)
    return olo.reshape(12, n).T, ohi.reshape(12, n).T


def sponge_hash_planes(values_p, interpret: bool = False):
    """(N, L) leaf-value planes -> (N, 4) digest planes (overwrite mode)."""
    vlo0, vhi0 = values_p
    n, L = vlo0.shape
    assert n % _LANE == 0
    num_chunks = max(1, (L + 7) // 8)
    R = n // _LANE
    vlo = vlo0.T.reshape(L, R, _LANE)
    vhi = vhi0.T.reshape(L, R, _LANE)
    if L < 8 * num_chunks:
        pad = jnp.zeros((8 * num_chunks - L, R, _LANE), jnp.uint32)
        vlo = jnp.concatenate([vlo, pad], axis=0)
        vhi = jnp.concatenate([vhi, pad], axis=0)
    tile = step_rows(num_chunks, R)
    olo, ohi = _sponge_planes(vlo, vhi, num_chunks, tile, interpret)
    return olo.reshape(4, n).T, ohi.reshape(4, n).T


def permutation(state: jax.Array, interpret: bool = False) -> jax.Array:
    """u64 shim over `permutation_planes` (converting path only)."""
    out = permutation_planes(limbs.split(state), interpret)
    return limbs.join(out)


def sponge_hash(values: jax.Array, interpret: bool = False) -> jax.Array:
    """u64 shim over `sponge_hash_planes` (converting path only)."""
    out = sponge_hash_planes(limbs.split(values), interpret)
    return limbs.join(out)
