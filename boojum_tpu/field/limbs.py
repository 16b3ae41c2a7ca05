"""Goldilocks field arithmetic on 2x uint32 limb pairs — the Pallas form.

TPU vector units have no 64-bit integer datapath: Mosaic (the Pallas TPU
compiler) rejects u64 values inside kernels, and XLA's u64 emulation cannot be
fused across kernel boundaries. This module is the 32-bit-limb field
representation the kernels compute in — the TPU counterpart of the reference's
per-ISA `MixedGL` backends (`/root/reference/src/field/goldilocks/
avx512_impl.rs`, `arm_asm_impl.rs`): where those pack 16 Goldilocks lanes into
AVX-512/NEON registers, these ops treat a field element as a pair of same-shape
uint32 arrays `(lo, hi)` and express add/sub/mul/reduce in pure `jnp` uint32
ops, so the SAME code runs inside Pallas kernels (VPU lanes over VMEM tiles)
and as plain XLA (CPU fallback / interpret-mode tests).

All scalar-level algorithms match `field/goldilocks.py` exactly (EPSILON
reduction, wrap/borrow fixups); values are kept canonical in [0, p). The
32x32->64 product uses a 16-bit split (4 VPU multiplies) because the TPU's
integer multiplier returns only the low 32 bits.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gl

_u32 = jnp.uint32
U16_MASK = np.uint32(0xFFFF)
# p = 2^64 - 2^32 + 1 as limbs: lo = 1, hi = 0xFFFFFFFF
P_LO = np.uint32(1)
P_HI = np.uint32(0xFFFFFFFF)
EPS = np.uint32(0xFFFFFFFF)  # 2^32 - 1 == 2^64 mod p (fits one limb)


# ---------------------------------------------------------------------------
# u64 <-> limb conversions (run OUTSIDE kernels, plain XLA)
# ---------------------------------------------------------------------------
# Every device-side conversion is charged to the metrics registry (ISSUE 10):
# `limb.splits` / `limb.joins` are the INTERIOR boundary tax the resident
# mode exists to delete; conversions wrapped in `edge(label)` are the
# allowlisted API-edge set (H2D/setup ingest, transcript absorbs, query
# openings, proof serialization) and count as `limb.edge_splits` /
# `limb.edge_joins` instead. The guard test (tests/test_limb_resident.py)
# pins a resident prove at ZERO interior conversions. Counters tick at
# trace time for jitted graphs — exactly when a conversion enters a
# compiled module — and at call time for eager ops; both are what "this
# graph contains a conversion" means.

_EDGE_LABEL: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "boojum_tpu.limb_edge", default=None
)


@contextlib.contextmanager
def edge(label: str):
    """Mark enclosed split/join calls as allowlisted edge conversions."""
    token = _EDGE_LABEL.set(str(label))
    try:
        yield
    finally:
        _EDGE_LABEL.reset(token)


def edge_label() -> str | None:
    return _EDGE_LABEL.get()


def _charge(kind: str):
    from ..utils import metrics as _metrics

    lbl = _EDGE_LABEL.get()
    if lbl is None:
        _metrics.count(f"limb.{kind}s")
    else:
        _metrics.count(f"limb.edge_{kind}s")


def split(x: jax.Array):
    """uint64 array -> (lo, hi) uint32 pair."""
    _charge("split")
    return (
        (x & jnp.uint64(0xFFFFFFFF)).astype(_u32),
        (x >> jnp.uint64(32)).astype(_u32),
    )


def join(pair) -> jax.Array:
    """(lo, hi) uint32 pair -> uint64 array."""
    _charge("join")
    lo, hi = pair
    return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))


def const_pair(value: int):
    """A python-int field constant as numpy uint32 scalars (kernel-bakeable)."""
    v = int(value) % gl.P
    return np.uint32(v & 0xFFFFFFFF), np.uint32(v >> 32)


def split_np(x: np.ndarray):
    """Host-side split for precomputed tables (never a device op; counted
    separately so the residency guard can tell host edges from interior
    device conversions)."""
    from ..utils import metrics as _metrics

    _metrics.count("limb.host_splits")
    x = np.asarray(x, dtype=np.uint64)
    return (
        (x & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        (x >> np.uint64(32)).astype(np.uint32),
    )


def join_np(lo, hi) -> np.ndarray:
    """Host-side join (query openings / transcript pulls land here: the
    resident prover fetches u32 planes and reassembles u64 on host)."""
    from ..utils import metrics as _metrics

    _metrics.count("limb.host_joins")
    return np.asarray(lo, dtype=np.uint64) | (
        np.asarray(hi, dtype=np.uint64) << np.uint64(32)
    )


# ---------------------------------------------------------------------------
# 32-bit building blocks
# ---------------------------------------------------------------------------

# The hot primitives below are traced ONCE per argument shape and inlined
# from the cached jaxpr afterwards (`inline=True`: the enclosing jaxpr, and
# with it every lowered module and Mosaic kernel, is the one plain Python
# would have traced). Tracing them through `jnp`'s operators every time was
# most of a process's set-up: the fused sweep's one Pallas body holds half
# a million u32 equations, 110 of them a field multiply, and a fresh
# process traces the whole kernel library before its first prove (245 s
# for the Era geometry's library here, 88 s with this; PERF.md, PR 26).
_once_per_shape = functools.partial(jax.jit, inline=True)


def _b2u(x) -> jax.Array:
    return x.astype(_u32)


@_once_per_shape
def mul32_wide(a, b):
    """Full 32x32 -> 64-bit product as (lo, hi) uint32 pair.

    16-bit split: the exact high half fits uint32, so intermediate mod-2^32
    wraps cancel (the final values are exact)."""
    a0 = a & U16_MASK
    a1 = a >> 16
    b0 = b & U16_MASK
    b1 = b >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = lh + hl  # 33-bit true value; capture the wrap bit
    mid_c = _b2u(mid < lh)
    lo = ll + (mid << 16)
    lo_c = _b2u(lo < ll)
    hi = hh + (mid >> 16) + (mid_c << 16) + lo_c
    return lo, hi


def add64(a, b):
    """(lo, hi, carry) of a 64-bit add over limb pairs."""
    lo = a[0] + b[0]
    c = _b2u(lo < a[0])
    t = a[1] + b[1]
    c1 = _b2u(t < a[1])
    hi = t + c
    c2 = _b2u(hi < t)
    return lo, hi, c1 | c2


def sub64(a, b):
    """(lo, hi, borrow) of a 64-bit subtract over limb pairs."""
    lo = a[0] - b[0]
    br = _b2u(a[0] < b[0])
    t = a[1] - b[1]
    b1 = _b2u(a[1] < b[1])
    hi = t - br
    b2 = _b2u(t < br)
    return lo, hi, b1 | b2


def _plus_eps_where(lo, hi, cond):
    """(lo,hi) + EPSILON where cond (cond in {0,1} uint32).

    Adding 0xFFFFFFFF to lo = lo - 1 with carry-out iff lo != 0."""
    new_lo = lo - cond
    new_hi = hi + (cond & _b2u(lo != 0))
    return new_lo, new_hi


def _minus_eps_where(lo, hi, cond):
    """(lo,hi) - EPSILON where cond: lo + 1 with borrow-out iff lo == max."""
    new_lo = lo + cond
    new_hi = hi - (cond & _b2u(lo != EPS))
    return new_lo, new_hi


def _canonicalize(lo, hi):
    """Subtract p once where (lo,hi) >= p. Input < p + 2^32 (so one pass)."""
    ge = _b2u(hi == P_HI) & _b2u(lo >= P_LO)
    return lo - ge, jnp.where(ge, jnp.zeros_like(hi), hi)


# ---------------------------------------------------------------------------
# Field ops on limb pairs (canonical in, canonical out)
# ---------------------------------------------------------------------------


@_once_per_shape
def add(a, b):
    lo, hi, c = add64(a, b)
    lo, hi = _plus_eps_where(lo, hi, c)
    return _canonicalize(lo, hi)


@_once_per_shape
def sub(a, b):
    lo, hi, br = sub64(a, b)
    return _minus_eps_where(lo, hi, br)


def neg(a):
    z = jnp.zeros_like(a[0])
    return sub((z, z), a)


def double(a):
    return add(a, a)


def mul_wide(a, b):
    """Full 64x64 -> 128-bit product as 4 uint32 limbs (p0 lowest)."""
    ll_lo, ll_hi = mul32_wide(a[0], b[0])
    lh_lo, lh_hi = mul32_wide(a[0], b[1])
    hl_lo, hl_hi = mul32_wide(a[1], b[0])
    hh_lo, hh_hi = mul32_wide(a[1], b[1])
    s1 = ll_hi + lh_lo
    c1 = _b2u(s1 < ll_hi)
    p1 = s1 + hl_lo
    c2 = _b2u(p1 < s1)
    carry1 = c1 + c2  # 0..2
    s2 = lh_hi + hl_hi
    d1 = _b2u(s2 < lh_hi)
    s3 = s2 + hh_lo
    d2 = _b2u(s3 < s2)
    p2 = s3 + carry1
    d3 = _b2u(p2 < s3)
    p3 = hh_hi + d1 + d2 + d3
    return ll_lo, p1, p2, p3


def _reduce96(lo, hi, p2):
    """(p2·2^64 + hi·2^32 + lo) mod p, canonical, for ANY u32 limbs: the
    tail of `reduce128` (p2·2^64 ≡ p2·ε, and p2·ε = p2·2^32 - p2 needs no
    multiply)."""
    nz = _b2u(p2 != 0)
    t1_lo = jnp.zeros_like(p2) - p2
    t1_hi = p2 - nz
    # (lo, hi) + t1, carry -> += EPSILON
    lo2, hi2, c = add64((lo, hi), (t1_lo, t1_hi))
    lo2, hi2 = _plus_eps_where(lo2, hi2, c)
    return _canonicalize(lo2, hi2)


@_once_per_shape
def reduce128(p0, p1, p2, p3):
    """(p3·2^96 + p2·2^64 + p1·2^32 + p0) mod p, canonical.

    Same identity as goldilocks.reduce128: x ≡ lo64 - hi_hi + hi_lo·ε."""
    # t0 = lo64 - p3 (64-bit), borrow -> -= EPSILON
    lo, hi, br = sub64((p0, p1), (p3, jnp.zeros_like(p3)))
    lo, hi = _minus_eps_where(lo, hi, br)
    return _reduce96(lo, hi, p2)


@_once_per_shape
def mul(a, b):
    return reduce128(*mul_wide(a, b))


@_once_per_shape
def sqr(a):
    """a*a, sharing the cross product (12 VPU multiplies instead of 16)."""
    ll_lo, ll_hi = mul32_wide(a[0], a[0])
    lh_lo, lh_hi = mul32_wide(a[0], a[1])
    hh_lo, hh_hi = mul32_wide(a[1], a[1])
    # cross term appears twice: (lh << 32) * 2
    x_lo = lh_lo << 1
    xc0 = lh_lo >> 31
    x_hi = (lh_hi << 1) | xc0
    xc1 = lh_hi >> 31  # carry into p3
    s1 = ll_hi + x_lo
    c1 = _b2u(s1 < ll_hi)
    s2 = hh_lo + x_hi
    d1 = _b2u(s2 < hh_lo)
    p2 = s2 + c1
    d2 = _b2u(p2 < s2)
    p3 = hh_hi + xc1 + d1 + d2
    return reduce128(ll_lo, s1, p2, p3)


def _shl96(a, k: int):
    """a·2^k as three u32 limbs (p0 lowest), static 0 <= k < 32."""
    lo, hi = a
    if k == 0:  # no `x >> 32`: a shift by the full width is not 0 everywhere
        return lo, hi, jnp.zeros_like(hi)
    return lo << k, (hi << k) | (lo >> (32 - k)), hi >> (32 - k)


def mul_pow2(a, k, plus=None):
    """a·2^k (+ plus) mod p by shifts and ONE short reduction: the product
    is a (64+k)-bit value, so no 64x64 multiply and no p3 limb.

    `k` is a static exponent in [0, 32), or a sequence of them, one per
    leading row of `a`: the five shift ops are then unrolled over the rows
    and the reduction runs once on the restacked planes. `plus` (canonical,
    broadcastable against `a`) is folded in before that same reduction."""
    if isinstance(k, int):
        assert 0 <= k < 32, k
        p0, p1, p2 = _shl96(a, k)
    else:
        assert len(k) == a[0].shape[0] and all(0 <= e < 32 for e in k), k
        rows = [_shl96((a[0][i], a[1][i]), int(e)) for i, e in enumerate(k)]
        p0, p1, p2 = (jnp.stack(limb) for limb in zip(*rows))
    if plus is not None:
        p0, p1, c = add64((p0, p1), plus)
        p2 = p2 + c  # < 2^31 + 1: no wrap
    return _reduce96(p0, p1, p2)


def mul_const(a, c_pair):
    """Multiply by a baked (np.uint32, np.uint32) constant pair."""
    clo, chi = c_pair
    b = (jnp.full_like(a[0], clo), jnp.full_like(a[1], chi))
    return mul(a, b)


# ---------------------------------------------------------------------------
# Quadratic extension GF(p^2) = GF(p)[w]/(w^2 - 7) on limb pairs
# ---------------------------------------------------------------------------

_SEVEN = (np.uint32(7), np.uint32(0))


def ext_add(a, b):
    return add(a[0], b[0]), add(a[1], b[1])


def ext_sub(a, b):
    return sub(a[0], b[0]), sub(a[1], b[1])


@_once_per_shape
def ext_mul(a, b):
    """(a0 + a1 w)(b0 + b1 w) = a0b0 + 7 a1b1 + (a0b1 + a1b0) w."""
    v0 = mul(a[0], b[0])
    v1 = mul(a[1], b[1])
    t = mul(add(a[0], a[1]), add(b[0], b[1]))
    c1 = sub(t, add(v0, v1))
    c0 = add(v0, mul_const(v1, _SEVEN))
    return c0, c1
