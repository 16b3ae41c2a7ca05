"""BabyBear field arithmetic: one u32 lane = one field element (ISSUE 19).

p = 2^31 - 2^27 + 1 = 2013265921, two-adicity 27. Where Goldilocks stores a
(lo, hi) u32 plane pair per element and pays four cross-products plus the
reduce128 carry chain per multiply (`field/goldilocks.py`, `field/limbs.py`),
BabyBear is a single u32 lane: adds/subs are one conditional correction, a
multiply is one widened 62-bit product folded back to u32. Arrays are HALF
the HBM/ICI/DCN bytes of the limb-resident Goldilocks planes — the raw-speed
ceiling this backend exists to raise (ROADMAP open item 5).

Three layers, mirroring the Goldilocks split:
  - device array ops on jnp uint32 (this module's jnp functions),
  - host scalar ops over python ints (`*_s` helpers + module constants),
  - numpy vectorized host-table ops (`mul_np`, `powers_np`).

Challenge soundness: 31 bits is far too small a draw, so challenges, DEEP
and FRI run over the degree-4 tower GF(p^4) = GF(p)[x]/(x^4 - 11)
(~2^124 ext order; Goldilocks needs only degree 2). Extension elements are
4-tuples of base elements everywhere — (c0, c1, c2, c3) u32 arrays on
device, int 4-tuples on host.

All values canonical in [0, p). Products widen to u64 inside the XLA graph
(a compiler-internal detail — stored arrays stay bare u32; the HBM win is
the array bytes, not the ALU width).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import blocked_inverse
from .spec import BABYBEAR as SPEC

P = SPEC.p
TWO_ADICITY = SPEC.two_adicity
MULTIPLICATIVE_GENERATOR = SPEC.multiplicative_generator
RADIX_2_SUBGROUP_GENERATOR = SPEC.radix2_subgroup_generator
EXT_NONRESIDUE = SPEC.ext_nonresidue  # w^4 = 11

_P32 = np.uint32(P)
_P64 = np.uint64(P)


# ---------------------------------------------------------------------------
# Host scalar ops (python ints) — transcript, twiddle setup, verifier
# ---------------------------------------------------------------------------


def add_s(a: int, b: int) -> int:
    s = a + b
    return s - P if s >= P else s


def sub_s(a: int, b: int) -> int:
    d = a - b
    return d + P if d < 0 else d


def neg_s(a: int) -> int:
    return 0 if a == 0 else P - a


def mul_s(a: int, b: int) -> int:
    return (a * b) % P


def pow_s(a: int, e: int) -> int:
    return pow(a, e, P)


def inv_s(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("inverse of zero in BabyBear")
    return pow(a, P - 2, P)


def omega(log_n: int) -> int:
    """Primitive 2^log_n-th root of unity (two-adic tower)."""
    return SPEC.omega(log_n)


def powers(base: int, count: int) -> list:
    out = [1] * count
    for i in range(1, count):
        out[i] = mul_s(out[i - 1], base)
    return out


# ---------------------------------------------------------------------------
# NumPy vectorized host-table ops (twiddles, scale tables, reference prover)
# ---------------------------------------------------------------------------


def mul_np(a, b):
    """Canonical BabyBear multiply on uint32 numpy arrays. The product is
    < 2^62, so one u64 widening + remainder is exact — no carry chain."""
    a = np.asarray(a, dtype=np.uint64)
    b = np.asarray(b, dtype=np.uint64)
    return ((a * b) % _P64).astype(np.uint32)


def add_np(a, b):
    # conditional-subtract written without an underflowing where-branch so
    # numpy scalar inputs (reference-backend ext ops) stay warning-free
    s = np.asarray(a, dtype=np.uint32) + np.asarray(b, dtype=np.uint32)
    return s - np.where(s >= _P32, _P32, np.uint32(0))


def sub_np(a, b):
    # a + (p - b) < 2^32 for canonical inputs; fold back with one cond-sub
    r = np.asarray(a, dtype=np.uint32) + (
        _P32 - np.asarray(b, dtype=np.uint32)
    )
    return r - np.where(r >= _P32, _P32, np.uint32(0))


def powers_np(base: int, count: int):
    """[1, b, ..., b^(count-1)] as a uint32 numpy array (log-doubling)."""
    out = np.ones(count, dtype=np.uint32)
    if count <= 1:
        return out
    cur = 1
    while cur < count:
        step = np.uint32(pow_s(base, cur))
        nxt = min(cur, count - cur)
        out[cur : cur + nxt] = mul_np(out[:nxt], step)
        cur += nxt
    return out


# ---------------------------------------------------------------------------
# Device array ops on bare u32 lanes
# ---------------------------------------------------------------------------

_u32 = jnp.uint32
_u64 = jnp.uint64


def add(a, b):
    s = a + b  # a, b < p < 2^31: no u32 overflow
    return jnp.where(s >= _u32(P), s - _u32(P), s)


def sub(a, b):
    # wrapping u32: a - b + p is exact whichever side wraps
    return jnp.where(a >= b, a - b, a + (_u32(P) - b))


def neg(a):
    return jnp.where(a == 0, a, _u32(P) - a)


def double(a):
    return add(a, a)


def mul(a, b):
    """a*b mod p. One widened 62-bit product, one constant-divisor
    remainder (XLA strength-reduces it to a multiply-high chain)."""
    w = a.astype(_u64) * b.astype(_u64)
    return (w % _u64(P)).astype(_u32)


def sqr(a):
    return mul(a, a)


def mul_const(a, c: int):
    return mul(a, jnp.full_like(a, np.uint32(int(c) % P)))


@jax.jit
def pow_const(a, e):
    """a^e for a traced uint32 exponent array/scalar (square-and-multiply
    over the 31 exponent bits)."""
    e = jnp.asarray(e, dtype=_u32)

    def body(i, carry):
        acc, base = carry
        take = (e >> i) & _u32(1)
        acc = jnp.where(take == 1, mul(acc, base), acc)
        return acc, sqr(base)

    acc, _ = jax.lax.fori_loop(0, 31, body, (jnp.ones_like(a), a))
    return acc


@jax.jit
def inv(a):
    """Fermat: a^(p-2), addition-chain free (31 squarings + bit-selected
    multiplies against the fixed exponent p-2)."""
    e = P - 2
    acc = jnp.ones_like(a)
    base = a
    for i in range(31):
        if (e >> i) & 1:
            acc = mul(acc, base)
        if i != 30:
            base = sqr(base)
    return acc


@jax.jit
def batch_inverse_xla(x):
    """Montgomery's trick, all on device: the BabyBear twin of
    goldilocks.batch_inverse_xla, `blocked_inverse.batch_inverse` over
    this field's `mul` and `inv`."""
    return blocked_inverse.batch_inverse(x, mul, inv, 1)


# ---------------------------------------------------------------------------
# Degree-4 extension GF(p^4) = GF(p)[w]/(w^4 - 11)
# Elements are 4-tuples (c0, c1, c2, c3); device tuples hold u32 arrays,
# host `_s` tuples hold python ints.
# ---------------------------------------------------------------------------

ZERO_S = (0, 0, 0, 0)
ONE_S = (1, 0, 0, 0)


def ext_from_base_s(a: int):
    return (int(a) % P, 0, 0, 0)


def ext_add_s(a, b):
    return tuple(add_s(x, y) for x, y in zip(a, b))


def ext_sub_s(a, b):
    return tuple(sub_s(x, y) for x, y in zip(a, b))


def ext_neg_s(a):
    return tuple(neg_s(x) for x in a)


def ext_mul_s(a, b):
    """Schoolbook with w^4 = 11: c_k = sum_{i+j=k} a_i b_j
    + 11 * sum_{i+j=k+4} a_i b_j."""
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    nr = EXT_NONRESIDUE
    c0 = (a0 * b0 + nr * (a1 * b3 + a2 * b2 + a3 * b1)) % P
    c1 = (a0 * b1 + a1 * b0 + nr * (a2 * b3 + a3 * b2)) % P
    c2 = (a0 * b2 + a1 * b1 + a2 * b0 + nr * (a3 * b3)) % P
    c3 = (a0 * b3 + a1 * b2 + a2 * b1 + a3 * b0) % P
    return (c0, c1, c2, c3)


def ext_scale_s(a, k: int):
    return tuple(mul_s(x, k % P) for x in a)


def ext_pow_s(a, e: int):
    acc = ONE_S
    base = a
    while e:
        if e & 1:
            acc = ext_mul_s(acc, base)
        base = ext_mul_s(base, base)
        e >>= 1
    return acc


def ext_inv_s(a):
    """Fermat over the extension: a^(p^4 - 2). ~250 ext muls of host ints
    — transcript-scale, never device-scale."""
    if all(x == 0 for x in a):
        raise ZeroDivisionError("inverse of zero in GF(p^4)")
    return ext_pow_s(a, P**4 - 2)


# --- device ext ops (tuples of u32 arrays) ---------------------------------


def ext_zero_like(x):
    z = jnp.zeros_like(x)
    return (z, z, z, z)


def ext_add(a, b):
    return tuple(add(x, y) for x, y in zip(a, b))


def ext_sub(a, b):
    return tuple(sub(x, y) for x, y in zip(a, b))


def ext_neg(a):
    return tuple(neg(x) for x in a)


def ext_mul(a, b):
    """16 base muls + folds; the nonresidue fold is a constant mul."""
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    nr = np.uint32(EXT_NONRESIDUE)

    def _nr(x):
        return mul(x, jnp.full_like(x, nr))

    c0 = add(mul(a0, b0), _nr(add(add(mul(a1, b3), mul(a2, b2)), mul(a3, b1))))
    c1 = add(add(mul(a0, b1), mul(a1, b0)), _nr(add(mul(a2, b3), mul(a3, b2))))
    c2 = add(add(mul(a0, b2), mul(a1, b1)), add(mul(a2, b0), _nr(mul(a3, b3))))
    c3 = add(add(mul(a0, b3), mul(a1, b2)), add(mul(a2, b1), mul(a3, b0)))
    return (c0, c1, c2, c3)


def ext_scale(a, k):
    """ext * base (base may be an array or a baked constant int)."""
    if isinstance(k, (int, np.integer)):
        return tuple(mul_const(x, int(k)) for x in a)
    return tuple(mul(x, k) for x in a)


def ext_const(c, like):
    """A host ext 4-tuple as device arrays broadcast like `like`."""
    return tuple(jnp.full_like(like, np.uint32(int(x) % P)) for x in c)


# --- numpy ext twins (reference prover) ------------------------------------


def ext_add_np(a, b):
    return tuple(add_np(x, y) for x, y in zip(a, b))


def ext_sub_np(a, b):
    return tuple(sub_np(x, y) for x, y in zip(a, b))


def ext_mul_np(a, b):
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    nr = np.uint32(EXT_NONRESIDUE)
    c0 = add_np(
        mul_np(a0, b0),
        mul_np(
            add_np(add_np(mul_np(a1, b3), mul_np(a2, b2)), mul_np(a3, b1)),
            nr,
        ),
    )
    c1 = add_np(
        add_np(mul_np(a0, b1), mul_np(a1, b0)),
        mul_np(add_np(mul_np(a2, b3), mul_np(a3, b2)), nr),
    )
    c2 = add_np(
        add_np(mul_np(a0, b2), mul_np(a1, b1)),
        add_np(mul_np(a2, b0), mul_np(mul_np(a3, b3), nr)),
    )
    c3 = add_np(
        add_np(mul_np(a0, b3), mul_np(a1, b2)),
        add_np(mul_np(a2, b1), mul_np(a3, b0)),
    )
    return (c0, c1, c2, c3)


def inv_np(a):
    """Vectorized Fermat a^(p-2) on uint32 numpy arrays (31-step chain,
    the numpy twin of the device `inv`)."""
    a = np.asarray(a, dtype=np.uint32)
    e = P - 2
    acc = np.ones_like(a)
    base = a
    for i in range(31):
        if (e >> i) & 1:
            acc = mul_np(acc, base)
        if i != 30:
            base = mul_np(base, base)
    return acc


# w^p = FROB_C * w where FROB_C = 11^((p-1)/4): Frobenius is coordinate-wise
# multiplication by powers of a 4th root of unity — the device inverse
# below rides on it (3 constant-mul maps + 3 ext muls + ONE base Fermat
# instead of a 124-bit ext exponentiation).
_FROB_C = pow(EXT_NONRESIDUE, (P - 1) // 4, P)
_FROB_COEFFS = {
    k: tuple(pow(_FROB_C, (i * k) % 4, P) for i in range(4)) for k in (1, 2, 3)
}


def ext_frobenius_s(a, k: int):
    return tuple(mul_s(x, c) for x, c in zip(a, _FROB_COEFFS[k]))


def ext_frobenius(a, k: int):
    return tuple(
        x if c == 1 else mul_const(x, c)
        for x, c in zip(a, _FROB_COEFFS[k])
    )


def ext_inv(a):
    """Vectorized device inverse in GF(p^4) via the norm map:
    a^-1 = (a^p * a^p2 * a^p3) / N(a), N(a) = a * a^p * a^p2 * a^p3 in
    GF(p). Cost: 2 ext muls + one c0-row of a third + 3 Frobenius constant
    maps + ONE base-field Fermat inversion."""
    t = ext_mul(
        ext_frobenius(a, 1), ext_mul(ext_frobenius(a, 2), ext_frobenius(a, 3))
    )
    a0, a1, a2, a3 = a
    t0, t1, t2, t3 = t
    nr = np.uint32(EXT_NONRESIDUE)
    norm = add(
        mul(a0, t0),
        mul(
            add(add(mul(a1, t3), mul(a2, t2)), mul(a3, t1)),
            jnp.full_like(a0, nr),
        ),
    )
    return ext_scale(t, inv(norm))


def ext_inv_np(a):
    """Numpy twin of the device ext_inv (same Frobenius/norm shape)."""
    frobs = [
        tuple(mul_np(x, np.uint32(c)) for x, c in zip(a, _FROB_COEFFS[k]))
        for k in (1, 2, 3)
    ]
    t = ext_mul_np(frobs[0], ext_mul_np(frobs[1], frobs[2]))
    a0, a1, a2, a3 = a
    t0, t1, t2, t3 = t
    nr = np.uint32(EXT_NONRESIDUE)
    norm = add_np(
        mul_np(a0, t0),
        mul_np(
            add_np(add_np(mul_np(a1, t3), mul_np(a2, t2)), mul_np(a3, t1)),
            nr,
        ),
    )
    ninv = inv_np(norm)
    return tuple(mul_np(x, ninv) for x in t)


def ext_prefix_product(a):
    """Inclusive prefix products of a GF(p^4) vector (4-tuple of device
    arrays) along the last axis — explicit Hillis–Steele doubling with
    ext_mul (field mul is not associative-scan-safe under XLA's
    reassociation assumptions; ISSUE 20 stage-2 z column)."""
    n = a[0].shape[-1]
    steps = max(1, (n - 1).bit_length())
    y = a
    for s in range(steps):
        shift = 1 << s
        shifted = tuple(
            jnp.concatenate(
                [
                    (jnp.ones_like if k == 0 else jnp.zeros_like)(
                        y[k][..., :shift]
                    ),
                    y[k][..., :-shift],
                ],
                axis=-1,
            )
            for k in range(4)
        )
        y = ext_mul(y, shifted)
    return y


def ext_prefix_product_np(a):
    """Sequential numpy twin of ext_prefix_product (reference backend)."""
    n = int(a[0].shape[-1])
    out = tuple(np.empty_like(x) for x in a)
    shape = a[0][..., :1].shape
    cur = (
        np.ones(shape, dtype=np.uint32),
        np.zeros(shape, dtype=np.uint32),
        np.zeros(shape, dtype=np.uint32),
        np.zeros(shape, dtype=np.uint32),
    )
    for j in range(n):
        cur = ext_mul_np(cur, tuple(x[..., j : j + 1] for x in a))
        for k in range(4):
            out[k][..., j : j + 1] = cur[k]
    return out
