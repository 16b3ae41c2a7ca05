"""Goldilocks field GF(p), p = 2^64 - 2^32 + 1, as batched JAX uint64 ops.

This is the TPU-native counterpart of the reference scalar/SIMD field layer
(`/root/reference/src/field/goldilocks/mod.rs:94`, `generic_impl.rs:13`). Where
the reference vectorizes 16 lanes with AVX-512, we express every op on whole
JAX arrays (any shape) and let XLA tile them onto the TPU vector units; u64 is
carried as XLA's emulated 64-bit integer pairs. All stored values are kept
canonical (in [0, p)).

The 128-bit product reduction is the standard Goldilocks identity
2^64 = 2^32 - 1 (mod p) (same algorithm family as the reference's
`from_u128_with_reduction`): with x = hi·2^64 + lo, hi = hh·2^32 + hl,
    x = lo - hh + hl·(2^32 - 1)  (mod p),
computed with explicit wrap/borrow fixups in uint64 arithmetic.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import blocked_inverse

# ---------------------------------------------------------------------------
# Constants
# ---------------------------------------------------------------------------

P_INT = 0xFFFFFFFF00000001  # 2^64 - 2^32 + 1
EPSILON_INT = 0xFFFFFFFF  # 2^32 - 1 == 2^64 mod p
MULTIPLICATIVE_GENERATOR_INT = 7
# Generator of the 2^32-order multiplicative subgroup
# (reference: src/field/goldilocks/mod.rs:107 RADIX_2_SUBGROUP_GENERATOR).
RADIX_2_SUBGROUP_GENERATOR_INT = 0x185629DCDA58878C
TWO_ADICITY = 32

_u64 = jnp.uint64
P = np.uint64(P_INT)
EPSILON = np.uint64(EPSILON_INT)
MASK32 = np.uint64(0xFFFFFFFF)
MULTIPLICATIVE_GENERATOR = np.uint64(MULTIPLICATIVE_GENERATOR_INT)
RADIX_2_SUBGROUP_GENERATOR = np.uint64(RADIX_2_SUBGROUP_GENERATOR_INT)


def to_field(x) -> jax.Array:
    """Lift python ints / numpy arrays into canonical uint64 field arrays."""
    arr = np.asarray(x, dtype=np.object_)
    arr = np.vectorize(lambda v: int(v) % P_INT, otypes=[np.uint64])(arr)
    return jnp.asarray(arr, dtype=_u64)


# ---------------------------------------------------------------------------
# Ring ops (all elementwise on arbitrary-shape uint64 arrays)
# ---------------------------------------------------------------------------


def add(a: jax.Array, b: jax.Array) -> jax.Array:
    s = a + b
    # on u64 overflow the true value is s + 2^64 ≡ s + EPSILON (mod p)
    s = jnp.where(s < a, s + EPSILON, s)
    return jnp.where(s >= P, s - P, s)


def sub(a: jax.Array, b: jax.Array) -> jax.Array:
    d = a - b
    # borrow: true value is d - 2^64 ≡ d - EPSILON (mod p)
    return jnp.where(a < b, d - EPSILON, d)


def neg(a: jax.Array) -> jax.Array:
    return jnp.where(a == 0, a, P - a)


def double(a: jax.Array) -> jax.Array:
    return add(a, a)


def mul_wide(a: jax.Array, b: jax.Array):
    """Full 64x64 -> 128-bit product as (hi, lo) uint64 pair."""
    a_lo = a & MASK32
    a_hi = a >> np.uint64(32)
    b_lo = b & MASK32
    b_hi = b >> np.uint64(32)
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = lh + hl
    mid_carry = (mid < lh).astype(_u64)
    lo = ll + (mid << np.uint64(32))
    lo_carry = (lo < ll).astype(_u64)
    hi = hh + (mid >> np.uint64(32)) + (mid_carry << np.uint64(32)) + lo_carry
    return hi, lo


def reduce128(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Reduce a 128-bit value (hi·2^64 + lo) to a canonical field element."""
    hi_hi = hi >> np.uint64(32)
    hi_lo = hi & MASK32
    t0 = lo - hi_hi
    t0 = jnp.where(lo < hi_hi, t0 - EPSILON, t0)
    t1 = hi_lo * EPSILON  # < 2^64, no overflow
    t2 = t0 + t1
    res = jnp.where(t2 < t0, t2 + EPSILON, t2)
    return jnp.where(res >= P, res - P, res)


def mul(a: jax.Array, b: jax.Array) -> jax.Array:
    hi, lo = mul_wide(a, b)
    return reduce128(hi, lo)


def sqr(a: jax.Array) -> jax.Array:
    return mul(a, a)


def mul_small(a: jax.Array, k: int) -> jax.Array:
    """Multiply by a small constant via modular double-and-add (cheap on VPU)."""
    assert 0 <= k
    if k == 0:
        return jnp.zeros_like(a)
    acc = None
    addend = a
    while k:
        if k & 1:
            acc = addend if acc is None else add(acc, addend)
        k >>= 1
        if k:
            addend = double(addend)
    return acc


def pow_const(a: jax.Array, e: int) -> jax.Array:
    """a ** e for a python-int exponent (static square-and-multiply chain)."""
    e = int(e)
    assert e >= 0
    result = None
    base = a
    while e:
        if e & 1:
            result = base if result is None else mul(result, base)
        e >>= 1
        if e:
            base = sqr(base)
    if result is None:
        return jnp.ones_like(a)
    return result


@jax.jit
def inv(a: jax.Array) -> jax.Array:
    """Fermat inverse a^(p-2); inverse of 0 is 0 (callers must avoid it).

    Jitted: the square-and-multiply chain is ~90 muls — one compile per
    shape instead of ~1500 eager primitive dispatches per call."""
    return pow_const(a, P_INT - 2)


def batch_inverse(a: jax.Array) -> jax.Array:
    """Montgomery batch inversion along the last axis: the blocked form of
    `blocked_inverse` (short independent chains, three multiplications an
    element), the single implementation. Two others ran here before: a
    sequential-tile Pallas block-scan, ONE chain across the axis whose
    carry from tile to tile serialized the grid (measured ~10x slower on
    v5e), and two log-doubling XLA scans (2 log2 n + 2 multiplications an
    element). The blocked form carries nothing from group to group."""
    return batch_inverse_xla(a)


@jax.jit
def batch_inverse_xla(a: jax.Array) -> jax.Array:
    """Montgomery batch inversion along the last axis (the vectorized
    counterpart of the reference's serial Montgomery trick,
    `/root/reference/src/cs/implementations/utils.rs:405`): ONE Fermat
    inversion a few elements a row, `blocked_inverse.batch_inverse` over
    the u64 `mul` and `inv`."""
    return blocked_inverse.batch_inverse(a, mul, inv, 1)
