"""Quadratic extension GF(p^2) = GF(p)[x] / (x^2 - 7).

Mirrors the reference `GoldilocksExt2` (non-residue 7,
`/root/reference/src/field/goldilocks/extension.rs`, generic ops
`src/field/traits/field.rs:326`). Device-side elements are pairs (c0, c1) of
uint64 arrays; host-side scalars are `(int, int)` tuples (functions suffixed
`_s`). All Fiat–Shamir challenges drawn after witness commitment live here.
"""

import jax
import jax.numpy as jnp

from . import goldilocks as gf
from . import gl

NON_RESIDUE = 7


# ---------------------------------------------------------------------------
# Device (jnp array pair) ops
# ---------------------------------------------------------------------------


def add(a, b):
    return (gf.add(a[0], b[0]), gf.add(a[1], b[1]))


def sub(a, b):
    return (gf.sub(a[0], b[0]), gf.sub(a[1], b[1]))


def neg(a):
    return (gf.neg(a[0]), gf.neg(a[1]))


def mul(a, b):
    # (a0 + a1 x)(b0 + b1 x) = a0 b0 + 7 a1 b1 + (a0 b1 + a1 b0) x
    v0 = gf.mul(a[0], b[0])
    v1 = gf.mul(a[1], b[1])
    c0 = gf.add(v0, gf.mul_small(v1, NON_RESIDUE))
    c1 = gf.add(gf.mul(a[0], b[1]), gf.mul(a[1], b[0]))
    return (c0, c1)


def mul_by_base(a, b):
    """Multiply extension element a by base-field array b."""
    return (gf.mul(a[0], b), gf.mul(a[1], b))


def sqr(a):
    return mul(a, a)


def scalar_to_arrays(s, like=None):
    """Lift a host scalar ext element (int, int) to a pair of 0-d arrays."""
    return (jnp.uint64(s[0]), jnp.uint64(s[1]))


def zeros(shape):
    return (jnp.zeros(shape, jnp.uint64), jnp.zeros(shape, jnp.uint64))


def inv(a):
    # 1/(c0 + c1 x) = (c0 - c1 x) / (c0^2 - 7 c1^2)
    d = gf.sub(gf.sqr(a[0]), gf.mul_small(gf.sqr(a[1]), NON_RESIDUE))
    dinv = gf.inv(d)
    return (gf.mul(a[0], dinv), gf.neg(gf.mul(a[1], dinv)))


@jax.jit
def batch_inverse(a):
    d = gf.sub(gf.sqr(a[0]), gf.mul_small(gf.sqr(a[1]), NON_RESIDUE))
    dinv = gf.batch_inverse(d)
    return (gf.mul(a[0], dinv), gf.neg(gf.mul(a[1], dinv)))


@jax.jit
def prefix_product(a):
    """Inclusive ext prefix product along the last axis (Hillis–Steele
    log-doubling, log2(n) rounds of shift+multiply; deliberately NOT
    lax.associative_scan, whose recursive slicing graph explodes XLA
    compile time for wide combine fns)."""
    n = a[0].shape[-1]
    shift = 1
    while shift < n:
        shifted = (
            jnp.concatenate([jnp.ones((shift,), jnp.uint64), a[0][:-shift]]),
            jnp.concatenate([jnp.zeros((shift,), jnp.uint64), a[1][:-shift]]),
        )
        a = mul(a, shifted)
        shift *= 2
    return a


def pow_const(a, e: int):
    result = None
    base = a
    e = int(e)
    while e:
        if e & 1:
            result = base if result is None else mul(result, base)
        e >>= 1
        if e:
            base = sqr(base)
    if result is None:
        return (jnp.ones_like(a[0]), jnp.zeros_like(a[1]))
    return result


# ---------------------------------------------------------------------------
# Host scalar ((int, int) tuple) ops
# ---------------------------------------------------------------------------

ZERO_S = (0, 0)
ONE_S = (1, 0)


def add_s(a, b):
    return (gl.add(a[0], b[0]), gl.add(a[1], b[1]))


def sub_s(a, b):
    return (gl.sub(a[0], b[0]), gl.sub(a[1], b[1]))


def neg_s(a):
    return (gl.neg(a[0]), gl.neg(a[1]))


def mul_s(a, b):
    v0 = gl.mul(a[0], b[0])
    v1 = gl.mul(a[1], b[1])
    c0 = gl.add(v0, gl.mul(v1, NON_RESIDUE))
    c1 = gl.add(gl.mul(a[0], b[1]), gl.mul(a[1], b[0]))
    return (c0, c1)


def mul_by_base_s(a, b: int):
    return (gl.mul(a[0], b), gl.mul(a[1], b))


def sqr_s(a):
    return mul_s(a, a)


def inv_s(a):
    d = gl.sub(gl.sqr(a[0]), gl.mul(gl.sqr(a[1]), NON_RESIDUE))
    dinv = gl.inv(d)
    return (gl.mul(a[0], dinv), gl.neg(gl.mul(a[1], dinv)))


def div_s(a, b):
    return mul_s(a, inv_s(b))


def pow_s(a, e: int):
    result = ONE_S
    base = a
    e = int(e)
    while e:
        if e & 1:
            result = mul_s(result, base)
        e >>= 1
        base = sqr_s(base)
    return result


def from_base_s(v: int):
    return (v, 0)


def powers_s(base, count: int):
    out = [ONE_S] * count
    for i in range(1, count):
        out[i] = mul_s(out[i - 1], base)
    return out
