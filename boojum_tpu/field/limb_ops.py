"""Limb-domain algebra for the quotient sweep: GF(p^2), powers, Horner.

`field/limbs.py` is the core Goldilocks algebra on `(lo, hi)` uint32 pairs —
the representation Mosaic accepts and XLA can fuse. This module is the
limb-domain ALGEBRA SURFACE layered on top of it (ISSUE 4): extension-field
helpers, power/horner supplies, boundary conversions, and the accumulate /
aggregate term combinators mirroring `prover/stages.py` — all in uint32 so
the SAME code runs inside Pallas kernels and as plain XLA. The sweep
kernels (`prover/pallas_sweep.py`) consume the combinators and broadcast
helpers directly; the power/horner/conversion primitives are the
kernel-side toolkit for stages that move limb-domain later (challenge
tables currently ride SMEM, computed outside the kernels) — every op here,
consumed or not yet, is pinned u64<->limb bit-exact by
tests/test_limb_sweep.py, so the surface cannot drift from goldilocks.py.

Conventions: a BASE element is a `(lo, hi)` pair of same-shape uint32
arrays; an EXT element of GF(p^2) = GF(p)[w]/(w^2 - 7) is a `(c0, c1)`
pair of base elements. Field ops are exact mod p and keep values
canonical, so any evaluation order produces bit-identical results to the
u64 path — parity is pinned per-op in tests/test_limb_sweep.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import blocked_inverse, gl
from . import limbs
from .limbs import add, double, ext_add, ext_mul, ext_sub, mul, neg, sqr, sub

NON_RESIDUE = 7


# ---------------------------------------------------------------------------
# Broadcast helpers
# ---------------------------------------------------------------------------


def zeros_like(a):
    """Base-field zero with `a`'s shape (`a` a limb pair or uint32 array)."""
    ref = a[0] if isinstance(a, tuple) else a
    z = jnp.zeros_like(ref)
    return z, z


def ones_like(a):
    ref = a[0] if isinstance(a, tuple) else a
    return jnp.ones_like(ref), jnp.zeros_like(ref)


def full_like(a, value: int):
    """A python-int field constant broadcast to `a`'s shape."""
    ref = a[0] if isinstance(a, tuple) else a
    clo, chi = limbs.const_pair(value)
    return jnp.full_like(ref, clo), jnp.full_like(ref, chi)


# ---------------------------------------------------------------------------
# Base-field extras
# ---------------------------------------------------------------------------


def mul_small(a, k: int):
    """Multiply by a small constant via modular double-and-add (mirrors
    goldilocks.mul_small; cheap on the VPU — no 16-bit product split)."""
    assert 0 <= k
    if k == 0:
        return zeros_like(a)
    acc = None
    addend = a
    while k:
        if k & 1:
            acc = addend if acc is None else add(acc, addend)
        k >>= 1
        if k:
            addend = double(addend)
    return acc


def powers(base, count: int):
    """[1, b, ..., b^(count-1)] as a python list of limb pairs (traced
    scalar chain — the limb counterpart of stages._ext_powers_traced's
    base-field half)."""
    assert count >= 1
    out = [ones_like(base)]
    for _ in range(count - 1):
        out.append(mul(out[-1], base))
    return out


def horner(coeffs, x):
    """Σ_j coeffs[j]·x^j by Horner's rule over limb pairs (coeffs[0] is the
    constant term). Exact mod p, so it matches the powers-table form
    bit-for-bit."""
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = add(mul(acc, x), c)
    return acc


# ---------------------------------------------------------------------------
# GF(p^2) extras (ext_add / ext_sub / ext_mul live in limbs.py)
# ---------------------------------------------------------------------------


def ext_neg(a):
    return neg(a[0]), neg(a[1])


def ext_sqr(a):
    return ext_mul(a, a)


def ext_mul_by_base(a, b):
    """Ext element `a` times base element `b`."""
    return mul(a[0], b), mul(a[1], b)


def ext_powers(base, count: int):
    """[1, g, ..., g^(count-1)] as a python list of ext limb elements."""
    assert count >= 1
    out = [(ones_like(base[0]), zeros_like(base[0]))]
    for _ in range(count - 1):
        out.append(ext_mul(out[-1], base))
    return out


def ext_horner(coeffs, x):
    """Σ_j coeffs[j]·x^j over ext limb elements."""
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = ext_add(ext_mul(acc, x), c)
    return acc


# ---------------------------------------------------------------------------
# Quotient-sweep combinators (stages.py counterparts, limb domain)
# ---------------------------------------------------------------------------


def accumulate(acc, term_base, ch):
    """acc += ch * term for a BASE-field term and ext challenge ch
    (stages.accumulate_ext)."""
    t0 = mul(term_base, ch[0])
    t1 = mul(term_base, ch[1])
    if acc is None:
        return (t0, t1)
    return add(acc[0], t0), add(acc[1], t1)


def ext_accumulate(acc, term_ext, ch):
    """acc += ch * term for an EXT term (stages.accumulate_ext_ext)."""
    t = ext_mul(term_ext, ch)
    if acc is None:
        return t
    return ext_add(acc, t)


def aggregate_columns(cols, table_id_col, gpow, beta):
    """Σ_j γ^j·col_j (+ γ^w·table_id) + β over base limb columns -> ext
    (stages.aggregate_lookup_columns). `gpow` is a list of ext elements
    [1, γ, γ², …] (broadcastable), `beta` an ext element."""
    like = cols[0][0] if isinstance(cols[0], tuple) else cols[0]
    acc0 = (
        jnp.broadcast_to(beta[0][0], like.shape),
        jnp.broadcast_to(beta[0][1], like.shape),
    )
    acc1 = (
        jnp.broadcast_to(beta[1][0], like.shape),
        jnp.broadcast_to(beta[1][1], like.shape),
    )
    seq = list(cols) + ([table_id_col] if table_id_col is not None else [])
    for j, col in enumerate(seq):
        acc0 = add(acc0, mul(col, gpow[j][0]))
        acc1 = add(acc1, mul(col, gpow[j][1]))
    return acc0, acc1


# ---------------------------------------------------------------------------
# Inversion (ISSUE 10: the resident prover's denominators/fold tables stay
# in limb planes end-to-end, so the Montgomery trick needs a limb form).
# Inverses are unique mod p and every op here is exact+canonical, so values
# are bit-identical to the u64 goldilocks.batch_inverse family, whatever
# the grouping: both instantiate `blocked_inverse.batch_inverse`.
# ---------------------------------------------------------------------------


def pow_int(a, e: int):
    """a ** e for a python-int exponent (square-and-multiply chain)."""
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
        return ones_like(a)
    return result


def inv(a):
    """Fermat inverse a^(p-2) on a limb pair; inverse of 0 is 0."""
    return pow_int(a, gl.P - 2)


# multiplications of one Fermat inversion: a squaring a bit below the top
# one of p - 2 and a multiply a set bit (`pow_int`)
FERMAT_MULS = (gl.P - 2).bit_length() + bin(gl.P - 2).count("1") - 2


def batch_inverse(a):
    """Montgomery batch inversion along the last axis on limb planes: the
    blocked form of `blocked_inverse`, three multiplications an element
    and one Fermat chain on a few elements a row, every chain step whole
    vregs with the batch folded beside the groups. Short independent
    chains, so nothing is carried across tiles (the carried scan that was
    tried, and the log-doubling scan that ran here until PR 33, are told
    there). No caller passes a zero, and nothing is promised for one."""
    return blocked_inverse.batch_inverse(a, mul, inv, (1, 0))


def batch_inverse_muls(shape) -> int:
    """Field multiplications `batch_inverse` spends on planes of `shape` by
    its plan; `ext_batch_inverse` inverts one norm an element, so the same
    (its four products an element for norm and conjugate are not in it)."""
    return blocked_inverse.planned_muls(shape, FERMAT_MULS)


def ext_batch_inverse(a):
    """GF(p^2) batch inversion on ext limb elements (extension.batch_inverse
    twin): 1/(c0 + c1 w) = (c0 - c1 w) / (c0² - 7 c1²)."""
    d = sub(sqr(a[0]), mul_small(sqr(a[1]), NON_RESIDUE))
    dinv = batch_inverse(d)
    return mul(a[0], dinv), neg(mul(a[1], dinv))


# top-level jit boundaries for the inversions (same posture as
# goldilocks.batch_inverse / extension.batch_inverse: the Fermat chain
# inlined into large XLA:CPU modules has miscompiled — keep it separate)
batch_inverse_jit = jax.jit(batch_inverse)
ext_batch_inverse_jit = jax.jit(ext_batch_inverse)


def counted(program, a):
    """Dispatch an inversion program (`batch_inverse_jit`,
    `ext_batch_inverse_jit`, `resident._lookup_denominators_inv_p`) on base
    or ext planes `a` from the prover's host code, its plan's
    multiplications added to the flight recorder's
    `field.batch_inverse_muls` (here and not in the routine, which is
    traced once a shape)."""
    from ..utils import metrics as _metrics

    ref = a[0][0] if isinstance(a[0], tuple) else a[0]
    _metrics.count("field.batch_inverse_muls", batch_inverse_muls(ref.shape))
    return program(a)


# ---------------------------------------------------------------------------
# u64-boundary conversions for ext pairs (stage seams only)
# ---------------------------------------------------------------------------


def ext_split(a_u64_pair):
    """(c0, c1) uint64 arrays -> ext limb element."""
    return limbs.split(a_u64_pair[0]), limbs.split(a_u64_pair[1])


def ext_join(a_limb_ext):
    """Ext limb element -> (c0, c1) uint64 arrays."""
    return limbs.join(a_limb_ext[0]), limbs.join(a_limb_ext[1])


def const_ext(c0: int, c1: int = 0):
    """Host ints -> ext element of numpy uint32 scalar pairs (bakeable)."""
    return limbs.const_pair(c0 % gl.P), limbs.const_pair(c1 % gl.P)
