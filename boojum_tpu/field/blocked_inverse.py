"""Montgomery batch inversion in blocks: three multiplications an element.

The one algorithm behind `goldilocks.batch_inverse_xla`,
`babybear.batch_inverse_xla` and `limb_ops.batch_inverse`, written over a
representation's own `(mul, inv, one)`.

A row of n elements is viewed as `(C, m)`, element `i = k*m + j`: m
independent groups, each a chain of C elements a stride m apart. Forward,
`E_0 = 1, E_k = E_{k-1} * a_{k-1}` leaves every exclusive prefix and the
group totals `T = E_{C-1} * a_{C-1}`; the totals, m a row, are inverted by
the same routine; backward from `r = 1/T`, `out_k = r * E_k` and
`r = r * a_k` for k = C-1 ... 0. Three multiplications an element a level,
levels shrinking by C, and one Fermat chain on the last few elements.

Every chain step is one elementwise multiply over ALL groups of ALL rows:
the batch axes are folded beside the groups into `(C, groups / 128, 128)`,
so a step is whole (8, 128) vregs whatever the batch is, and a `(9, n)`
stack costs nine sixteenths of a `(16, n)` one. Nothing is carried from
one group to the next. (What ran before: two Hillis-Steele log-doubling
prefix products, 2 log2 n + 2 full-length multiplies an element with a
full-length shift each, the batch axis of a `(B, n)` plane on the 128
lanes (a (9, n) stack filled 7 % of every vreg); and, tried before
that, ONE chain across the whole axis as a sequential-tile Pallas scan,
ten times slower on the v5e because its carry serialized the grid. The
log n depth bought nothing: rows and groups are independent already.)

Zeros: an inverse of zero does not exist and no caller passes one
(denominators at random challenges, points of a coset). A zero spoils the
outputs of the group it is chained with, and through the recursion
possibly more of its row; nothing is promised about which.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# a chain step is whole vregs while a row has this many groups
_TILE = 8 * 128
_MAX_CHAIN = 64
# rows this short are inverted elementwise, one Fermat chain
_FERMAT_FLOOR = 32


def chain_length(n: int) -> int:
    """The chain length C of the level that inverts rows of `n` elements,
    a function of `n` alone; 1 where the row is inverted directly. Long
    rows keep at least `_TILE` groups; a row too short for that goes
    straight down to the floor."""
    if n <= _FERMAT_FLOOR:
        return 1
    if n >= 2 * _TILE:
        return min(_MAX_CHAIN, n // _TILE)
    return min(_MAX_CHAIN, -(-n // _FERMAT_FLOOR))


def planned_muls(shape, fermat_muls: int) -> int:
    """Field multiplications the plan spends on an array of `shape`
    (`fermat_muls`: those of one elementwise inversion)."""
    rows, n = math.prod(shape[:-1]), int(shape[-1])
    c = chain_length(n)
    if c == 1:
        return rows * fermat_muls * n
    m = -(-n // c)
    return rows * 3 * c * m + planned_muls((rows, m), fermat_muls)


def batch_inverse(a, mul, inv, one):
    """Elementwise inverses of `a` along the last axis. `a` is a pytree of
    same-shape arrays (a u64 array, a (lo, hi) plane pair), `one` the
    multiplicative identity as the same pytree of python ints, `mul` and
    `inv` the representation's own."""
    shape = jax.tree.leaves(a)[0].shape
    lead, n = shape[:-1], shape[-1]
    c = chain_length(n)
    if c == 1:
        return inv(a)
    m = -(-n // c)
    pad = c * m - n
    groups = math.prod(lead) * m
    step = (groups // 128, 128) if groups % 128 == 0 else (groups,)

    def to_chains(x, o):
        if pad:
            ones = jnp.full(lead + (pad,), o, x.dtype)
            x = jnp.concatenate([x, ones], axis=-1)
        x = jnp.moveaxis(x.reshape(lead + (c, m)), -2, 0)
        return x.reshape((c,) + step)

    def from_chains(y):
        y = jnp.moveaxis(y.reshape((c,) + lead + (m,)), 0, -2)
        y = y.reshape(lead + (c * m,))
        return y[..., :n] if pad else y

    xs = jax.tree.map(to_chains, a, one)
    ones = jax.tree.map(lambda x, o: jnp.full(step, o, x.dtype), a, one)
    total, excl = lax.scan(lambda r, x: (mul(r, x), r), ones, xs)
    total = jax.tree.map(lambda t: t.reshape(lead + (m,)), total)
    r = jax.tree.map(
        lambda t: t.reshape(step), batch_inverse(total, mul, inv, one)
    )
    _, out = lax.scan(
        lambda r, xe: (mul(r, xe[0]), mul(r, xe[1])), r, (xs, excl),
        reverse=True,
    )
    return jax.tree.map(from_chains, out)
