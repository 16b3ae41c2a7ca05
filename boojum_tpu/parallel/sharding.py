"""Multi-chip sharding of the proving pipeline over a jax device mesh.

The reference is single-node rayon data parallelism (SURVEY.md §2.4;
`/root/reference/src/worker/mod.rs:5`). The TPU-native scaling axes are:

- ``col``  — trace columns. Through round 3 every polynomial op (iNTT, coset
  LDE, gate sweep) is per-column, so columns shard across chips with ZERO
  communication; this is the tensor-parallel analogue.
- ``row``  — the LDE domain. Merkle leaf hashing consumes ALL columns of one
  domain row, so between the per-column NTT phase and the hashing phase the
  layout pivots from column-sharded to row-sharded — one all-to-all that XLA
  inserts from sharding constraints (the framework never writes a collective
  by hand; GSPMD propagates them over ICI).

Merkle caps, transcript inputs and FRI final polys are tiny and replicated.
"""

from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..field import gl
from ..field import goldilocks as gf
from ..field import extension as ext_f
# the explicitly-XLA sponge entry points: this module's arrays carry
# NamedShardings for GSPMD to partition, which pallas_call cannot split
from ..hashes.poseidon2 import leaf_hash_xla as leaf_hash
from ..hashes.poseidon2 import node_hash_xla as node_hash
from ..ntt import lde_from_monomial, monomial_from_values, powers_device


_ACTIVE_MESH: list = [None]


def active_mesh() -> Mesh | None:
    """The mesh the prover is currently sharding over (None = single chip)."""
    return _ACTIVE_MESH[0]


def shard_map_mesh(variant=None) -> Mesh | None:
    """The active mesh when it executes via shard_map (each chip runs the
    native kernels on its local shard, collectives written explicitly:
    parallel/shard_sweep.py), else None. `variant` is the prove's resolved
    record (utils/pallas_util.resolve_variant); without one the resolver
    is asked."""
    if variant is None:
        from ..utils.pallas_util import resolve_variant

        variant = resolve_variant()
    return active_mesh() if variant.mesh == "shard_map" else None


class prover_mesh:
    """Context manager activating a device mesh for a full `prove()` run.

    Inside the context the prover device-puts its polynomial-batch inputs
    column-sharded and pivots Merkle leaves to row sharding; every jitted
    stage then auto-partitions from its operand shardings (GSPMD inserts
    the collectives). All field ops are exact integer ops with a fixed
    reduction structure, so the sharded proof is byte-identical to the
    single-device proof.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        self._prev = _ACTIVE_MESH[0]
        _ACTIVE_MESH[0] = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH[0] = self._prev
        return False


_SHARD_COLS_WARNED: set = set()


def _note_shard_axis(axis: str, shape, ncol: int):
    """Audit trail for shard_cols' divisibility fallback: the chosen axis
    lands on the current flight-recorder span as an attribute, and every
    fallback away from 'col' logs ONE warning per (shape, mesh) so mesh
    runs silently sharding the wrong axis become visible."""
    from ..utils.spans import span_attr

    span_attr("shard_cols_axis", axis)
    if axis == "col":
        return
    key = (axis, tuple(shape), ncol)
    if key in _SHARD_COLS_WARNED:
        return
    _SHARD_COLS_WARNED.add(key)
    logging.getLogger("boojum_tpu").warning(
        "shard_cols: batch axis %s does not divide the %d-way 'col' mesh "
        "axis; sharding %s instead",
        shape,
        ncol,
        "the domain axis" if axis.startswith("domain") else "nothing",
    )


def shard_cols(arr):
    """Column-shard a (C, ...) polynomial batch over the active mesh (no-op
    when no mesh is active). Column counts are arbitrary (e.g. 15 oracle
    columns over a 4-way axis), and NamedSharding demands divisibility, so
    when 'col' does not divide the batch axis the (power-of-two) domain axis
    is sharded instead — the row axis always divides it. Fallbacks are
    logged once and recorded as a span attribute (_note_shard_axis)."""
    m = active_mesh()
    if m is None:
        return arr
    ncol, nrow = m.shape["col"], m.shape["row"]
    nd = arr.ndim
    if arr.shape[0] % ncol == 0:
        spec = P("col", *([None] * (nd - 1)))
        _note_shard_axis("col", arr.shape, ncol)
    elif arr.shape[-1] % (ncol * nrow) == 0:
        spec = P(*([None] * (nd - 1)), ("col", "row"))
        _note_shard_axis("domain(col,row)", arr.shape, ncol)
    elif arr.shape[-1] % nrow == 0:
        spec = P(*([None] * (nd - 1)), "row")
        _note_shard_axis("domain(row)", arr.shape, ncol)
    else:
        _note_shard_axis("none", arr.shape, ncol)
        return arr
    return jax.device_put(arr, NamedSharding(m, spec))


def shard_leaves(arr):
    """Row-shard a (num_leaves, width) leaf batch over BOTH mesh axes (the
    col->row layout pivot before Merkle leaf hashing). Falls back to the
    largest mesh axis dividing the (power-of-two) leaf count on non-pow2
    meshes, and to no sharding when nothing divides."""
    m = active_mesh()
    if m is None:
        return arr
    n = arr.shape[0]
    ncol, nrow = m.shape["col"], m.shape["row"]
    if n % (ncol * nrow) == 0:
        axes = ("col", "row")
    elif n % ncol == 0:
        axes = ("col",)
    elif n % nrow == 0:
        axes = ("row",)
    else:
        return arr
    spec = P(axes, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(m, spec))


def default_col_axis(n: int) -> int:
    """Favor the column axis (columns carry the zero-communication phase):
    the largest power of two <= sqrt-ish of the device count dividing it."""
    col_axis = 1 << (n.bit_length() // 2)
    while n % col_axis:
        col_axis //= 2
    return col_axis


def make_mesh(devices=None, col_axis: int | None = None) -> Mesh:
    """2D ('col', 'row') mesh over the given (or all) devices.

    col_axis devices shard trace columns; the rest shard LDE-domain rows.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if col_axis is None:
        col_axis = default_col_axis(n)
    row_axis = n // col_axis
    dev_grid = np.array(devices).reshape(col_axis, row_axis)
    return Mesh(dev_grid, axis_names=("col", "row"))


def col_sharding(mesh: Mesh) -> NamedSharding:
    """(C, n) polynomial storage: columns across 'col', rows replicated."""
    return NamedSharding(mesh, P("col", None))


def leaf_sharding(mesh: Mesh) -> NamedSharding:
    """(num_leaves, width) leaf storage: leaves across BOTH mesh axes."""
    return NamedSharding(mesh, P(("col", "row"), None))


def _num_den_products(copy_vals, sigma_vals, non_residues, beta, gamma):
    """Copy-permutation numerator/denominator column products (column axis
    collapses via a log tree of ext muls; with a column-sharded operand, XLA
    turns the tree into a psum-style reduction over ICI)."""
    C, n = copy_vals.shape
    omega = gl.omega(n.bit_length() - 1)
    xs = powers_device(omega, n)
    b0, b1 = beta[0], beta[1]
    g0, g1 = gamma[0], gamma[1]
    ks = non_residues
    kx = gf.mul(xs[None, :], ks[:, None])  # (C, n)
    num = (
        gf.add(gf.add(copy_vals, gf.mul(kx, b0)), g0),
        gf.add(gf.mul(kx, b1), g1),
    )
    den = (
        gf.add(gf.add(copy_vals, gf.mul(sigma_vals, b0)), g0),
        gf.add(gf.mul(sigma_vals, b1), g1),
    )

    def tree_prod(pair):
        c0, c1 = pair
        while c0.shape[0] > 1:
            if c0.shape[0] % 2:
                c0 = jnp.concatenate([c0, jnp.ones((1, c0.shape[1]), jnp.uint64)])
                c1 = jnp.concatenate([c1, jnp.zeros((1, c1.shape[1]), jnp.uint64)])
            h = c0.shape[0] // 2
            c0, c1 = ext_f.mul((c0[:h], c1[:h]), (c0[h:], c1[h:]))
        return c0[0], c1[0]

    return tree_prod(num), tree_prod(den)


def _z_from_ratio(ratio):
    """Exclusive prefix product of the per-row ratio (shared log-doubling
    scan, field/extension.prefix_product)."""
    incl = ext_f.prefix_product(ratio)
    one = jnp.ones((1,), jnp.uint64)
    zero = jnp.zeros((1,), jnp.uint64)
    return (
        jnp.concatenate([one, incl[0][:-1]]),
        jnp.concatenate([zero, incl[1][:-1]]),
    )


def _commit_fragment(copy_vals, lde_factor, cap_size, mesh):
    """Per-column iNTT -> coset LDE -> Merkle digest layers with the
    col->row layout pivot."""
    from ..utils.pallas_util import force_xla

    C, n = copy_vals.shape
    with force_xla():
        mono = monomial_from_values(copy_vals)  # column-sharded, no comm
        lde = lde_from_monomial(mono, lde_factor)  # (C, L, n) per-column
    leaves = lde.reshape(C, -1).T  # (L*n, C): the layout pivot
    leaves = jax.lax.with_sharding_constraint(leaves, leaf_sharding(mesh))
    digests = leaf_hash(leaves)  # (L*n, 4) row-sharded
    while digests.shape[0] > cap_size:
        digests = node_hash(digests[0::2], digests[1::2])
    return jax.lax.with_sharding_constraint(
        digests, NamedSharding(mesh, P(None, None))
    )


def _prove_fragment(copy_vals, sigma_vals, non_residues, beta, gamma,
                    lde_factor, cap_size, mesh):
    """Single-graph form of the rounds-1+2 core (used by the driver's
    single-chip COMPILE check; execution goes through the sequenced phases
    of sharded_prove_fragment)."""
    cap = _commit_fragment(copy_vals, lde_factor, cap_size, mesh)
    num_p, den_p = _num_den_products(
        copy_vals, sigma_vals, non_residues, beta, gamma
    )
    ratio = ext_f.mul(num_p, ext_f.batch_inverse(den_p))
    z = _z_from_ratio(ratio)
    return cap, z


def sharded_prove_fragment(mesh: Mesh, lde_factor: int = 4, cap_size: int = 4):
    """The prove fragment over `mesh`, as a SEQUENCE of jitted phases.

    Inputs: copy_vals/sigma_vals (C, n) uint64; non_residues (C,) uint64;
    beta/gamma (2,) uint64 extension scalars.

    Phased rather than one fused jit for two reasons: the extension-field
    batch inversion must sit at a top-level jit boundary (XLA:CPU has
    produced never-terminating executables when its inversion chain is
    inlined into large modules — see prover/stages.py), and each phase's
    GSPMD partitioning stays small and predictable.
    """
    cs = col_sharding(mesh)
    rep = NamedSharding(mesh, P())

    commit = jax.jit(
        lambda cv: _commit_fragment(cv, lde_factor, cap_size, mesh),
        in_shardings=(cs,),
    )
    numden = jax.jit(
        _num_den_products, in_shardings=(cs, cs, rep, rep, rep)
    )
    ratio_z = jax.jit(
        lambda num_p, den_inv: _z_from_ratio(ext_f.mul(num_p, den_inv))
    )

    def run(copy_vals, sigma_vals, non_residues, beta, gamma):
        cap = commit(copy_vals)
        num_p, den_p = numden(copy_vals, sigma_vals, non_residues, beta, gamma)
        den_inv = ext_f.batch_inverse(den_p)
        return cap, ratio_z(num_p, den_inv)

    return run


def host_np(x):
    """np.asarray that also works for MULTI-PROCESS global arrays: a
    sharded jax.Array spanning non-addressable devices cannot be fetched
    directly (jax raises), so gather it to every host first. Single-process
    (and plain numpy/host values) pass straight through.

    Delegates to utils.transfer.to_host — the pipeline's single blocking
    d2h seam, where the flight recorder's d2h byte counter and the
    `host.blocking_syncs` tick live (no-ops without a metrics registry).
    Batched/prefetched pulls go through transfer.start_fetch instead."""
    from ..utils.transfer import to_host

    return to_host(x)
