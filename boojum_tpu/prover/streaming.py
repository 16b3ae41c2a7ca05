"""Streamed commit-rate LDE: bound HBM by never materializing full LDE
storages.

The reference's long-trace posture is cache-friendly blocked processing
(SURVEY §5); on an accelerator the binding constraint is HBM: at 2^20 rows
the materialized rate-L storages (witness + setup + stage-2 + quotient)
exceed the chip even at the Era commit rate. This module streams them in
column blocks straight from the (always-resident) monomials:

- commit: blocks of <= 64 columns LDE-transform, transpose to rows, and
  absorb 8 columns at a time into a CARRIED sponge state (N, 12) — the
  digest stream feeds `MerkleTreeWithCap.from_digests`, so the full
  (N, total_cols) leaf matrix never exists. Absorption order equals
  `leaf_hash` over whole rows, so trees (and proofs) are BIT-IDENTICAL to
  the materialized path.
- DEEP / query gathers: the same block generator re-evaluates each column
  block at query time (one extra LDE pass each — FLOPs traded for the
  ~4 GB of residency the materialized path pins).

Streaming engages when the committed-storage footprint would exceed an
eighth of the device's memory as its allocator reports it (1.5 GiB where
the backend reports no limit, and never less); BOOJUM_TPU_STREAM_LDE
overrides the choice ("1" forces on, "0" off, a number is a byte
threshold) — small traces keep the materialized fast path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..merkle import MerkleTreeWithCap
from ..ntt import lde_from_monomial

# columns per streamed block (a multiple of the sponge rate 8)
COL_BLOCK = 32


# Share of the device's memory the committed storages of one prove may take
# before the commits stream: the round-3 working sets, the NTT temporaries
# and the DEEP/FRI codewords need several times the storages beside them.
# On a 16 GB chip that is 2 GB: the Era geometry at 2^18 rows (1.67 GB)
# stays materialized, SHA-256 at 2^20 rows (4.4 GB) streams.
STREAM_SHARE_OF_DEVICE = 1 / 8
DEFAULT_STREAM_THRESHOLD = 1536 << 20


@functools.lru_cache(maxsize=1)
def _device_stream_threshold() -> float:
    """Once a process: the kernel enumeration, generate_setup and every
    prove must agree on which path runs."""
    from ..utils.metrics import device_memory_room

    memory = device_memory_room()
    if memory is None:
        return float(DEFAULT_STREAM_THRESHOLD)
    return max(
        float(DEFAULT_STREAM_THRESHOLD), STREAM_SHARE_OF_DEVICE * memory[0]
    )


def stream_threshold_bytes() -> float:
    """BOOJUM_TPU_STREAM_LDE as an override ("1" forces streaming, "0"
    forbids it, a number is a byte threshold); unset, the library chooses
    from the device's memory: a share of what the allocator reports as its
    limit, and 1.5 GiB where the backend reports none (XLA:CPU)."""
    v = os.environ.get("BOOJUM_TPU_STREAM_LDE", "").strip()
    if v == "0":
        return float("inf")
    if v == "1":
        return 0.0
    if v:
        try:
            return float(v)  # explicit byte threshold
        except ValueError:
            pass
    return _device_stream_threshold()


def use_streamed_lde(total_cols: int, domain_size: int) -> bool:
    return total_cols * domain_size * 8 > stream_threshold_bytes()


class MonomialSource:
    """A committed oracle's columns, represented by monomials + rate.

    Stands in for the materialized (B, L*n) flat array in the DEEP and
    query phases; `blocks()` regenerates rate-L column blocks on demand."""

    def __init__(self, mono, L: int):
        self.mono = mono
        self.L = int(L)

    @property
    def shape(self):
        return (self.mono.shape[0], self.mono.shape[-1] * self.L)

    def blocks(self, per: int = COL_BLOCK):
        B = self.mono.shape[0]
        for i in range(0, B, per):
            lde = lde_from_monomial(self.mono[i : i + per], self.L)
            yield i, lde.reshape(lde.shape[0], -1)  # (b, N)

    def column(self, i: int):
        """One column's rate-L values (N,) — for the handful of single
        columns round 5 opens at shifted points."""
        lde = lde_from_monomial(self.mono[i : i + 1], self.L)
        return lde.reshape(-1)

    def gather_rows(self, idx_dev):
        """(B, num_queries) leaf-value gather, blockwise."""
        parts = [flat[:, idx_dev] for _, flat in self.blocks()]
        return jnp.concatenate(parts, axis=0)


@jax.jit
def _sponge_absorb8(state, chunk8):
    """Overwrite-absorb 8 columns into a carried (N, 12) sponge state."""
    from ..hashes.poseidon2 import poseidon2_permutation

    st = jnp.concatenate([chunk8, state[:, 8:]], axis=-1)
    return poseidon2_permutation(st)


def streamed_leaf_digests(mono, L: int):
    """(N, 4) leaf digests of the rate-L LDE of `mono`, block-streamed.

    Traceable (plain jnp + python loops): callable inside a fused-round jit
    so the whole commit is one dispatch. Bit-identical to leaf_hash over the
    materialized (N, B) leaf matrix: full 8-column chunks absorb in order,
    the trailing partial chunk zero-pads (the sponge finalize rule)."""
    n = mono.shape[-1]
    N = n * L
    state = jnp.zeros((N, 12), jnp.uint64)
    rem = None  # (N, r < 8) trailing columns
    for _, flat in MonomialSource(mono, L).blocks():
        cols = flat.T  # (N, b)
        if rem is not None:
            cols = jnp.concatenate([rem, cols], axis=1)
            rem = None
        b = cols.shape[1]
        for k in range(b // 8):
            state = _sponge_absorb8(state, cols[:, 8 * k : 8 * k + 8])
        if b % 8:
            rem = cols[:, (b // 8) * 8 :]
    if rem is not None:
        pad = jnp.zeros((N, 8 - rem.shape[1]), jnp.uint64)
        state = _sponge_absorb8(state, jnp.concatenate([rem, pad], axis=1))
    return state[:, :4]


def streamed_leaf_digests_blocks(mono, L: int):
    """Block-DISPATCHED form of streamed_leaf_digests: bit-identical
    digests, but each COL_BLOCK column block is its own top-level jit
    keyed only on (block, n, L) — so the expensive NTT+Poseidon2 graph is
    compiled ONCE and reused across every block of every streamed oracle,
    instead of re-tracing the whole B-column absorb chain into each
    oracle's private mega-graph (the round-3 `_commit_fused` compile
    bill, ISSUE 1). The per-block dynamic_slice start rides as an array
    argument, so block index never enters a cache key.

    The commit is DOUBLE-BUFFERED:
    the LDE transform and the carried-sponge absorb are separate
    dispatches, and block b+1's transform is enqueued before block b's
    absorb — the transforms carry no data dependence on the sponge chain,
    so the device pipelines them instead of draining between blocks. The
    absorb order (and therefore every digest) is unchanged."""
    assert COL_BLOCK % 8 == 0
    n = mono.shape[-1]
    B = mono.shape[0]
    state = jnp.zeros((n * L, 12), jnp.uint64)

    def _lde(i):
        b = min(COL_BLOCK, B - i)
        blk = jax.lax.dynamic_slice_in_dim(mono, i, b, axis=0)
        return _lde_block_cols(blk, L)

    return double_buffered_absorb(
        state, range(0, B, COL_BLOCK), _lde
    )[:, :4]


def double_buffered_absorb(state, starts, produce_cols, absorb=None):
    """The double-buffered absorb loop shared by the meshless streamed
    commit above and the per-chip shard_map one
    (parallel/shard_sweep.streamed_leaf_digests_sm): block b+1's leaf
    columns (an LDE — and on the mesh, its pivot collective) are enqueued
    BEFORE block b's absorb, so the device pipelines transforms against
    the serial sponge chain. `produce_cols(start)` must return the (N, b)
    leaf columns for the block at `start`; absorb order — and therefore
    every digest — is identical to the sequential loop. `absorb` swaps
    the per-block absorb kernel (the limb-resident commit passes its
    plane twin); default is the u64 `_absorb_cols`."""
    from ..utils import metrics as _metrics

    if absorb is None:
        absorb = _absorb_cols
    starts = list(starts)
    nxt = produce_cols(starts[0])
    for k in range(len(starts)):
        cols, nxt = nxt, (
            produce_cols(starts[k + 1]) if k + 1 < len(starts) else None
        )
        _metrics.count("stream.double_buffered_blocks")
        state = absorb(state, cols)
    return state


from functools import partial as _partial


@_partial(jax.jit, static_argnums=(1,))
def _lde_block_cols(mono_blk, L: int):
    """One column block's rate-L leaf columns (N, b): its own dispatch,
    so the double-buffered commit can enqueue block b+1's transform while
    block b absorbs. Keyed (b, n, L)."""
    b = mono_blk.shape[0]
    lde = lde_from_monomial(mono_blk, L)
    return lde.reshape(b, -1).T  # (N, b)


@jax.jit
def _absorb_cols(state, cols):
    """Absorb an (N, b) leaf-column block into the carried sponge state:
    full 8-column chunks in order; a trailing partial chunk (only ever the
    final block of an oracle — COL_BLOCK is a multiple of the sponge rate)
    zero-pads per the sponge finalize rule, matching leaf_hash exactly."""
    b = cols.shape[1]
    for k in range(b // 8):
        state = _sponge_absorb8(state, cols[:, 8 * k : 8 * k + 8])
    rem = b % 8
    if rem:
        pad = jnp.zeros((cols.shape[0], 8 - rem), jnp.uint64)
        state = _sponge_absorb8(
            state, jnp.concatenate([cols[:, b - rem :], pad], axis=1)
        )
    return state


# ---------------------------------------------------------------------------
# Limb-plane streamed commit (ISSUE 10): the double-buffered blocks carry
# (lo, hi) u32 planes end-to-end — LDE, pivot-to-rows and the carried
# sponge state never materialize u64. Digest values are identical.
# ---------------------------------------------------------------------------


class MonomialPlanesSource:
    """MonomialSource twin over plane monomials: stands in for a resident
    oracle's materialized (B, L*n) plane pair in the DEEP/query phases."""

    def __init__(self, mono_p, L: int):
        self.mono = mono_p
        self.L = int(L)

    @property
    def shape(self):
        return (self.mono[0].shape[0], self.mono[0].shape[-1] * self.L)

    def blocks(self, per: int = COL_BLOCK):
        from ..ntt.limb_ntt import lde_from_monomial_p

        B = self.mono[0].shape[0]
        for i in range(0, B, per):
            blk = (self.mono[0][i : i + per], self.mono[1][i : i + per])
            lde = lde_from_monomial_p(blk, self.L)
            b = lde[0].shape[0]
            yield i, (lde[0].reshape(b, -1), lde[1].reshape(b, -1))

    def column(self, i: int):
        from ..ntt.limb_ntt import lde_from_monomial_p

        blk = (self.mono[0][i : i + 1], self.mono[1][i : i + 1])
        lde = lde_from_monomial_p(blk, self.L)
        return lde[0].reshape(-1), lde[1].reshape(-1)

    def gather_rows(self, idx_dev):
        parts = [
            (flat[0][:, idx_dev], flat[1][:, idx_dev])
            for _, flat in self.blocks()
        ]
        return (
            jnp.concatenate([p[0] for p in parts], axis=0),
            jnp.concatenate([p[1] for p in parts], axis=0),
        )


@jax.jit
def _sponge_absorb8_p(state_p, chunk8_p):
    from ..hashes.poseidon2 import poseidon2_permutation_planes

    st = (
        jnp.concatenate([chunk8_p[0], state_p[0][:, 8:]], axis=-1),
        jnp.concatenate([chunk8_p[1], state_p[1][:, 8:]], axis=-1),
    )
    return poseidon2_permutation_planes(st)


@jax.jit
def _absorb_cols_p(state_p, cols_p):
    """Plane twin of _absorb_cols (same chunk/finalize semantics)."""
    b = cols_p[0].shape[1]
    for k in range(b // 8):
        state_p = _sponge_absorb8_p(
            state_p,
            (cols_p[0][:, 8 * k : 8 * k + 8], cols_p[1][:, 8 * k : 8 * k + 8]),
        )
    rem = b % 8
    if rem:
        pad = jnp.zeros((cols_p[0].shape[0], 8 - rem), jnp.uint32)
        state_p = _sponge_absorb8_p(
            state_p,
            (
                jnp.concatenate([cols_p[0][:, b - rem :], pad], axis=1),
                jnp.concatenate([cols_p[1][:, b - rem :], pad], axis=1),
            ),
        )
    return state_p


@_partial(jax.jit, static_argnums=(1,))
def _lde_block_cols_p(mono_blk_p, L: int):
    """Plane twin of _lde_block_cols: (b, n) monomial planes ->
    (N, b) leaf-column planes."""
    from ..ntt.limb_ntt import lde_from_monomial_p

    b = mono_blk_p[0].shape[0]
    lde = lde_from_monomial_p(mono_blk_p, L)
    return lde[0].reshape(b, -1).T, lde[1].reshape(b, -1).T


def streamed_leaf_digests_blocks_p(mono_p, L: int):
    """Plane twin of streamed_leaf_digests_blocks: (N, 4) digest planes,
    double-buffered exactly like the u64 form."""
    assert COL_BLOCK % 8 == 0
    n = mono_p[0].shape[-1]
    B = mono_p[0].shape[0]
    state = (
        jnp.zeros((n * L, 12), jnp.uint32),
        jnp.zeros((n * L, 12), jnp.uint32),
    )

    def _blk(i):
        b = min(COL_BLOCK, B - i)
        return (
            jax.lax.dynamic_slice_in_dim(mono_p[0], i, b, axis=0),
            jax.lax.dynamic_slice_in_dim(mono_p[1], i, b, axis=0),
        )

    state = double_buffered_absorb(
        state,
        range(0, B, COL_BLOCK),
        lambda i: _lde_block_cols_p(_blk(i), L),
        absorb=_absorb_cols_p,
    )
    return state[0][:, :4], state[1][:, :4]


def commit_streaming(mono, L: int, cap_size: int) -> MerkleTreeWithCap:
    """Merkle-commit the rate-L LDE of `mono` without materializing it."""
    return MerkleTreeWithCap.from_digests(
        streamed_leaf_digests_blocks(mono, L), cap_size
    )


def deep_source_blocks(sources, per_bytes: int):
    """Yield (block (b, N), column_offset) across mixed sources: plain
    (B, N) arrays slice by a byte budget; MonomialSource regenerates."""
    off = 0
    for src in sources:
        if isinstance(src, MonomialSource):
            for i, flat in src.blocks():
                yield flat, off + i
            off += src.shape[0]
        else:
            B, N = src.shape
            per = max(1, per_bytes // (N * 8))
            for i in range(0, B, per):
                yield src[i : i + per], off + i
            off += B
