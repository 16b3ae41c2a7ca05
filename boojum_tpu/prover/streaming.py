"""Streamed commit-rate LDE: bound HBM by never materializing full LDE
storages.

The reference's long-trace posture is cache-friendly blocked processing
(SURVEY §5); on an accelerator the binding constraint is HBM: at 2^19 rows
of the Era geometry the materialized rate-L storages (witness + setup +
stage-2 + quotient, 399 columns x 2^20 x 8 B = 3.35 GB) and round 3's
working sets beside them exceed the chip.

What streams, and what stays resident. The monomials of every oracle stay
on the device through a prove (they are what rounds 3-5 evaluate from).
The commits of ONE prove stream together, witness, stage 2 and quotient
(`merkle.streamed_commits` 3): their rate-L storages are never made.
The setup oracle is decided ALONE, by `generate_setup`
(`use_streamed_lde(B_setup, N)`): under the threshold it is committed
materialized and stays resident, beside streamed proves too (a MIXED prove:
`keccak256-era-512k`, 1.40 GB of setup storage under 2.1 GB), and round 3
reads its committed cosets (`prover.coset_is_committed`) while the streamed
groups transform on every coset.

- commit: blocks of COL_BLOCK columns LDE-transform and absorb 8 columns
  at a time into a CARRIED sponge state of 12 words a leaf — the
  digest stream feeds the node stack, so the full (N, total_cols) leaf
  matrix never exists. Absorption order equals `leaf_hash` over whole rows,
  so trees (and proofs) are BIT-IDENTICAL to the materialized path.
- DEEP / query gathers: the same blocks are re-evaluated from the monomials
  (one extra LDE pass each, `stream.regen_columns` — FLOPs traded for the
  residency the materialized path pins), and round 5's few single columns
  once more (`cols_from_mono`).

On limb planes every streamed routine is host-driven, at every size: the
forward NTT above 2^16 rows is a device program of its own that no jit may
hold (`limb_ntt._hybrid_fwd_p`), so the host dispatches a block as a cut
(`_lde_block_cols_take_p`), one forward transform a chunk of
`lde_from_monomial_p`'s walk (16 columns at 2^19 rows) and their join
(`_lde_block_cols_join_p`); below that size the same three steps run with
the size's own transform. The commit is double-buffered, and every loop over
blocks keeps at most BLOCK_LEAD of them in flight (`BlockLead`: dispatch
allocates, and the host runs ahead). On planes the block and the carried
sponge state are column-major, (b, N) and (12, N): leaves along the lanes,
as the transform leaves them and the permutation kernel takes them
(`_absorb_cols_cm_p`); the shard_map commit keeps the row-major
`_absorb_cols_p` for its pivoted blocks.

The threshold. Streaming engages when the committed-storage footprint would
exceed an eighth of the device's memory as its allocator reports it (2.1 GB
on a v5e; 1.5 GiB where the backend reports no limit, and never less);
BOOJUM_TPU_STREAM_LDE overrides the choice ("1" forces on, "0" off, a number
is a byte threshold) — small traces keep the materialized fast path.
"""

from __future__ import annotations

import contextlib
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


# Blocks of a streamed loop the host may have in flight. Dispatch allocates:
# a host that runs ahead of the device (it always does: a block is a dozen
# launches, 3 ms of host time, and 10 to 130 ms of device time) would hold
# every block of an oracle at once, 155 columns' transforms and their
# temporaries at 2^19 rows, which is the storage streaming exists not to
# hold. A fixed number, so that the peak does not follow the host's pace.
BLOCK_LEAD = 2


class BlockLead:
    """Holds a streamed loop to BLOCK_LEAD blocks in flight: `done(x)` is
    told what each block produced, in order, and waits for the block
    BLOCK_LEAD back (one `host.sync` span, `d2h.stream_block`, and one
    tick of `host.blocking_syncs` a wait). The device still has the blocks
    between queued when the host wakes, so it does not idle."""

    def __init__(self):
        self._produced = []

    def done(self, x):
        from ..utils import metrics as _metrics
        from ..utils import transfer as _transfer

        self._produced.append(x)
        if len(self._produced) > BLOCK_LEAD:
            _metrics.count("host.blocking_syncs")
            with _transfer.sync("stream_block"):
                jax.block_until_ready(self._produced.pop(0))


def count_lde_columns(phase: str, columns: int):
    """`stream.lde_columns.<phase>`: columns evaluated from monomials at
    rate L because no storage holds them, counted where the host
    dispatches the transforms (`commit`, `deep`, `queries`); the two
    regenerations also add up in `stream.regen_columns`."""
    from ..utils import metrics as _metrics

    _metrics.count(f"stream.lde_columns.{phase}", columns)
    if phase != "commit":
        _metrics.count("stream.regen_columns", columns)


def _block_span(name: str | None, columns: int):
    from ..utils.spans import span as _span

    return _span(name, columns=columns) if name else contextlib.nullcontext()


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

    def blocks(self, per: int = COL_BLOCK, span: str | None = None):
        """`span` names a span opened around each block's dispatch (never
        across the `yield`: the consumer's work is not the block's)."""
        B = self.mono.shape[0]
        for i in range(0, B, per):
            with _block_span(span, min(per, B - i)):
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
        count_lde_columns("commit", b)
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
    lead = BlockLead()
    nxt = produce_cols(starts[0])
    for k in range(len(starts)):
        cols, nxt = nxt, (
            produce_cols(starts[k + 1]) if k + 1 < len(starts) else None
        )
        _metrics.count("stream.double_buffered_blocks")
        state = absorb(state, cols)
        lead.done(state)
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
    oracle's materialized (B, L*n) plane pair in the DEEP/query phases.

    A block is `lde_rows_p`'s two dispatches, made from the host: no jit
    may hold the forward transform above 2^16 rows
    (limb_ntt._hybrid_fwd_p), and one form serves every size."""

    def __init__(self, mono_p, L: int):
        self.mono = mono_p
        self.L = int(L)

    @property
    def shape(self):
        return (self.mono[0].shape[0], self.mono[0].shape[-1] * self.L)

    def blocks(self, span: str | None = None):
        """(first column, (b, N) planes) over the whole oracle; `span` as
        `MonomialSource.blocks` takes it."""
        B, n = self.mono[0].shape
        lead = BlockLead()  # bounded like the commit
        i = 0
        for b in block_chunk_sizes(B, n, self.L):
            with _block_span(span, b):
                lde = lde_rows_p(self.mono, i, b, self.L)
                lead.done(lde)
            yield i, (lde[0].reshape(b, -1), lde[1].reshape(b, -1))
            i += b

    def gather_rows(self, idx_dev):
        """(B, num_queries) leaf-value planes, blockwise."""
        return _stream_gather_join_p(tuple(
            _stream_gather_block_p(flat, idx_dev) for _, flat in self.blocks()
        ))


def block_chunk_sizes(B: int, n: int, L: int) -> list[int]:
    """Column counts of the blocks a streamed oracle of B columns is
    regenerated in: COL_BLOCK columns, each in the chunks
    `lde_from_monomial_p` walks, one forward dispatch each (at most 128 MiB
    of output: 16 columns at 2^19 rows under L = 2)."""
    from ..ntt.limb_ntt import lde_chunk_sizes

    return [
        c
        for i in range(0, B, COL_BLOCK)
        for c in lde_chunk_sizes(min(COL_BLOCK, B - i), n, L)
    ]


@_partial(jax.jit, static_argnums=(2,))
def _lde_block_cols_take_p(mono_p, start, b: int):
    """Rows [start, start + b) of a monomial stack, `start` a device
    scalar: the input of one forward dispatch."""
    return tuple(
        jax.lax.dynamic_slice_in_dim(a, start, b, axis=0) for a in mono_p
    )


def lde_rows_p(mono_p, start: int, b: int, L: int):
    """(b, L, n) rate-L planes of columns [start, start + b) of `mono_p`:
    the cut, then ONE forward dispatch (b is a chunk of `lde_chunk_sizes`),
    each keyed on the chunk alone, so that every streamed oracle shares
    them in the commit, in DEEP and in the queries."""
    from ..ntt.limb_ntt import lde_from_monomial_p

    if b != mono_p[0].shape[0]:
        mono_p = _lde_block_cols_take_p(mono_p, jnp.int32(start), b)
    return lde_from_monomial_p(mono_p, L)


@jax.jit
def _stream_gather_block_p(flat_p, idx_dev):
    return flat_p[0][:, idx_dev], flat_p[1][:, idx_dev]


@jax.jit
def _stream_gather_join_p(parts):
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


@jax.jit
def _lde_block_cols_join_p(chunks):
    """The chunks of one column block, each (c, L, n) planes, as the
    block's (b, N) leaf-column planes, COLUMN-MAJOR (leaves along the
    lanes, as `_absorb_cols_cm_p` takes them)."""
    return tuple(
        jnp.concatenate(
            [c[k].reshape(c[k].shape[0], -1) for c in chunks], axis=0
        )
        for k in (0, 1)
    )


def lde_block_cols_p(mono_p, start: int, b: int, L: int):
    """(b, N) leaf-column planes of columns [start, start + b) of the
    monomial stack: the host dispatches the transform of each chunk
    (`lde_rows_p`) and one program joins them."""
    from ..ntt.limb_ntt import lde_chunk_sizes

    n = mono_p[0].shape[-1]
    chunks, i = [], start
    for c in lde_chunk_sizes(b, n, L):
        chunks.append(lde_rows_p(mono_p, i, c, L))
        i += c
    return _lde_block_cols_join_p(tuple(chunks))


@jax.jit
def _absorb_cols_cm_p(state_p, cols_p):
    """`_absorb_cols_p` on COLUMN-MAJOR planes: the carried state is
    (12, N) and the block (b, N), leaves along the lanes. That is how the
    transform leaves a block and how the permutation kernel takes its
    state, so nothing is transposed; and an (N, 12) or (N, 32) array on the
    TPU is held in (8, 128) tiles padded out to 128 lanes, 11 and 4 times
    its bytes, at 2^20 leaves a GB a state and a GB a block in flight.
    Same chunks, same finalize rule, same digests."""
    from ..hashes.poseidon2 import poseidon2_permutation_planes_cm

    b, N = cols_p[0].shape
    for k in range(0, b, 8):
        rows = min(8, b - k)
        pad = jnp.zeros((8 - rows, N), jnp.uint32)
        state_p = poseidon2_permutation_planes_cm(tuple(
            jnp.concatenate([c[k : k + rows], pad, s[8:]], axis=0)
            for c, s in zip(cols_p, state_p)
        ))
    return state_p


@jax.jit
def _absorb_cols_digests_p(state_p):
    """The carried (12, N) state's first four words as the (N, 4) digest
    planes the node stack takes."""
    return state_p[0][:4].T, state_p[1][:4].T


def streamed_leaf_digests_blocks_p(mono_p, L: int):
    """Plane twin of streamed_leaf_digests_blocks: (N, 4) digest planes,
    double-buffered exactly like the u64 form, on column-major planes
    (`_absorb_cols_cm_p`)."""
    assert COL_BLOCK % 8 == 0
    n = mono_p[0].shape[-1]
    B = mono_p[0].shape[0]
    state = (
        jnp.zeros((12, n * L), jnp.uint32),
        jnp.zeros((12, n * L), jnp.uint32),
    )

    def _block(i):
        b = min(COL_BLOCK, B - i)
        count_lde_columns("commit", b)
        return lde_block_cols_p(mono_p, i, b, L)

    state = double_buffered_absorb(
        state, range(0, B, COL_BLOCK), _block, absorb=_absorb_cols_cm_p
    )
    return _absorb_cols_digests_p(state)


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
            count_lde_columns("deep", src.shape[0])
            for i, flat in src.blocks(span="stream.deep_regen"):
                yield flat, off + i
            off += src.shape[0]
        else:
            B, N = src.shape
            per = max(1, per_bytes // (N * 8))
            for i in range(0, B, per):
                yield src[i : i + per], off + i
            off += B
