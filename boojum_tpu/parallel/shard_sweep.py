"""shard_map mesh execution: native per-chip kernels + explicit collectives.

The GSPMD mesh path (parallel/sharding.py) lets XLA insert every collective
from NamedSharding constraints — which is exactly why it cannot run the
fused Pallas kernels: GSPMD cannot partition a `pallas_call`, so the
multi-chip prover fell back to the slowest u64-emulated XLA bodies right
where the FLOPs are (ISSUE 5). This module is the explicit counterpart:

- every heavy kernel — the per-column iNTT/LDE, the fused coset-sweep
  terms kernel, the limb FRI fold, the Poseidon2 leaf sponge — runs inside
  `jax.experimental.shard_map` over the ('col','row') mesh, so each chip
  traces the kernel at its LOCAL block shape and Pallas never sees a
  sharded operand;
- the col->row Merkle layout pivot is ONE hand-written `lax.all_to_all`
  on the rate-L column blocks (DIZK's lesson: the distributed prover lives
  or dies on how this pivot is orchestrated), and replicated outputs (caps,
  gathered node layers) are ONE explicit `lax.all_gather` — both charged
  to `ici.*` gauges so the interconnect bill is a first-class metric;
- digests, checkpoints and proof bytes are bit-identical to the
  single-chip path: the per-chip kernels are the same exact-integer field
  ops over a partition of the data, and the collectives only move bytes.

Column batches whose count does not divide the device count are zero-padded
to a multiple (padding columns iNTT/LDE to zeros and are sliced off after
the pivot, BEFORE any sponge absorb — so hashing sees exactly the real
columns, in order). All wrappers are lru-cached per (mesh, static shape)
and jitted, so new challenges/proofs never retrace.
"""

from __future__ import annotations

import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..field import goldilocks as gf
from ..utils import metrics as _metrics
from ..utils.pallas_util import local_operands

_AXES = ("col", "row")


def mesh_devices(mesh: Mesh) -> int:
    return int(mesh.shape["col"] * mesh.shape["row"])


def mesh_from_shape(shape) -> Mesh:
    """A ('col','row') mesh of the given (ncol, nrow) shape over the first
    ncol*nrow local devices — precompile.enumerate_kernels(mesh_shape=...)
    uses this to enumerate the `_sm` kernel variants for a target mesh
    without one being active (e.g. on the forced-8-device CPU in tier-1)."""
    ncol, nrow = int(shape[0]), int(shape[1])
    devs = jax.devices()
    if len(devs) < ncol * nrow:
        raise ValueError(
            f"mesh shape {shape} needs {ncol * nrow} devices, "
            f"have {len(devs)}"
        )
    grid = np.array(devs[: ncol * nrow]).reshape(ncol, nrow)
    return Mesh(grid, axis_names=_AXES)


# ---------------------------------------------------------------------------
# ICI accounting — the explicit collectives' byte/time bill
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _dcn_frac(mesh: Mesh) -> float:
    """Cross-process fraction of this mesh's crossing bytes (cached per
    mesh; 0.0 on any single-process topology)."""
    from .multihost import dcn_fraction

    return dcn_fraction(mesh)


def _ici_all_to_all(nbytes_global: int, mesh: Mesh):
    """Tally one all-to-all layout pivot: (D-1)/D of the global payload
    crosses the interconnect (each chip keeps its own 1/D slice). On a
    multi-host mesh the crossing bytes split intra-host (ici.*) vs
    cross-process (dcn.*) by the mesh's process topology."""
    D = mesh_devices(mesh)
    crossing = nbytes_global * (D - 1) / max(D, 1)
    f = _dcn_frac(mesh)
    _metrics.count_ici_all_to_all(crossing * (1.0 - f), crossing * f)


def _ici_all_gather(nbytes_global: int, mesh: Mesh):
    """Tally one all-gather to replicated: every chip receives the
    (D-1)/D it does not hold — D*(D-1)/D = (D-1) payloads total. Same
    ici/dcn split as the pivot (the crossing fraction is topology-
    identical for both collective shapes)."""
    D = mesh_devices(mesh)
    crossing = nbytes_global * (D - 1)
    f = _dcn_frac(mesh)
    _metrics.count_ici_all_gather(crossing * (1.0 - f), crossing * f)


class _pivot_timer:
    """Wall-clock window of a pivot-containing dispatch, accumulated into
    the `ici.pivot_s` gauge. This measures the host-side dispatch window
    (the device work is async), which is what the overlapped pipeline can
    actually lose to a pivot; device-side collective time shows up in the
    stage spans as usual."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _metrics.gauge_add("ici.pivot_s", time.perf_counter() - self._t0)
        return False


# ---------------------------------------------------------------------------
# Padding + sharding of column batches
# ---------------------------------------------------------------------------


def padded_cols(B: int, D: int) -> int:
    return -(-B // D) * D


def pad_cols_sharded(arr, mesh: Mesh):
    """Zero-pad a (B, ...) column batch to a multiple of the device count
    and lay it out column-sharded over BOTH mesh axes (each chip holds a
    contiguous stripe of columns — the layout every per-column shard_map
    kernel here consumes)."""
    D = mesh_devices(mesh)
    B = int(arr.shape[0])
    Bp = padded_cols(B, D)
    if Bp != B:
        pad = jnp.zeros((Bp - B,) + tuple(arr.shape[1:]), arr.dtype)
        arr = jnp.concatenate([arr, pad], axis=0)
    spec = P(_AXES, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Commit pipeline: iNTT -> LDE -> all_to_all pivot -> local leaf sponge
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _mono_fn(mesh: Mesh):
    """Per-chip inverse NTT over the local column stripe (values over H ->
    monomials). No communication: columns are independent."""
    from ..ntt import monomial_from_values

    def body(vals):
        # local_operands: the block is per-chip, so the NTT dispatcher may
        # keep its MXU kernel despite the active mesh (same in every
        # shard_map body below)
        with local_operands():
            return monomial_from_values(vals)

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _lde_pivot_leaf_fn(mesh: Mesh, L: int, B_real: int):
    """Rate-L LDE of the local monomial stripe, the explicit col->row
    all_to_all pivot, and the per-chip leaf sponge — one shard_map graph.

    Returns (lde (Bp, L, n) column-sharded, digests (N, 4) row-sharded).
    Padding columns pivot along with the real ones and are sliced off
    BEFORE the sponge (absorption sees exactly the committed columns)."""
    from ..hashes.poseidon2 import leaf_hash_xla
    from ..ntt import lde_from_monomial

    def body(mono_blk):
        b = mono_blk.shape[0]
        with local_operands():
            lde = lde_from_monomial(mono_blk, L)  # (b, L, n) local
        flat = lde.reshape(b, -1)
        # THE layout pivot: split the full domain D ways, concat the
        # column stripes received from every chip — (Bp, N/D) local
        piv = jax.lax.all_to_all(
            flat, _AXES, split_axis=1, concat_axis=0, tiled=True
        )
        leaves = piv.T[:, :B_real]  # (N/D, B): rows of real columns
        return lde, leaf_hash_xla(leaves)

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=(P(_AXES, None, None), P(_AXES, None)),
            check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _lde_pivot_cols_fn(mesh: Mesh, L: int, b_real: int):
    """Streamed-commit block pivot: local LDE of one column block, the
    explicit all_to_all, and the transpose to this chip's row range —
    (N, b_real) row-sharded leaf columns ready for the carried sponge."""
    from ..ntt import lde_from_monomial

    def body(mono_blk):
        b = mono_blk.shape[0]
        with local_operands():
            lde = lde_from_monomial(mono_blk, L)
        flat = lde.reshape(b, -1)
        piv = jax.lax.all_to_all(
            flat, _AXES, split_axis=1, concat_axis=0, tiled=True
        )
        return piv.T[:, :b_real]

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _node_step_fn(mesh: Mesh):
    """One Merkle node layer, per chip: adjacent digest pairs are local as
    long as the local row count is even (the caller guarantees it). The
    `node_hash` dispatcher picks the Pallas sponge on TPU — shard_map
    hands it the LOCAL block, so unlike the GSPMD path the kernel is
    never lost to the partitioner."""
    from ..hashes.poseidon2 import node_hash

    def body(d):
        with local_operands():
            return node_hash(d[0::2], d[1::2])

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _all_gather_fn(mesh: Mesh, ndim: int):
    """Explicit all_gather of a leading-axis-sharded array to replicated
    (caps / small node layers / transcript inputs)."""

    def body(x):
        return jax.lax.all_gather(x, _AXES, axis=0, tiled=True)

    spec_in = P(_AXES, *([None] * (ndim - 1)))
    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(spec_in,),
            out_specs=P(*([None] * ndim)), check_rep=False,
        )
    )


def all_gather_replicated(arr, mesh: Mesh):
    out = _all_gather_fn(mesh, arr.ndim)(arr)
    _ici_all_gather(int(arr.size) * arr.dtype.itemsize, mesh)
    return out


# node counts at or below this finish replicated in one fused graph (the
# same latency-vs-size trade as merkle._FUSE_THRESHOLD)
_SM_GATHER_THRESHOLD = 1 << 12


def node_plan(n_leaves: int, cap_size: int, D: int):
    """(per-chip node-step input sizes, all_gather input size | None) for
    a mesh Merkle tree of `n_leaves` digests: 2-to-1 layers run per chip
    while pairs stay shard-local and the count is worth sharding, the
    remainder gathers and finishes replicated. Shared by node_layers_sm
    and precompile.enumerate_kernels so the enumerated `_sm` set cannot
    drift from the dispatched one."""
    steps = []
    cur = n_leaves
    while (
        cur > cap_size
        and cur > _SM_GATHER_THRESHOLD
        and cur // 2 >= D
        and (cur // D) % 2 == 0
    ):
        steps.append(cur)
        cur //= 2
    return steps, (cur if cur > cap_size else None)


def node_layers_sm(digests, cap_size: int, mesh: Mesh):
    """All Merkle node layers from row-sharded leaf digests: per-chip
    2-to-1 hashing while pairs stay shard-local, then ONE explicit
    all_gather and the fused replicated tail. Layer values (and count)
    are identical to merkle._node_layers."""
    from ..merkle import _tree_tail_layers

    steps, gather = node_plan(
        int(digests.shape[0]), cap_size, mesh_devices(mesh)
    )
    layers = [digests]
    cur = digests
    for _ in steps:
        cur = _node_step_fn(mesh)(cur)
        layers.append(cur)
    if gather is not None:
        rep = all_gather_replicated(cur, mesh)
        layers.extend(_tree_tail_layers(rep, cap_size))
    return tuple(layers)


def commit_from_mono_sm(mono, L: int, cap_size: int, mesh: Mesh):
    """Materialized commit of a (B, n) monomial stack over the mesh:
    shard_map LDE + explicit pivot + per-chip leaf sponge + node layers.
    Returns (lde (B, L, n), layers) — same contract as the meshless
    lde_from_monomial + commit_layers_device pair, bit-identical values."""
    B, n = int(mono.shape[0]), int(mono.shape[-1])
    N = n * L
    mono_p = pad_cols_sharded(mono, mesh)
    fn = _lde_pivot_leaf_fn(mesh, L, B)
    with _pivot_timer():
        lde_p, digests = fn(mono_p)
    _ici_all_to_all(int(mono_p.shape[0]) * N * 8, mesh)
    _metrics.count("merkle.sm_commits")
    lde = lde_p[:B] if lde_p.shape[0] != B else lde_p
    return lde, node_layers_sm(digests, cap_size, mesh)


def streamed_leaf_digests_sm(mono, L: int, mesh: Mesh):
    """Streamed commit over the mesh: each chip absorbs ITS OWN row range
    of every column block into a carried local sponge state. Per block:
    local LDE of the block's column stripe, the explicit all_to_all pivot,
    then streaming._absorb_cols on the row-sharded (N, b) columns (the
    absorb itself needs no communication — the sponge state is row-local).
    Only the final digests leave the chip (node_layers_sm gathers the
    cap). The loop is streaming.double_buffered_absorb, so block b+1's
    LDE + pivot collective are in flight while block b absorbs. Absorb
    order equals the meshless streamed commit exactly, so digests are
    bit-identical."""
    from ..prover.streaming import COL_BLOCK, double_buffered_absorb

    B, n = int(mono.shape[0]), int(mono.shape[-1])
    N = n * L
    state = jax.device_put(
        jnp.zeros((N, 12), jnp.uint64),
        NamedSharding(mesh, P(_AXES, None)),
    )

    def _cols(i):
        b = min(COL_BLOCK, B - i)
        blk_p = pad_cols_sharded(mono[i : i + b], mesh)
        fn = _lde_pivot_cols_fn(mesh, L, b)
        with _pivot_timer():
            cols = fn(blk_p)
        _ici_all_to_all(int(blk_p.shape[0]) * N * 8, mesh)
        _metrics.count("stream.sm_blocks")
        return cols

    state = double_buffered_absorb(state, range(0, B, COL_BLOCK), _cols)
    return state[:, :4]


def commit_pipeline_sm(values, L: int, cap_size: int, stream: bool,
                       mesh: Mesh):
    """The shard_map twin of prover._commit_pipeline: values over H ->
    (mono, lde | None, tree layers)."""
    B = int(values.shape[0])
    vp = pad_cols_sharded(values, mesh)
    mono_p = _mono_fn(mesh)(vp)
    mono = mono_p[:B] if mono_p.shape[0] != B else mono_p
    _metrics.count("ntt.monomial_from_values")
    if stream:
        digests = streamed_leaf_digests_sm(mono, L, mesh)
        _metrics.count("merkle.streamed_commits")
        return mono, None, node_layers_sm(digests, cap_size, mesh)
    lde, layers = commit_from_mono_sm(mono, L, cap_size, mesh)
    _metrics.count("ntt.lde_from_monomial")
    _metrics.count("merkle.commits")
    return mono, lde, layers


# ---------------------------------------------------------------------------
# Round 3: coset evaluation (with pivot) + row-sharded terms sweep
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _coset_eval_fn(mesh: Mesh, B_real: int):
    """Per-coset group evaluation over the mesh: per-chip scale + forward
    NTT of the local column stripe, then the explicit all_to_all pivot to
    row sharding — the layout the terms sweep consumes. Keyed on the real
    column count (the pad is sliced off after the pivot); jit keys the
    rest by shape."""
    from ..ntt.ntt import fft_natural_to_bitreversed

    def body(mono_blk, scale_row):
        with local_operands():
            v = fft_natural_to_bitreversed(
                gf.mul(mono_blk, scale_row[None, :])
            )
        return jax.lax.all_to_all(
            v, _AXES, split_axis=1, concat_axis=0, tiled=True
        )

    smf = shard_map(
        body, mesh=mesh, in_specs=(P(_AXES, None), P(None)),
        out_specs=P(None, _AXES), check_rep=False,
    )

    @jax.jit
    def fn(mono_p, scale_q, c_arr):
        scale_row = jax.lax.dynamic_index_in_dim(
            scale_q, c_arr, 0, keepdims=False
        )
        return smf(mono_p, scale_row)[:B_real]

    return fn


def coset_eval_q_sm(mono_p, scale_q, c_arr, B_real: int, mesh: Mesh):
    """shard_map twin of prover._coset_eval_q; `mono_p` comes from
    pad_cols_sharded (done once per round, not per coset)."""
    fn = _coset_eval_fn(mesh, B_real)
    with _pivot_timer():
        out = fn(mono_p, scale_q, c_arr)
    _ici_all_to_all(int(mono_p.shape[0] * mono_p.shape[-1]) * 8, mesh)
    return out


def sweep_shard_map(core, mesh: Mesh):
    """Wrap a per-coset terms core (limb Pallas kernel or the u64 body —
    both are pointwise across the domain) in shard_map over row-sharded
    oracle evaluations. The xs/L0/1-Z_H coset slices happen OUTSIDE the
    map on the replicated full-rate tables (slice boundaries are coset
    multiples of n, so resharding the slice is communication-free); the
    challenge scalars and alpha/γ-power tables replicate."""
    row = P(None, _AXES)
    vec = P(_AXES)
    rep = P(None)
    smf = shard_map(
        core, mesh=mesh,
        in_specs=(
            row, row, row, row, vec, vec, vec,
            rep, rep, rep, rep, rep, rep,
        ),
        out_specs=(vec, vec), check_rep=False,
    )

    def body(
        wit_v, setup_v, s2_v, zs_v, c_arr,
        xs_q, l0_q, zhinv_q, ap0, ap1, beta01, gamma01, lkb01, lkg01,
    ):
        n = wit_v.shape[-1]
        start = c_arr * n
        xs_sl = jax.lax.dynamic_slice_in_dim(xs_q, start, n)
        l0_sl = jax.lax.dynamic_slice_in_dim(l0_q, start, n)
        zhinv_sl = jax.lax.dynamic_slice_in_dim(zhinv_q, start, n)
        return smf(
            wit_v, setup_v, s2_v, zs_v, xs_sl, l0_sl, zhinv_sl,
            ap0, ap1, beta01, gamma01, lkb01, lkg01,
        )

    return jax.jit(body)


# ---------------------------------------------------------------------------
# Round 5: DEEP codeword per chip (pointwise across the domain)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _deep_fn(mesh: Mesh, nsrc: int, num_zw: int, num_lk: int, num_pi: int):
    """The whole DEEP accumulation — main sum + extra terms — as ONE
    shard_map graph over domain shards. Every term is pointwise across the
    domain (per position: Σ ch_i·(f_i(x) − y_i)/(x − z) plus the z·ω /
    lookup-at-0 / public-input opens), so each chip computes its N/D slice
    with the exact same integer ops as the meshless graph and the BODY
    needs no collective. The (B, N) sources arrive column-sharded from the
    commit pipelines, so the jit boundary re-lays them to the domain
    sharding the in_specs demand — that pivot is charged to the ici.*
    gauges by deep_codeword_sm (it is round 5's dominant ICI payload). This exists for correctness as much as speed: a plain jit over
    mesh-sharded u64 operands goes through XLA's SPMD partitioner, which
    miscompiles this very accumulation (first divergence of the whole
    prove lands on fri_cap_0 — h itself comes out wrong on the
    forced-8-device CPU mesh). shard_map hands the body per-chip blocks,
    so the partitioner never sees it."""
    from ..prover.prover import _deep_extras_fn, _deep_main_sum

    row = P(None, _AXES)
    vec = P(_AXES)
    rep = P(None)

    def body(
        srcs, y0s, y1s, c0s, c1s, inv_xz, inv_xzw,
        cols_zw, cols_lk, inv_x, cols_pi, pi_denoms, pi_vals,
        y_zw, y_lk0, ch0e, ch1e,
    ):
        h = _deep_main_sum(list(srcs), y0s, y1s, c0s, c1s, inv_xz)
        return _deep_extras_fn(num_zw, num_lk, num_pi)(
            h, cols_zw, cols_lk, cols_pi, inv_xzw, inv_x, pi_denoms,
            y_zw, y_lk0, pi_vals, ch0e, ch1e,
        )

    in_specs = (
        (row,) * nsrc, rep, rep, rep, rep, (vec, vec), (vec, vec),
        row, row, vec if num_lk else rep, row, row, rep,
        (rep, rep), (rep, rep), rep, rep,
    )
    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=(vec, vec), check_rep=False,
        )
    )


def deep_codeword_sm(
    mesh: Mesh, deep_sources, y0s, y1s, c0s, c1s, inv_xz, prep,
    y_zw, y_lk0, ch0e, ch1e, num_zw: int, num_lk: int, num_pi: int,
):
    """shard_map twin of the fused round-5 body in prover._prove_impl
    (_deep_main_sum + _deep_extras_fn). `deep_sources` must all be
    materialized (B, N) arrays — the streamed MonomialSource oracles
    regenerate inside plain jits and take the de-meshed fallback in
    prover.py instead. Returns the ext codeword pair row-sharded over
    ('col','row') — exactly the layout the per-chip FRI fold and commit
    graphs consume."""
    fn = _deep_fn(mesh, len(deep_sources), num_zw, num_lk, num_pi)
    _metrics.count("deep.sm_codewords")
    # the sources are column-sharded (commit-pipeline layout); entering
    # the domain-sharded shard_map re-lays them out at the jit boundary —
    # bill that pivot like the explicit ones, it is round 5's dominant
    # interconnect movement
    _ici_all_to_all(
        sum(int(a.size) * a.dtype.itemsize for a in deep_sources), mesh
    )
    s2_cols = prep["s2_cols"]
    with _pivot_timer():
        return fn(
            tuple(deep_sources), y0s, y1s, c0s, c1s,
            inv_xz, prep["inv_xzw"],
            s2_cols[:num_zw], s2_cols[num_zw:], prep["inv_x"],
            prep["cols_pi"], prep["pi_denoms"], prep["pi_vals"],
            y_zw, y_lk0, ch0e, ch1e,
        )


# ---------------------------------------------------------------------------
# FRI fold over row shards (pairs are adjacent in brev layout -> local)
# ---------------------------------------------------------------------------


def fold_shards_ok(size: int, k: int, mesh: Mesh) -> bool:
    """A k-fold chain stays shard-local iff every intermediate local size
    is even: size must be divisible by D·2^k — the same predicate also
    guards the per-chip oracle commit (the 2^k-points-per-leaf regroup
    must land on whole local rows)."""
    return size % (mesh_devices(mesh) << k) == 0


@lru_cache(maxsize=None)
def _fri_leaf_fn(mesh: Mesh, k: int):
    """Per-chip FRI oracle leaf hashing: regroup 2^k brev-consecutive
    domain points (interleaved c0,c1) per leaf and sponge them — the leaf
    subtrees are fully shard-local under fold_shards_ok. The `leaf_hash`
    dispatcher picks the Pallas sponge on TPU over the local block."""
    from ..hashes.poseidon2 import leaf_hash

    def body(c0, c1):
        arr = jnp.stack([c0, c1], axis=-1)
        leaves = arr.reshape(c0.shape[0] >> k, -1)
        with local_operands():
            return leaf_hash(leaves)

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES), P(_AXES)),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


def fri_commit_sm(cur, k: int, cap_size: int, mesh: Mesh):
    """Commit one FRI oracle over the mesh: per-chip leaf sponges over the
    row-sharded codeword, then node_layers_sm (per-chip 2-to-1 layers, one
    cap all_gather). Layer values are identical to merkle._tree_layers."""
    dig = _fri_leaf_fn(mesh, k)(cur[0], cur[1])
    _metrics.count("fri.sm_commits")
    return node_layers_sm(dig, cap_size, mesh)


def _demesh_array(arr, dev):
    """One jax.Array onto a single LOCAL device. Fully-addressable arrays
    move with a plain device_put; a multi-host global array spanning
    non-addressable devices (for which that device_put is illegal) is
    gathered to THIS host first — transfer.to_host rides
    multihost_utils.process_allgather and bills the cross-host bytes to
    the dcn.* gauges — then re-lands on the local device. Every process
    gathers the same global value, so downstream single-device graphs
    stay bit-identical across hosts."""
    if getattr(arr, "is_fully_addressable", True):
        return jax.device_put(arr, dev)
    from ..utils import transfer as _transfer

    return jax.device_put(_transfer.to_host(arr), dev)


def demesh(arr):
    """Pull an array (or ext pair / MonomialSource / plane structures)
    onto one local device — the correctness fallback where a mesh layout
    would send a plain jit through the SPMD partitioner (legacy GSPMD
    round 5, streamed DEEP sources, deep FRI fold tails). Addressable-
    safe: on multi-host meshes non-addressable arrays gather to every
    host (billed as dcn.host_gather_bytes) instead of attempting the
    cross-process device_put that PR 5's single-device pull performed."""
    from ..prover.streaming import MonomialPlanesSource, MonomialSource

    dev = jax.local_devices()[0]
    if isinstance(arr, MonomialSource):
        return MonomialSource(_demesh_array(arr.mono, dev), arr.L)
    if isinstance(arr, MonomialPlanesSource):
        return MonomialPlanesSource(demesh(arr.mono), arr.L)
    if isinstance(arr, tuple):
        return tuple(demesh(a) for a in arr)
    if isinstance(arr, jax.Array):
        return _demesh_array(arr, dev)
    return arr


# ---------------------------------------------------------------------------
# Limb-resident twins (ISSUE 10): the same per-chip kernels + explicit
# collectives over (lo, hi) u32 plane pairs. Each pivot/gather moves two
# u32 planes instead of one u64 array — same total bytes, HALF the
# per-element payload width — and every body computes in the limb domain
# (ntt/limb_ntt.py, poseidon2 plane sponges), so values (digests, caps,
# terms) are bit-identical to the u64 mesh path.
# ---------------------------------------------------------------------------


def pad_cols_sharded_p(p, mesh: Mesh):
    """Plane twin of pad_cols_sharded."""
    return pad_cols_sharded(p[0], mesh), pad_cols_sharded(p[1], mesh)


def _ici_all_to_all_p(nbytes_global_pair: int, mesh: Mesh):
    """Two u32-plane collectives = one logical pivot: bill each plane
    (halved per-element width; the byte total equals the u64 pivot's)."""
    _ici_all_to_all(nbytes_global_pair // 2, mesh)
    _ici_all_to_all(nbytes_global_pair // 2, mesh)


@lru_cache(maxsize=None)
def _mono_fn_p(mesh: Mesh):
    """Per-chip plane inverse NTT over the local column stripe."""
    from ..ntt.limb_ntt import monomial_from_values_p

    def body(vals_p):
        with local_operands():
            return monomial_from_values_p(vals_p)

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


def _pivot_planes(flat_p):
    """The col->row layout pivot on a plane pair: one all_to_all per
    plane (u32 payloads)."""
    return (
        jax.lax.all_to_all(
            flat_p[0], _AXES, split_axis=1, concat_axis=0, tiled=True
        ),
        jax.lax.all_to_all(
            flat_p[1], _AXES, split_axis=1, concat_axis=0, tiled=True
        ),
    )


@lru_cache(maxsize=None)
def _lde_pivot_leaf_fn_p(mesh: Mesh, L: int, B_real: int):
    """Plane twin of _lde_pivot_leaf_fn: per-chip plane LDE, the plane
    pivot, and the per-chip plane leaf sponge (fused kernel on TPU, XLA
    limb rounds elsewhere — hashes/poseidon2.leaf_hash_planes)."""
    from ..hashes.poseidon2 import leaf_hash_planes
    from ..ntt.limb_ntt import lde_from_monomial_p

    def body(mono_p):
        b = mono_p[0].shape[0]
        with local_operands():
            lde = lde_from_monomial_p(mono_p, L)
        flat = (lde[0].reshape(b, -1), lde[1].reshape(b, -1))
        piv = _pivot_planes(flat)
        leaves = (piv[0].T[:, :B_real], piv[1].T[:, :B_real])
        with local_operands():
            dig = leaf_hash_planes(leaves)
        return lde, dig

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=(P(_AXES, None, None), P(_AXES, None)),
            check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _lde_pivot_cols_fn_p(mesh: Mesh, L: int, b_real: int):
    """Plane twin of _lde_pivot_cols_fn (streamed block pivot)."""
    from ..ntt.limb_ntt import lde_from_monomial_p

    def body(mono_p):
        b = mono_p[0].shape[0]
        with local_operands():
            lde = lde_from_monomial_p(mono_p, L)
        piv = _pivot_planes((lde[0].reshape(b, -1), lde[1].reshape(b, -1)))
        return piv[0].T[:, :b_real], piv[1].T[:, :b_real]

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


@lru_cache(maxsize=None)
def _node_step_fn_p(mesh: Mesh):
    """Plane twin of _node_step_fn."""
    from ..hashes.poseidon2 import node_hash_planes

    def body(d_p):
        with local_operands():
            return node_hash_planes(
                (d_p[0][0::2], d_p[1][0::2]), (d_p[0][1::2], d_p[1][1::2])
            )

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES, None),),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


def all_gather_replicated_p(p, mesh: Mesh):
    """Plane twin of all_gather_replicated (two u32 gathers)."""
    out = (
        _all_gather_fn(mesh, p[0].ndim)(p[0]),
        _all_gather_fn(mesh, p[1].ndim)(p[1]),
    )
    _ici_all_gather(int(p[0].size) * p[0].dtype.itemsize, mesh)
    _ici_all_gather(int(p[1].size) * p[1].dtype.itemsize, mesh)
    return out


def node_layers_sm_p(digests_p, cap_size: int, mesh: Mesh):
    """Plane twin of node_layers_sm."""
    from ..merkle import _tree_tail_layers_planes

    steps, gather = node_plan(
        int(digests_p[0].shape[0]), cap_size, mesh_devices(mesh)
    )
    layers = [digests_p]
    cur = digests_p
    for _ in steps:
        cur = _node_step_fn_p(mesh)(cur)
        layers.append(cur)
    if gather is not None:
        rep = all_gather_replicated_p(cur, mesh)
        layers.extend(_tree_tail_layers_planes(rep, cap_size))
    return tuple(layers)


def commit_from_mono_sm_p(mono_p, L: int, cap_size: int, mesh: Mesh):
    """Plane twin of commit_from_mono_sm."""
    B, n = int(mono_p[0].shape[0]), int(mono_p[0].shape[-1])
    N = n * L
    mono_pp = pad_cols_sharded_p(mono_p, mesh)
    fn = _lde_pivot_leaf_fn_p(mesh, L, B)
    with _pivot_timer():
        lde_p, digests = fn(mono_pp)
    _ici_all_to_all_p(int(mono_pp[0].shape[0]) * N * 8, mesh)
    _metrics.count("merkle.sm_commits")
    _metrics.count("merkle.resident_commits")
    if lde_p[0].shape[0] != B:
        lde_p = (lde_p[0][:B], lde_p[1][:B])
    return lde_p, node_layers_sm_p(digests, cap_size, mesh)


def streamed_leaf_digests_sm_p(mono_p, L: int, mesh: Mesh):
    """Plane twin of streamed_leaf_digests_sm: per-chip plane absorb of
    each pivoted block (streaming._absorb_cols_p)."""
    from ..prover.streaming import (
        COL_BLOCK,
        _absorb_cols_p,
        double_buffered_absorb,
    )

    B, n = int(mono_p[0].shape[0]), int(mono_p[0].shape[-1])
    N = n * L
    sh = NamedSharding(mesh, P(_AXES, None))
    state = (
        jax.device_put(jnp.zeros((N, 12), jnp.uint32), sh),
        jax.device_put(jnp.zeros((N, 12), jnp.uint32), sh),
    )

    def _cols(i):
        b = min(COL_BLOCK, B - i)
        blk_p = pad_cols_sharded_p(
            (mono_p[0][i : i + b], mono_p[1][i : i + b]), mesh
        )
        fn = _lde_pivot_cols_fn_p(mesh, L, b)
        with _pivot_timer():
            cols = fn(blk_p)
        _ici_all_to_all_p(int(blk_p[0].shape[0]) * N * 8, mesh)
        _metrics.count("stream.sm_blocks")
        return cols

    state = double_buffered_absorb(
        state, range(0, B, COL_BLOCK), _cols, absorb=_absorb_cols_p
    )
    return state[0][:, :4], state[1][:, :4]


def commit_pipeline_sm_p(values_p, L: int, cap_size: int, stream: bool,
                         mesh: Mesh):
    """Plane twin of commit_pipeline_sm."""
    B = int(values_p[0].shape[0])
    vp = pad_cols_sharded_p(values_p, mesh)
    mono_pp = _mono_fn_p(mesh)(vp)
    if mono_pp[0].shape[0] != B:
        mono_pp = (mono_pp[0][:B], mono_pp[1][:B])
    _metrics.count("ntt.monomial_from_values")
    _metrics.count("ntt.resident_transforms")
    if stream:
        digests = streamed_leaf_digests_sm_p(mono_pp, L, mesh)
        _metrics.count("merkle.streamed_commits")
        _metrics.count("merkle.resident_commits")
        return mono_pp, None, node_layers_sm_p(digests, cap_size, mesh)
    lde, layers = commit_from_mono_sm_p(mono_pp, L, cap_size, mesh)
    _metrics.count("ntt.lde_from_monomial")
    _metrics.count("merkle.commits")
    return mono_pp, lde, layers


@lru_cache(maxsize=None)
def _coset_eval_fn_p(mesh: Mesh, B_real: int):
    """Plane twin of _coset_eval_fn: per-chip plane scale+NTT, plane
    pivot to row sharding."""
    from ..field import limbs
    from ..ntt.limb_ntt import fft_natural_to_bitreversed_p

    def body(mono_p, scale_row_p):
        with local_operands():
            v = fft_natural_to_bitreversed_p(
                limbs.mul(
                    mono_p, (scale_row_p[0][None, :], scale_row_p[1][None, :])
                )
            )
        return (
            jax.lax.all_to_all(
                v[0], _AXES, split_axis=1, concat_axis=0, tiled=True
            ),
            jax.lax.all_to_all(
                v[1], _AXES, split_axis=1, concat_axis=0, tiled=True
            ),
        )

    smf = shard_map(
        body, mesh=mesh, in_specs=(P(_AXES, None), P(None)),
        out_specs=P(None, _AXES), check_rep=False,
    )

    @jax.jit
    def fn(mono_p, scale_q_p, c_arr):
        scale_row = (
            jax.lax.dynamic_index_in_dim(
                scale_q_p[0], c_arr, 0, keepdims=False
            ),
            jax.lax.dynamic_index_in_dim(
                scale_q_p[1], c_arr, 0, keepdims=False
            ),
        )
        out = smf(mono_p, scale_row)
        return out[0][:B_real], out[1][:B_real]

    return fn


def coset_eval_q_sm_p(mono_p, scale_q_p, c_arr, B_real: int, mesh: Mesh):
    """Plane twin of coset_eval_q_sm."""
    fn = _coset_eval_fn_p(mesh, B_real)
    with _pivot_timer():
        out = fn(mono_p, scale_q_p, c_arr)
    _ici_all_to_all_p(int(mono_p[0].shape[0] * mono_p[0].shape[-1]) * 8, mesh)
    return out


def sweep_shard_map_p(core_p, mesh: Mesh):
    """Plane twin of sweep_shard_map: wraps the RESIDENT per-coset terms
    core (plane stacks + host-built scalar table) in shard_map over
    row-sharded plane evaluations."""
    row = P(None, _AXES)
    vec = P(_AXES)
    rep = P(None)
    smf = shard_map(
        core_p, mesh=mesh,
        in_specs=(
            row, row, row, row, vec, vec, vec, rep,
        ),
        out_specs=(vec, vec), check_rep=False,
    )

    def body(
        wit_p, setup_p, s2_p, zs_p, c_arr,
        xs_q_p, l0_q_p, zhinv_q_p, table,
    ):
        n = wit_p[0].shape[-1]
        start = c_arr * n

        def _sl(p):
            return (
                jax.lax.dynamic_slice_in_dim(p[0], start, n),
                jax.lax.dynamic_slice_in_dim(p[1], start, n),
            )

        return smf(
            wit_p, setup_p, s2_p, zs_p,
            _sl(xs_q_p), _sl(l0_q_p), _sl(zhinv_q_p), table,
        )

    return jax.jit(body)


@lru_cache(maxsize=None)
def _deep_fn_p(mesh: Mesh, nsrc: int, num_zw: int, num_lk: int, num_pi: int):
    """Plane twin of _deep_fn: the whole resident DEEP accumulation as
    ONE shard_map graph over domain shards."""
    from ..prover.resident import _deep_extras_fn_p, _deep_main_sum_p

    row = P(None, _AXES)
    vec = P(_AXES)
    rep = P(None)

    def body(
        srcs, y0s, y1s, c0s, c1s, inv_xz, inv_xzw,
        cols_zw, cols_lk, inv_x, cols_pi, pi_denoms, pi_vals,
        y_zw, y_lk0, ch0e, ch1e,
    ):
        h = _deep_main_sum_p(list(srcs), y0s, y1s, c0s, c1s, inv_xz)
        return _deep_extras_fn_p(num_zw, num_lk, num_pi)(
            h, cols_zw, cols_lk, cols_pi, inv_xzw, inv_x, pi_denoms,
            y_zw, y_lk0, pi_vals, ch0e, ch1e,
        )

    in_specs = (
        (row,) * nsrc, rep, rep, rep, rep, (vec, vec), (vec, vec),
        row, row, vec if num_lk else rep, row, row, rep,
        (rep, rep), (rep, rep), rep, rep,
    )
    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=(vec, vec), check_rep=False,
        )
    )


def deep_codeword_sm_p(
    mesh: Mesh, deep_sources, y0s, y1s, c0s, c1s, inv_xz, prep,
    y_zw, y_lk0, ch0e, ch1e, num_zw: int, num_lk: int, num_pi: int,
):
    """Plane twin of deep_codeword_sm; returns the ext codeword PLANE
    pair row-sharded — the layout the resident per-chip FRI graphs
    consume."""
    fn = _deep_fn_p(mesh, len(deep_sources), num_zw, num_lk, num_pi)
    _metrics.count("deep.sm_codewords")
    _ici_all_to_all(
        sum(
            int(a.size) * a.dtype.itemsize
            for pair in deep_sources
            for a in pair
        ),
        mesh,
    )
    s2_cols = prep["s2_cols"]
    cols_zw = (s2_cols[0][:num_zw], s2_cols[1][:num_zw])
    cols_lk = (s2_cols[0][num_zw:], s2_cols[1][num_zw:])
    with _pivot_timer():
        return fn(
            tuple(deep_sources), y0s, y1s, c0s, c1s,
            inv_xz, prep["inv_xzw"],
            cols_zw, cols_lk, prep["inv_x"],
            prep["cols_pi"], prep["pi_denoms"], prep["pi_vals"],
            y_zw, y_lk0, ch0e, ch1e,
        )


@lru_cache(maxsize=None)
def _fri_leaf_fn_p(mesh: Mesh, k: int):
    """Plane twin of _fri_leaf_fn."""
    from ..hashes.poseidon2 import leaf_hash_planes

    def body(c0, c1):
        n_loc = c0[0].shape[0]
        llo = jnp.stack([c0[0], c1[0]], axis=-1).reshape(n_loc >> k, -1)
        lhi = jnp.stack([c0[1], c1[1]], axis=-1).reshape(n_loc >> k, -1)
        with local_operands():
            return leaf_hash_planes((llo, lhi))

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(_AXES), P(_AXES)),
            out_specs=P(_AXES, None), check_rep=False,
        )
    )


def fri_commit_sm_p(cur, k: int, cap_size: int, mesh: Mesh):
    """Plane twin of fri_commit_sm."""
    dig = _fri_leaf_fn_p(mesh, k)(cur[0], cur[1])
    _metrics.count("fri.sm_commits")
    return node_layers_sm_p(dig, cap_size, mesh)
