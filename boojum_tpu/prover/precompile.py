"""Parallel precompilation of the prover's kernel library (ISSUE 1).

A cold process used to pay the compile bill SERIALLY: each fused round
graph compiled at first dispatch, one at a time, minutes each (BASELINE.md
round 4). With the round graphs split into shape-keyed top-level kernels
(prover.py / stages.py / merkle.py / streaming.py / fri.py), the bill
becomes a LIBRARY of small modules that can compile concurrently:

- `enumerate_kernels(assembly, config)` derives every shape-keyed
  executable a fused prove of this (CSGeometry, ProofConfig) will
  dispatch — the commit pipelines for each oracle, the stage-2 chunk
  scan/prefix/stack graphs, the per-coset evaluation + terms sweep, the
  round-4/5 evaluation and DEEP graphs and the FRI schedule — as
  (name, jitted_fn, ShapeDtypeStruct args) specs. No device memory is
  allocated.
- `precompile(...)` lowers the specs serially (tracing is Python/GIL
  work) and runs `.compile()` on a thread pool: the backend compile (XLA
  and Mosaic) releases the GIL, so the host's cores overlap the compiles
  instead of queueing them. Compiled executables land in the persistent
  cache (boojum_tpu/compile_cache.py), which both this process's first
  prove and every later process read back — re-dispatch pays re-tracing
  plus a cache load, never the compile.

Every lower/compile is timed into a `utils.profiling.CompileLedger`;
bench.py and chip_smoke.py emit the ledger so compile-bill regressions
show up in round artifacts.
"""

from __future__ import annotations

import ctypes
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..utils import metrics as _metrics
from ..utils.profiling import CompileLedger, current_compile_ledger
from ..utils.spans import span as _span
from .config import require_poseidon2_tree


def trim_host_heap():
    """Hand the heap's freed pages back to the OS (glibc `malloc_trim`).

    One TPU compile allocates and frees about a gigabyte on its pool
    thread, and glibc keeps the freed pages in that thread's arena: a
    sweep of 57 kernels left 45 GB resident in a process whose live heap
    was under 1 GB, and the first run on a 40 GiB v5e host died of it in
    its second sweep (PR 22; measured compiling for a described v5e:
    5.8 GB resident after six compiles, 0.9 GB after the trim). Called
    after every pool compile. A libc without `malloc_trim` is left alone."""
    try:
        trim = ctypes.CDLL(None).malloc_trim
    except (OSError, AttributeError):
        return
    trim(0)


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint64)


def _sdsp(*shape):
    """A (lo, hi) u32 plane-pair ShapeDtypeStruct (the limb-resident
    kernel set's argument unit, ISSUE 10)."""
    s = jax.ShapeDtypeStruct(shape, jnp.uint32)
    return (s, s)


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _i32():
    return jax.ShapeDtypeStruct((), jnp.int32)


@dataclass
class KernelSpec:
    name: str
    fn: object  # a jitted callable supporting .lower(*args)
    args: tuple


def _next_pow2(x: int) -> int:
    c = 1
    while c < max(x, 1):
        c *= 2
    return c


def _round3_eval_plan(sds, groups, N: int, L: int, Q: int, smm):
    """What round 3 dispatches for its committed groups, by the prover's
    own rule (prover.coset_is_committed) on a stand-in for what the prove
    will hold of each commitment: `groups` is (tag, B, streamed) and `sds`
    makes the (B, N) shape struct of a materialized storage; a streamed
    commit holds none, and the shard_map proves transform every group
    (prover._prove_impl). Returns (picked, transformed): the storages
    `_coset_eval_pick` reads together on the first min(L, Q) cosets, and
    the (tag, B) that still run a transform on some coset (all of them
    where Q > L, plus the shifted z)."""
    from .prover import coset_is_committed

    held = {
        t: None if (streamed or smm is not None) else sds(B, N)
        for t, B, streamed in groups
    }
    picked = tuple(
        o for o in held.values() if coset_is_committed(0, L, Q, o)
    )
    transformed = [
        (t, B) for t, B, _st in groups
        if not all(coset_is_committed(c, L, Q, held[t]) for c in range(Q))
    ]
    return picked, transformed + [("zs", 2)]


def _streamed_single_columns(assembly, S, B_wit, num_partials, num_lk):
    """(tag, oracle columns, column indices) of the single columns round 5
    regenerates from a streamed oracle's monomials (`_deep_round5_prep`):
    stage 2's z pair and lookup sums, the witness's public-input columns."""
    ab_off = 2 + 2 * num_partials
    s2_idxs = (0, 1) + tuple(ab_off + j for j in range(2 * num_lk))
    pi_idxs = tuple(c_ for (c_, _r, _v) in assembly.public_inputs)
    return [
        (tag, B, idxs)
        for tag, B, idxs in (("s2", S, s2_idxs), ("pi", B_wit, pi_idxs))
        if idxs
    ]


def enumerate_kernels(assembly, config, mesh_shape=None) -> list[KernelSpec]:
    """The shape-keyed kernel library for a fused prove of `assembly`
    under `config` — meshless, or per-chip shard_map when a shard_map
    mesh is active (parallel/shard_sweep.py) or `mesh_shape` names one.

    `mesh_shape`: a ('col','row') device-count pair like (2, 4), or an
    already-built Mesh — enumerates the `_sm` kernel variants (per-chip
    iNTT/LDE + pivot + leaf sponge, coset_sweep_terms[_limbres]_sm,
    fri_fold[_limbres]_k*_sm) for that mesh without one being active.
    Only the variant a prove on that mesh will dispatch
    (utils/pallas_util.resolve_variant) is enumerated, so the compile
    ledger records exactly the dispatched set.

    Derivations mirror prover._prove_impl / setup.generate_setup; only
    circuit STRUCTURE is read (placements, gates, geometry, lookup
    params) — the witness values and the setup's sigma columns are never
    touched, so this runs before generate_setup. Deliberately skipped
    (cheap, query-dependent shapes): the fused query gather, the
    replicated Merkle tail after the cap all_gather, and the PoW grind
    (host-side). A streamed prove's leaf-value gathers and single-column
    opens are listed: each holds a forward transform."""
    from ..merkle import tree_hasher
    from ..field import extension as ext_f
    from ..ntt.ntt import _ext_powers_jit, ntt_kernel_specs
    from .fri import fri_kernel_specs
    from .setup import build_selector_tree, non_residues_for_copy_permutation
    from .shape_key import shape_bucket
    from .stages import (
        _all_chunk_num_den,
        _lookup_denominators,
        _z_and_partials,
        num_gate_sweep_terms,
    )
    from .streaming import (
        COL_BLOCK,
        _absorb_cols,
        _lde_block_cols,
        use_streamed_lde,
    )
    from . import prover as P
    from ..parallel import shard_sweep as SS
    from ..parallel.sharding import active_mesh
    from ..utils import transfer as _transfer
    from ..utils.pallas_util import resolve_variant

    if mesh_shape is None:
        mesh = active_mesh()
    elif isinstance(mesh_shape, (tuple, list)):
        mesh = SS.mesh_from_shape(mesh_shape)
    else:
        mesh = mesh_shape  # an already-built Mesh
    # the same record a prove on `mesh` resolves (it also keys
    # prover/aot.py's bundles): three DISJOINT kernel sets — the
    # plane-free `_bb` set of the BabyBear field (prover/bb_kernels.py),
    # the plane set (`*_limbres` ledger names) and the u64 set below
    variant = resolve_variant(mesh)
    smm = mesh if variant.mesh == "shard_map" else None
    D = SS.mesh_devices(smm) if smm is not None else 1
    # the key's tree hasher: its leaf, node and FRI-oracle programs are
    # listed when, and only when, the configuration names it
    hasher = tree_hasher(getattr(config, "tree_hasher", "poseidon2"))
    htag = hasher.tag
    if mesh is not None:
        require_poseidon2_tree(hasher.name, "under a mesh")
    if variant.field == "babybear":
        require_poseidon2_tree(hasher.name, "in the BabyBear prover")
        return _enumerate_babybear(assembly, config)
    if variant.planes:
        return _enumerate_resident(assembly, config, smm, D, hasher)

    # ONE derivation of every shape-keyed quantity, shared with the
    # service admission queue and the compile-ledger tags (shape_key.py)
    sb = shape_bucket(assembly, config)
    n = sb.trace_len
    log_n = sb.log_n
    L = sb.lde_factor
    N = sb.domain_len
    cap = sb.cap_size
    Cg, LC, Ct, W = sb.num_copy_cols, sb.num_lookup_cols, sb.Ct, sb.num_wit_cols
    lookups = sb.lookups
    lk_mode = assembly.lookup_mode
    R_args = sb.lookup_subargs
    M, K, TW, width = sb.M, sb.num_constant_cols, sb.TW, sb.lookup_width

    chunks = list(sb.chunks)
    num_chunks = sb.num_chunks
    num_partials = num_chunks - 1
    S, B_wit, B_setup = sb.S, sb.B_wit, sb.B_setup

    # selector paths are structure, not shape — still derived here, exactly
    # as generate_setup derives them (shape_key resolves Q the same way)
    _tree, selector_paths = build_selector_tree(assembly.gates)
    Q = sb.quotient_degree
    B_q = sb.B_q
    B_all = sb.B_all
    non_residues = non_residues_for_copy_permutation(Ct)

    total_cols = B_all
    stream = use_streamed_lde(total_cols, N)
    stream_setup = use_streamed_lde(B_setup, N)
    if stream or stream_setup:
        require_poseidon2_tree(hasher.name, "on a streamed commit")

    specs: list[KernelSpec] = []

    def add(name, fn, *args):
        specs.append(KernelSpec(name, fn, args))

    # ---- commit pipelines (witness / stage-2 / quotient / setup) ---------
    absorb_blocks: set[int] = set()

    def commit_specs(tag, B, streamed, mono=True):
        if smm is not None:
            return commit_specs_sm(tag, B, streamed, mono)
        for nm, fn, args in ntt_kernel_specs(
            B, log_n, None if streamed else L, mono=mono
        ):
            add(f"{tag}:{nm}", fn, *args)
        if streamed:
            for i in range(0, B, COL_BLOCK):
                absorb_blocks.add(min(COL_BLOCK, B - i))
        else:
            add(
                f"{tag}:leaf_digests{htag}", hasher.leaf_digests_device,
                _sds(B, L, n),
            )

    def commit_specs_sm(tag, B, streamed, mono=True):
        # the per-chip pipeline (shard_sweep.commit_pipeline_sm): local
        # iNTT of the column stripe, then — materialized — the fused
        # LDE + all_to_all pivot + leaf-sponge graph, or — streamed —
        # the per-block LDE+pivot feeding the carried local sponge
        Bp = SS.padded_cols(B, D)
        if mono:
            add(f"{tag}:mono_sm", SS._mono_fn(smm), _sds(Bp, n))
        if streamed:
            # block widths only — the per-width lde_pivot_cols spec is
            # added ONCE per width in the shared absorb_blocks loop below
            # (oracles share block shapes, and each lower() is a full
            # retrace: duplicate specs would re-pay the trace bill)
            for i in range(0, B, COL_BLOCK):
                absorb_blocks.add(min(COL_BLOCK, B - i))
        else:
            add(
                f"{tag}:lde_pivot_leaf_sm",
                SS._lde_pivot_leaf_fn(smm, L, B), _sds(Bp, n),
            )

    commit_specs("wit", B_wit, stream)
    commit_specs("s2", S, stream)
    # the quotient's monomials come from _quotient_interp rather than
    # monomial_from_values — no imono kernel; it streams with the prove's
    # other commits (the shard_map tail always materializes it)
    stream_q = stream and smm is None
    commit_specs("q", B_q, stream_q, mono=False)
    commit_specs("setup", B_setup, stream_setup)
    # streamed commits are double-buffered: the block LDE (on the mesh
    # with its pivot) and the absorb are separate dispatches
    for b in sorted(absorb_blocks):
        if smm is not None:
            add(
                f"lde_pivot_cols_b{b}_sm",
                SS._lde_pivot_cols_fn(smm, L, b),
                _sds(SS.padded_cols(b, D), n),
            )
        else:
            add(f"lde_block_cols_b{b}", _lde_block_cols, _sds(b, n), L)
        add(f"absorb_cols_b{b}", _absorb_cols, _sds(N, 12), _sds(N, b))
    if smm is None:
        add(f"node_layers{htag}", hasher.node_layers_device, _sds(N, 4), cap)
    else:
        # per-chip node layers while digest pairs stay shard-local
        # (shard_sweep.node_layers_sm; the replicated tail past the
        # all_gather is cheap and compiles at dispatch)
        steps, gather = SS.node_plan(N, cap, D)
        for cur in steps:
            add("node_step_sm", SS._node_step_fn(smm), _sds(cur, 4))
        if gather is not None:
            add(
                "node_gather_sm", SS._all_gather_fn(smm, 2), _sds(gather, 4)
            )

    # the chunked witness upload's on-device concatenate
    wit_groups = [Cg] + ([LC] if LC else []) + ([W] if W else []) \
        + ([1] if M else [])
    upload_parts = _transfer.upload_chunk_shapes(wit_groups, n)
    if len(upload_parts) > 1:
        add(
            "witness_upload_concat", _transfer._concat_jit(),
            *[_sds(b, n) for b in upload_parts],
        )

    # ---- round 2: chunk products, inversions, prefix product, stack ------
    sc = (_sds(), _sds())
    chunks_t = tuple(tuple(c) for c in chunks)
    add(
        "chunk_num_den", _all_chunk_num_den,
        _sds(Ct, n), _sds(Ct, n), _sds(Ct), _sds(n), sc, sc, chunks_t,
    )
    pair = lambda *shape: (_sds(*shape), _sds(*shape))  # noqa: E731
    add("ext_binv_chunks", ext_f.batch_inverse, pair(num_chunks, n))
    if lookups:
        lk_cols = _sds(LC, n) if lk_mode == "specialized" else _sds(Cg, n)
        add(
            "lookup_denominators", _lookup_denominators,
            lk_cols, _sds(n), _sds(width + 1, n), sc, sc, R_args, width,
        )
        add("ext_binv_lookup", ext_f.batch_inverse, pair(R_args + 1, n))
    add("z_and_partials", _z_and_partials, pair(num_chunks, n),
        pair(num_chunks, n))
    stack_fn = P._stage2_stack_fn(assembly, selector_paths)
    lk_inv = pair(R_args + 1, n) if lookups else None
    mult = _sds(n) if lookups else None
    consts = _sds(K, n) if (lookups and lk_mode == "general") else None
    add("stage2_stack", stack_fn, pair(n), pair(num_partials, n),
        lk_inv, mult, consts)

    # ---- round 3: per-coset evaluations + terms sweep + quotient tail ----
    total_alpha_terms = (
        num_gate_sweep_terms(assembly)
        + 1 + num_chunks
        + ((R_args + 1) if lookups else 0)
    )
    capA = _next_pow2(total_alpha_terms)
    add("zshift", P._zshift_fused, _sds(2, n), _sds())
    picked, transformed = _round3_eval_plan(
        _sds,
        (("wit", B_wit, stream), ("setup", B_setup, stream_setup),
         ("s2", S, stream)),
        N, L, Q, smm,
    )
    if picked:
        add("coset_eval_pick", P._coset_eval_pick, picked, _i32(), n)
    for tag, B in transformed:
        if smm is None:
            add(f"coset_eval_{tag}", P._coset_eval_q,
                _sds(B, n), _sds(Q, n), _i32())
        else:
            add(f"coset_eval_{tag}_sm", SS._coset_eval_fn(smm, B),
                _sds(SS.padded_cols(B, D), n), _sds(Q, n), _i32())
    mk_path = None
    if lookups and lk_mode == "general":
        mk_path = selector_paths[assembly.lookup_marker_gid()]
    lk_ctx = (
        lookups, lk_mode, R_args, width, num_partials, chunks_t,
        total_alpha_terms, Cg, Ct, W, K, M,
        tuple(mk_path) if mk_path is not None else None,
    )
    sweep = P._coset_sweep_fn(
        assembly, selector_paths, non_residues, lk_ctx, False, smm
    )
    add(
        "coset_sweep_terms" + ("_sm" if smm is not None else ""), sweep,
        _sds(B_wit, n), _sds(B_setup, n), _sds(S, n), _sds(2, n), _i32(),
        _sds(Q * n), _sds(Q * n), _sds(Q * n), _sds(capA), _sds(capA),
        _sds(2), _sds(2), _sds(2), _sds(2),
    )
    add(
        "quotient_interp", P._quotient_interp,
        tuple(_sds(n) for _ in range(Q)), tuple(_sds(n) for _ in range(Q)),
        Q, n,
    )

    # ---- rounds 4-5: openings, DEEP, FRI ---------------------------------
    num_lk = (R_args + 1) if lookups else 0
    num_pi = len(assembly.public_inputs)
    add("alpha_powers", _ext_powers_jit, _sds(2), capA)
    capD = _next_pow2(B_all + 2 + num_lk + num_pi)
    add("deep_powers", _ext_powers_jit, _sds(2), capD)
    add("evals_fused", P._evals_fused, _sds(B_all, n), _sds(S, n),
        _sds(2), _sds(2))
    add("deep_denoms", P._deep_denoms_fused, _sds(N), _sds(2), _sds(2))
    add("ext_binv_deep", ext_f.batch_inverse, pair(2, N))
    deep_blocks: set[int] = set()
    from ..ntt.ntt import chunk_shapes

    # the setup oracle streams in the DEEP phase iff it was COMMITTED
    # streamed (prover follows setup.setup_lde, decided per-setup by
    # generate_setup), independently of the prove-wide stream flag
    per = max(1, P._DEEP_BLOCK_BUDGET // (N * 8))
    for B, streamed_src in (
        (B_wit, stream), (B_setup, stream_setup), (S, stream),
        (B_q, stream_q),
    ):
        if streamed_src:
            for i in range(0, B, COL_BLOCK):
                b32 = min(COL_BLOCK, B - i)
                deep_blocks.add(b32)
                # streamed DEEP blocks regenerate their rate-L values
                for nm, fn, args in ntt_kernel_specs(
                    b32, log_n, L, mono=False
                ):
                    add(f"deep_regen:{nm}", fn, *args)
            # the query phase's leaf values, one program an oracle
            add(
                f"stream_gather_b{B}", P._stream_gather_fused, _sds(B, n),
                jax.ShapeDtypeStruct((config.num_queries,), jnp.int64), L,
            )
        else:
            for i in range(0, B, per):
                deep_blocks.add(min(per, B - i))
    if stream:
        for tag, B, idxs in _streamed_single_columns(
            assembly, S, B_wit, num_partials, num_lk
        ):
            add(f"deep_cols_{tag}", P._cols_from_mono, _sds(B, n), idxs, L)
    if smm is not None and not (stream or stream_setup):
        # the sm round 5: ONE shard_map graph for main sum + extras
        # (shard_sweep.deep_codeword_sm) — the per-block meshless deep
        # graphs are never dispatched
        capE = 2 + num_lk + num_pi
        add(
            "deep_codeword_sm", SS._deep_fn(smm, 4, 2, num_lk, num_pi),
            (_sds(B_wit, N), _sds(B_setup, N), _sds(S, N), _sds(B_q, N)),
            _sds(B_all), _sds(B_all), _sds(B_all), _sds(B_all),
            pair(N), pair(N), _sds(2, N), _sds(2 * num_lk, N),
            _sds(N) if lookups else _sds(1), _sds(num_pi, N),
            _sds(num_pi, N), _sds(num_pi), pair(2), pair(num_lk),
            _sds(capE), _sds(capE),
        )
    else:
        for b in sorted(deep_blocks):
            add(
                f"deep_block_b{b}", P._deep_block,
                _sds(b, N), _sds(b), _sds(b),
            )
        add("deep_combine", P._deep_combine, _sds(N), _sds(N),
            _sds(B_all), _sds(B_all), _sds(B_all), _sds(B_all), pair(N))
        extras = P._deep_extras_fn(2, num_lk, num_pi)
        add(
            "deep_extras", extras,
            pair(N), _sds(2, N), _sds(2 * num_lk, N), _sds(num_pi, N),
            pair(N), _sds(N) if lookups else _sds(1), _sds(num_pi, N),
            pair(2), pair(num_lk), _sds(num_pi), _sds(2 + num_lk + num_pi),
            _sds(2 + num_lk + num_pi),
        )
    for nm, fn, args in fri_kernel_specs(n, config, False, smm, hasher):
        add(nm, fn, *args)

    # ---- cached domain tables (built once per geometry, but their batch
    # inversions are real compiles on a cold cache) ------------------------
    from ..field import goldilocks as gf
    from .fri import fold_schedule

    add("gf_binv_domain", gf.batch_inverse_xla, _sds(N))
    num_folds = sum(
        fold_schedule(
            n, config.fri_final_degree,
            getattr(config, "fri_folding_schedule", None),
        )
    )
    log_full = N.bit_length() - 1
    for r in range(num_folds):
        add(
            f"gf_binv_fold_r{r}", gf.batch_inverse_xla,
            _sds(1 << (log_full - r - 1)),
        )
    if num_pi:
        add("gf_binv_pi", gf.batch_inverse_xla, _sds(num_pi, N))

    # dedupe identical (fn, args) pairs surfaced under several tags — one
    # executable serves them all, compiling it twice is pure waste
    seen = set()
    out = []
    for s in specs:
        key = (id(s.fn), repr(s.args))
        if key in seen:
            continue
        seen.add(key)
        out.append(s)
    return out


def _enumerate_babybear(assembly, config) -> list[KernelSpec]:
    """The BabyBear plane-free kernel library (enumerate_kernels' `_bb`
    twin, ISSUE 19): every top-level executable the self-contained
    BabyBear prover leg (prover/bb_prover.py) dispatches at this shape
    bucket's domain — single u32-lane args throughout, no (lo, hi)
    plane pairs anywhere in the set."""
    from .bb_kernels import bb_kernel_specs
    from .shape_key import shape_bucket

    sb = shape_bucket(assembly, config)
    specs = [
        KernelSpec(name, fn, args)
        for name, fn, args in bb_kernel_specs(
            sb.log_n, sb.lde_factor, sb.cap_size
        )
    ]
    specs += _enumerate_babybear_full(sb)
    return specs


def _enumerate_babybear_full(sb) -> list[KernelSpec]:
    """The FULL BabyBear prover's assembly-independent executables
    (ISSUE 20, prover/prover_bb.py): batched u32 iNTT/LDE at the
    bucket's oracle widths, paired-leaf commits at every oracle's
    (2B, N/2) stack, and the factor-2 FRI fold chain. The fused gate
    sweep jit is assembly-shaped (gate evaluators are baked into the
    graph) and warms on first prove instead."""
    import jax
    import jax.numpy as jnp

    from ..ntt.bb_ntt import lde_from_monomial_bb, monomial_from_values_bb
    from .bb_kernels import leaf_digests_bb, node_layers_bb, fri_fold_bb

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32)

    n, L, cap = sb.trace_len, sb.lde_factor, sb.cap_size
    log_n = sb.log_n
    N = n * L
    half = N // 2
    Q = sb.quotient_degree
    shift = 31
    specs: list[KernelSpec] = []

    def add(name, fn, *args):
        specs.append(KernelSpec(name, fn, args))

    zs_rows = 4  # the z poly's base columns (omega-shifted monomials)
    oracle_widths = sorted(
        {sb.B_wit, sb.S, sb.B_q, zs_rows, 4}  # 4 = DEEP/FRI codeword
    )
    for B in oracle_widths:
        if B <= 0:
            continue
        add(f"imono_bb_n{n}x{B}", monomial_from_values_bb,
            u32(B, n), log_n)
        add(f"lde_bb_L{L}_n{n}x{B}", lde_from_monomial_bb,
            u32(B, n), log_n, L, shift)
        add(f"leaf_digests_bb_n{half}x{2 * B}", leaf_digests_bb,
            u32(2 * B, half))
    add(f"node_layers_bb_n{half}", node_layers_bb, u32(half, 8),
        min(cap, half))
    # rate-Q sweep-domain evals of every committed oracle group
    for B in sorted({sb.B_wit, sb.B_setup, sb.S, zs_rows}):
        if B > 0:
            add(f"lde_bb_Q{Q}_n{n}x{B}", lde_from_monomial_bb,
                u32(B, n), log_n, Q, shift)
    # quotient interpolation over the rate-Q accumulator
    add(f"imono_bb_n{Q * n}x4", monomial_from_values_bb,
        u32(4, Q * n), (Q * n).bit_length() - 1)
    return specs


def _enumerate_resident(assembly, config, smm, D, hasher) -> list[KernelSpec]:
    """The limb-RESIDENT kernel library (enumerate_kernels' plane twin):
    every executable a resident prove dispatches, with `_limbres`-tagged
    ledger names and (lo, hi) u32 plane-pair argument specs. Mirrors the
    derivations of prover._prove_impl's resident branches exactly."""
    from ..field import limb_ops as lop
    from ..ntt.limb_ntt import plane_ntt_kernel_specs
    from .fri import fri_kernel_specs
    from .setup import build_selector_tree, non_residues_for_copy_permutation
    from .shape_key import shape_bucket
    from .streaming import (
        COL_BLOCK,
        _absorb_cols_cm_p,
        _absorb_cols_digests_p,
        _absorb_cols_p,
        block_chunk_sizes,
        use_streamed_lde,
    )
    from . import prover as P
    from . import resident as RES
    from ..parallel import shard_sweep as SS
    from ..utils import transfer as _transfer

    htag = hasher.tag
    sb = shape_bucket(assembly, config)
    n, log_n, L, N, cap = (
        sb.trace_len, sb.log_n, sb.lde_factor, sb.domain_len, sb.cap_size
    )
    Cg, LC, Ct, W = sb.num_copy_cols, sb.num_lookup_cols, sb.Ct, sb.num_wit_cols
    lookups = sb.lookups
    lk_mode = assembly.lookup_mode
    R_args = sb.lookup_subargs
    M, K, TW, width = sb.M, sb.num_constant_cols, sb.TW, sb.lookup_width
    chunks = list(sb.chunks)
    num_chunks = sb.num_chunks
    num_partials = num_chunks - 1
    S, B_wit, B_setup = sb.S, sb.B_wit, sb.B_setup
    _tree, selector_paths = build_selector_tree(assembly.gates)
    Q = sb.quotient_degree
    B_q = sb.B_q
    B_all = sb.B_all
    non_residues = non_residues_for_copy_permutation(Ct)
    stream = use_streamed_lde(B_all, N)
    stream_setup = use_streamed_lde(B_setup, N)
    if stream or stream_setup:
        require_poseidon2_tree(hasher.name, "on a streamed commit")

    specs: list[KernelSpec] = []

    def add(name, fn, *args):
        specs.append(KernelSpec(name, fn, args))

    # ---- commit pipelines (plane NTT + plane sponges) --------------------
    absorb_blocks: set[int] = set()

    def commit_specs(tag, B, streamed, mono=True):
        if smm is not None:
            Bp = SS.padded_cols(B, D)
            if mono:
                add(
                    f"{tag}:mono_limbres_sm", SS._mono_fn_p(smm),
                    _sdsp(Bp, n),
                )
            if streamed:
                for i in range(0, B, COL_BLOCK):
                    absorb_blocks.add(min(COL_BLOCK, B - i))
            else:
                add(
                    f"{tag}:lde_pivot_leaf_limbres_sm",
                    SS._lde_pivot_leaf_fn_p(smm, L, B), _sdsp(Bp, n),
                )
            return
        for nm, fn, args in plane_ntt_kernel_specs(
            B, log_n, None if streamed else L, mono=mono
        ):
            add(f"{tag}:{nm}", fn, *args)
        if streamed:
            for i in range(0, B, COL_BLOCK):
                absorb_blocks.add(min(COL_BLOCK, B - i))
            # the block transforms, DEEP's regenerations and the query
            # gather (resident.stream_kernel_specs: oracles share blocks)
            for nm, fn, args in RES.stream_kernel_specs(
                B, n, L, config.num_queries
            ):
                add(nm, fn, *args)
        else:
            add(
                f"{tag}:leaf_digests{htag}_limbres",
                hasher.leaf_digests_planes, _sdsp(B, L, n),
            )

    # the quotient streams with the prove's other commits (the shard_map
    # tail, shard_sweep.commit_from_mono_sm_p, always materializes it)
    stream_q = stream and smm is None
    commit_specs("wit", B_wit, stream)
    commit_specs("s2", S, stream)
    commit_specs("q", B_q, stream_q, mono=False)
    # the setup's monomials are generate_setup's, on the u64 path: nothing
    # hands them to `monomial_from_values_p` (a Mosaic compile a chunk shape)
    commit_specs("setup", B_setup, stream_setup, mono=False)
    for b in sorted(absorb_blocks):
        if smm is not None:
            add(
                f"lde_pivot_cols_limbres_b{b}_sm",
                SS._lde_pivot_cols_fn_p(smm, L, b),
                _sdsp(SS.padded_cols(b, D), n),
            )
            add(
                f"absorb_cols_limbres_b{b}", _absorb_cols_p,
                _sdsp(N, 12), _sdsp(N, b),
            )
        else:  # column-major: leaves along the lanes (streaming.py)
            add(
                f"absorb_cols_limbres_b{b}", _absorb_cols_cm_p,
                _sdsp(12, N), _sdsp(b, N),
            )
    if absorb_blocks and smm is None:
        add("absorb_cols_digests_limbres", _absorb_cols_digests_p, _sdsp(12, N))
    if smm is None:
        add(
            f"node_layers{htag}_limbres", hasher.node_layers_planes,
            _sdsp(N, 4), cap,
        )
    else:
        steps, gather = SS.node_plan(N, cap, D)
        for cur in steps:
            add("node_step_limbres_sm", SS._node_step_fn_p(smm), _sdsp(cur, 4))
        if gather is not None:
            add(
                "node_gather_limbres_sm", SS._all_gather_fn(smm, 2),
                _u32(gather, 4),
            )
    wit_groups = [Cg] + ([LC] if LC else []) + ([W] if W else []) \
        + ([1] if M else [])
    upload_parts = _transfer.upload_chunk_shapes(wit_groups, n)
    if len(upload_parts) > 1:
        add(
            "witness_upload_concat_limbres", _transfer._concat_jit(),
            *[_u32(b, n) for b in upload_parts],
        )

    # ---- round 2 plane twins ---------------------------------------------
    chunks_t = tuple(tuple(c) for c in chunks)
    bg8 = _u32(8)
    pairp = lambda *shape: (_sdsp(*shape), _sdsp(*shape))  # noqa: E731
    add(
        "chunk_num_den_limbres", RES._all_chunk_num_den_p,
        _sdsp(Ct, n), _sdsp(Ct, n), _sdsp(Ct), (_sdsp(n), bg8), chunks_t,
    )
    add(
        "ext_binv_chunks_limbres", lop.ext_batch_inverse_jit,
        pairp(num_chunks, n),
    )
    if lookups:
        lk_cols = _sdsp(LC, n) if lk_mode == "specialized" else _sdsp(Cg, n)
        add(
            "lookup_denominators_limbres", RES._lookup_denominators_p,
            lk_cols, (_sdsp(n), _sdsp(width + 1, n)), bg8, R_args, width,
        )
        add(
            "ext_binv_lookup_limbres", RES._lookup_denominators_inv_p,
            pairp(R_args + 1, n),
        )
    add(
        "z_and_partials_limbres", RES._z_and_partials_p,
        pairp(num_chunks, n), pairp(num_chunks, n),
    )
    stack_fn = RES.stage2_stack_fn_p(assembly, selector_paths)
    lk_inv = pairp(R_args + 1, n) if lookups else None
    mult = _sdsp(n) if lookups else None
    consts = _sdsp(K, n) if (lookups and lk_mode == "general") else None
    add(
        "stage2_stack_limbres", stack_fn, pairp(n), pairp(num_partials, n),
        lk_inv, mult, consts,
    )

    # ---- round 3: plane evals + resident sweep + interp ------------------
    from .stages import num_gate_sweep_terms

    total_alpha_terms = (
        num_gate_sweep_terms(assembly)
        + 1 + num_chunks
        + ((R_args + 1) if lookups else 0)
    )
    capA = _next_pow2(total_alpha_terms)
    add("zshift_limbres", RES._zshift_p, _sdsp(2, n), _sdsp(n))
    picked, transformed = _round3_eval_plan(
        _sdsp,
        (("wit", B_wit, stream), ("setup", B_setup, stream_setup),
         ("s2", S, stream)),
        N, L, Q, smm,
    )
    if picked:
        add("coset_eval_pick_limbres", P._coset_eval_pick, picked, _i32(), n)
    for tag, B in transformed:
        if smm is None:
            for nm, fn, args in RES.coset_eval_kernel_specs(tag, B, n, Q):
                add(nm, fn, *args)
        else:
            add(
                f"coset_eval_{tag}_limbres_sm", SS._coset_eval_fn_p(smm, B),
                _sdsp(SS.padded_cols(B, D), n), _sdsp(Q, n), _i32(),
            )
    mk_path = None
    if lookups and lk_mode == "general":
        mk_path = selector_paths[assembly.lookup_marker_gid()]
    lk_ctx = (
        lookups, lk_mode, R_args, width, num_partials, chunks_t,
        total_alpha_terms, Cg, Ct, W, K, M,
        tuple(mk_path) if mk_path is not None else None,
    )
    sweep = P._coset_sweep_fn(
        assembly, selector_paths, non_residues, lk_ctx, True, smm
    )
    S_cols = capA + 4 + ((width + 2) if lookups else 0)
    add(
        "coset_sweep_terms_limbres" + ("_sm" if smm is not None else ""),
        sweep,
        _sdsp(B_wit, n), _sdsp(B_setup, n), _sdsp(S, n), _sdsp(2, n),
        _i32(), _sdsp(Q * n), _sdsp(Q * n), _sdsp(Q * n), _u32(4, S_cols),
    )
    for nm, fn, args in RES.quotient_interp_kernel_specs(Q, n):
        add(nm, fn, *args)

    # ---- rounds 4-5 plane twins ------------------------------------------
    num_lk = (R_args + 1) if lookups else 0
    num_pi = len(assembly.public_inputs)
    sc4 = _u32(4)
    add(
        "evals_limbres", RES._evals_p, _sdsp(B_all, n), _sdsp(S, n),
        sc4, sc4,
    )
    add("deep_denoms_limbres", RES._deep_denoms_p, _sdsp(N), sc4, sc4)
    add("ext_binv_deep_limbres", lop.ext_batch_inverse_jit, pairp(2, N))
    deep_blocks: set[int] = set()
    per = max(1, RES._DEEP_BLOCK_BUDGET // (N * 8))
    for B, streamed_src in (
        (B_wit, stream), (B_setup, stream_setup), (S, stream),
        (B_q, stream_q),
    ):
        if streamed_src:
            # regenerated a block at a time (its transforms are listed
            # with the oracle's commit above; a shard_map prove streams
            # its commits per chip and regenerates here off the mesh)
            deep_blocks.update(block_chunk_sizes(B, n, L))
            if smm is not None:
                for nm, fn, args in RES.stream_kernel_specs(
                    B, n, L, config.num_queries
                ):
                    add(nm, fn, *args)
        else:
            for i in range(0, B, per):
                deep_blocks.add(min(per, B - i))
    if stream:
        for tag, B, idxs in _streamed_single_columns(
            assembly, S, B_wit, num_partials, num_lk
        ):
            for nm, fn, args in RES.cols_from_mono_kernel_specs(
                tag, B, n, L, idxs
            ):
                add(nm, fn, *args)
    if smm is not None and not (stream or stream_setup):
        capE = 2 + num_lk + num_pi
        add(
            "deep_codeword_limbres_sm",
            SS._deep_fn_p(smm, 4, 2, num_lk, num_pi),
            (_sdsp(B_wit, N), _sdsp(B_setup, N), _sdsp(S, N), _sdsp(B_q, N)),
            _sdsp(B_all), _sdsp(B_all), _sdsp(B_all), _sdsp(B_all),
            pairp(N), pairp(N), _sdsp(2, N), _sdsp(2 * num_lk, N),
            _sdsp(N) if lookups else _sdsp(1), _sdsp(num_pi, N),
            _sdsp(num_pi, N), _sdsp(num_pi), pairp(2), pairp(num_lk),
            _sdsp(capE), _sdsp(capE),
        )
    else:
        for b in sorted(deep_blocks):
            add(
                f"deep_block_limbres_b{b}", RES._deep_block_p,
                _sdsp(b, N), _sdsp(b), _sdsp(b),
            )
        add(
            "deep_combine_limbres", RES._deep_combine_p,
            _sdsp(N), _sdsp(N), _sdsp(B_all), _sdsp(B_all),
            _sdsp(B_all), _sdsp(B_all), pairp(N),
        )
        extras = RES._deep_extras_fn_p(2, num_lk, num_pi)
        add(
            "deep_extras_limbres", extras,
            pairp(N), _sdsp(2, N), _sdsp(2 * num_lk, N), _sdsp(num_pi, N),
            pairp(N), _sdsp(N) if lookups else _sdsp(1), _sdsp(num_pi, N),
            pairp(2), pairp(num_lk), _sdsp(num_pi),
            _sdsp(2 + num_lk + num_pi), _sdsp(2 + num_lk + num_pi),
        )
    for nm, fn, args in fri_kernel_specs(n, config, True, smm, hasher):
        add(nm, fn, *args)

    # ---- cached plane domain tables' inversions --------------------------
    from .fri import fold_schedule

    add("binv_domain_limbres", lop.batch_inverse_jit, _sdsp(N))
    num_folds = sum(
        fold_schedule(
            n, config.fri_final_degree,
            getattr(config, "fri_folding_schedule", None),
        )
    )
    log_full = N.bit_length() - 1
    for r in range(num_folds):
        add(
            f"binv_fold_limbres_r{r}", lop.batch_inverse_jit,
            _sdsp(1 << (log_full - r - 1)),
        )
    if num_pi:
        add("binv_pi_limbres", lop.batch_inverse_jit, _sdsp(num_pi, N))

    seen = set()
    out = []
    for s in specs:
        key = (id(s.fn), repr(s.args))
        if key in seen:
            continue
        seen.add(key)
        out.append(s)
    return out


def precompile(
    assembly,
    config,
    max_workers: int = 8,
    ledger: CompileLedger | None = None,
    lower_only: bool = False,
    mesh_shape=None,
    specs=None,
) -> CompileLedger:
    """Lower + compile the whole kernel library, overlapping the backend
    compiles on a thread pool.

    Tracing/lowering runs on the calling thread (it is Python work and
    would only contend for the GIL); `.compile()` calls, which release
    the GIL, run on up to `max_workers` threads. Failures are
    recorded per-kernel (ledger entry gains an "error" field) and never
    abort the sweep: a kernel that fails to precompile simply compiles at
    first dispatch like before. With `lower_only`, skips the backend
    compile — used by tier-1 tests to validate the enumeration on CPU,
    and still exercises every trace path. `specs` lets a caller that
    already enumerated (the aot.py bundle builder exports the same list)
    skip the second derivation."""
    from .shape_key import bucket_key

    if ledger is None:
        ledger = current_compile_ledger() or CompileLedger()
    # every ledger entry of this sweep carries the shape-bucket key —
    # the SAME key the service admission queue groups requests by
    shape = bucket_key(assembly, config)
    if specs is None:
        with _span("precompile_enumerate", shape=shape):
            specs = enumerate_kernels(
                assembly, config, mesh_shape=mesh_shape
            )
    _metrics.count("precompile.kernels", len(specs))
    # warm the analytic cost sheet from this enumeration so the first
    # recorded prove's cost seam never re-walks it inside its span
    from ..utils import costmodel as _costmodel

    _costmodel.prime_sheet(assembly, config, specs, mesh_shape=mesh_shape)

    lowered = []
    with _span("precompile_lower", kernels=len(specs)):
        for spec in specs:
            t0 = time.perf_counter()
            try:
                low = spec.fn.lower(*spec.args)
            except Exception as e:  # noqa: BLE001 - record and continue
                ledger.record(
                    spec.name, time.perf_counter() - t0, 0.0, error=repr(e),
                    shape_key=shape,
                )
                _metrics.count("precompile.lower_errors")
                continue
            lowered.append((spec, time.perf_counter() - t0, low))

    if lower_only:
        for spec, trace_s, _low in lowered:
            ledger.record(spec.name, trace_s, 0.0, cache_hit=None,
                          shape_key=shape)
        return ledger

    def _compile_one(item):
        spec, trace_s, low = item
        t0 = time.perf_counter()
        try:
            compiled = low.compile()
        except Exception as e:  # noqa: BLE001
            ledger.record(
                spec.name, trace_s, time.perf_counter() - t0, error=repr(e),
                shape_key=shape,
            )
            _metrics.count("precompile.compile_errors")
            return
        finally:
            trim_host_heap()
        dt = time.perf_counter() - t0
        # compile-time cost actuals (ISSUE 12): the executable's own
        # flops / bytes-accessed — the analytic cost sheet's
        # cross-check axis, carried per kernel in the ledger
        from ..utils.costmodel import xla_cost_of

        # sub-100ms "compiles" are persistent-cache loads in practice —
        # a heuristic, but the ledger's monitoring counters carry the
        # authoritative process-wide hit/miss totals
        ledger.record(spec.name, trace_s, dt, cache_hit=dt < 0.1,
                      shape_key=shape, xla_cost=xla_cost_of(compiled))

    def _weight(item):
        # schedule the biggest modules first: with K workers and a handful
        # of minute-scale graphs among hundreds of second-scale ones, the
        # makespan is set by whatever big graph starts LAST. Total input
        # bytes (from the ShapeDtypeStruct args already in hand) is the
        # proxy — rendering every module's MLIR text (len(low.as_text()))
        # ranked similarly but cost multi-MB transient strings and seconds
        # of serial Python on the cold-start path this sweep exists to
        # shorten.
        spec, _t, _low = item

        def arg_bytes(a):
            if isinstance(a, (tuple, list)):
                return sum(arg_bytes(x) for x in a)
            shape = getattr(a, "shape", None)
            if shape is None:
                return 0
            n = 1
            for d in shape:
                n *= int(d)
            itemsize = getattr(getattr(a, "dtype", None), "itemsize", 8)
            return n * itemsize

        return -arg_bytes(spec.args)

    lowered.sort(key=_weight)
    workers = max(1, min(max_workers, len(lowered) or 1))
    # every sweep compile is already record()ed above — keep the ledger's
    # log capture from double-counting them into dispatch_compiles
    ledger.suppress_log_capture = True
    try:
        with _span("precompile_compile_pool", workers=workers):
            with ThreadPoolExecutor(max_workers=workers) as pool:
                list(pool.map(_compile_one, lowered))
    finally:
        ledger.suppress_log_capture = False
    return ledger
