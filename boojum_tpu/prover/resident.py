"""Limb-resident prove pipeline (ISSUE 10 tentpole).

PR 4 put the quotient sweep and the FRI folds on (lo, hi) u32 limb planes
but converted u64<->limb "ONLY at call boundaries" — so every kernel call
still paid a split on entry and a join on exit, and each conversion fenced
XLA fusion at the seam. This module makes the PLANES the canonical
on-device representation for the whole prove (ICICLE's conclusion,
PAPERS.md): witness columns enter as planes at H2D upload
(`utils/transfer.chunked_upload(planes=True)` splits once on host), stay
planes through iNTT/LDE (`ntt/limb_ntt.py`), the stage-2 grand product,
Poseidon2 leaf/node sponges, the fused quotient sweep, DEEP accumulation,
streamed commits and the FRI chain, and `limbs.join` survives only at the
API edge — transcript absorbs, query openings and proof serialization all
reassemble u64 ON HOST (`limbs.join_np`).

Everything here is a `_p`-suffixed twin of a fused-round graph in
prover.py/stages.py, computing the SAME exact mod-p values on planes
(limb ops are exact and canonical, inverses unique), so proof bytes and
the Fiat–Shamir checkpoint stream are bit-identical to the u64 path —
pinned by tests/test_limb_resident.py, which also pins ZERO interior
`limb.splits`/`limb.joins` during a resident prove (the metrics counters
charged inside field/limbs.py; the allowlisted edges are the host-side
conversions plus the per-setup-object `limbs.edge("ingest:*")` splits of
data that was born u64 before residency — sigma/setup oracles and their
committed tree).

Dispatch: `utils/pallas_util.resolve_variant().planes` — the TPU's
representation (meshless or shard_map); BOOJUM_TPU_LIMB_RESIDENT `=0`
keeps the u64 path, `=1` opts in on CPU (how the parity tests reach it).

Field note (ISSUE 19): limb residency is a Goldilocks-only concern — the
planes exist because Goldilocks elements are 64-bit and Mosaic has no
64-bit integer datapath. Under `BOOJUM_TPU_FIELD=babybear` every element
already fits one u32 lane, so there is nothing to split: the dispatcher
(`precompile.enumerate_kernels`) selects the plane-free `_bb` kernel twins
(prover/bb_kernels.py) first, and the resolver never answers `planes`
under babybear. No module here participates in a BabyBear prove.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field import extension as ext_f
from ..field import gl
from ..field import limb_ops as lop
from ..field import limbs
from ..merkle import POSEIDON2
from ..ntt import limb_ntt as LN
from ..ntt.ntt import _powers_np, bitreverse_indices
from ..utils import metrics as _metrics
from ..utils import transfer as _transfer
from ..utils.spans import span as _span

_MASK = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Host-side builders: scalars/tables enter the device already as planes
# ---------------------------------------------------------------------------


def host_planes(arr):
    """Host uint64 numpy -> device (lo, hi) planes (host split: an edge
    by construction — no device conversion exists)."""
    lo, hi = limbs.split_np(np.asarray(arr, dtype=np.uint64))
    return jnp.asarray(lo), jnp.asarray(hi)


def sc_table_np(cols0, cols1) -> np.ndarray:
    """Two int lists (c0s, c1s) -> (4, S) u32 scalar table in the kernel
    layout of pallas_sweep._pack_table, built entirely on host."""
    c0 = np.array([int(v) % gl.P for v in cols0], dtype=np.uint64)
    c1 = np.array([int(v) % gl.P for v in cols1], dtype=np.uint64)
    return np.stack(
        [
            (c0 & _MASK).astype(np.uint32),
            (c0 >> np.uint64(32)).astype(np.uint32),
            (c1 & _MASK).astype(np.uint32),
            (c1 >> np.uint64(32)).astype(np.uint32),
        ]
    )


def ext_sc_np(s) -> np.ndarray:
    """One host ext scalar -> (4,) u32 [c0lo, c0hi, c1lo, c1hi]."""
    c0, c1 = int(s[0]) % gl.P, int(s[1]) % gl.P
    return np.array(
        [c0 & _MASK, c0 >> 32, c1 & _MASK, c1 >> 32], dtype=np.uint32
    )


def bg_np(b, g) -> np.ndarray:
    """Two host ext scalars -> (8,) u32 [b0lo,b0hi,b1lo,b1hi,g0..]."""
    return np.concatenate([ext_sc_np(b), ext_sc_np(g)])


def _next_pow2(x: int) -> int:
    c = 1
    while c < max(x, 1):
        c *= 2
    return c


def sweep_table_np(alpha, total_alpha_terms, beta, gamma, lkb, lkg,
                   lookups: bool, width: int) -> np.ndarray:
    """The (4, S) u32 scalar table of the resident sweep, in EXACTLY the
    column layout pallas_sweep.build_coset_terms' `call` reads ([alpha
    powers (pow2 cap) | beta | gamma | lkb | lkg | gpow(width+1) | lkb']),
    built from the host transcript challenges — the alpha/γ-power tables
    never exist as device u64."""
    capA = _next_pow2(total_alpha_terms)
    ap = ext_f.powers_s(tuple(int(v) for v in alpha), capA)
    cols0 = [p[0] for p in ap] + [beta[0], gamma[0], lkb[0], lkg[0]]
    cols1 = [p[1] for p in ap] + [beta[1], gamma[1], lkb[1], lkg[1]]
    if lookups:
        gpow = ext_f.powers_s(tuple(int(v) for v in lkg), width + 1)
        cols0 += [p[0] for p in gpow] + [lkb[0]]
        cols1 += [p[1] for p in gpow] + [lkb[1]]
    return sc_table_np(cols0, cols1)


# ---------------------------------------------------------------------------
# Cached plane domain tables (challenge-independent, per geometry)
# ---------------------------------------------------------------------------


_mul_gen_jit = jax.jit(
    lambda p: limbs.mul_const(
        p, limbs.const_pair(int(gl.MULTIPLICATIVE_GENERATOR))
    )
)


@lru_cache(maxsize=4)
def domain_xs_brev_p(log_n: int, lde_factor: int):
    """Plane twin of prover._domain_xs_brev (host powers + limb scale)."""
    log_full = log_n + (lde_factor.bit_length() - 1)
    xs = host_planes(_powers_np(gl.omega(log_full), 1 << log_full))
    xs = _mul_gen_jit(xs)
    brev = jnp.asarray(bitreverse_indices(log_full))
    return xs[0][brev], xs[1][brev]


@jax.jit
def _sub_ones_jit(p):
    return limbs.sub(p, lop.ones_like(p[0]))


@partial(jax.jit, static_argnums=(2,))
def _l0_scale_jit(zh_p, binv_p, log_n: int):
    t = limbs.mul_const(zh_p, limbs.const_pair(gl.inv(1 << log_n)))
    return limbs.mul(t, binv_p)


@lru_cache(maxsize=4)
def l0_brev_p(log_n: int, lde_factor: int):
    """Plane twin of prover._l0_brev."""
    n = 1 << log_n
    log_full = log_n + (lde_factor.bit_length() - 1)
    zh_vals = np.array(
        [
            gl.sub(
                gl.pow_(
                    gl.mul(
                        gl.MULTIPLICATIVE_GENERATOR,
                        gl.pow_(gl.omega(log_full), int(jb)),
                    ),
                    n,
                ),
                1,
            )
            for jb in bitreverse_indices(lde_factor.bit_length() - 1)
        ],
        dtype=np.uint64,
    )
    zh = host_planes(np.repeat(zh_vals, n))
    xs = domain_xs_brev_p(log_n, lde_factor)
    binv = lop.counted(lop.batch_inverse_jit, _sub_ones_jit(xs))
    return _l0_scale_jit(zh, binv, log_n)


@lru_cache(maxsize=4)
def inv_xs_brev_p(log_n: int, L: int):
    return lop.counted(lop.batch_inverse_jit, domain_xs_brev_p(log_n, L))


@lru_cache(maxsize=4)
def vanishing_inv_brev_p(log_n: int, lde_factor: int):
    """Plane twin of prover._vanishing_inv_brev (fully host-built)."""
    n = 1 << log_n
    log_lde = lde_factor.bit_length() - 1
    w_full = gl.omega(log_n + log_lde)
    vals = []
    for jb in bitreverse_indices(log_lde):
        shift = gl.mul(gl.MULTIPLICATIVE_GENERATOR, gl.pow_(w_full, int(jb)))
        vals.append(gl.inv(gl.sub(gl.pow_(shift, n), 1)))
    return host_planes(np.repeat(np.array(vals, dtype=np.uint64), n))


@lru_cache(maxsize=8)
def omega_powers_p(log_n: int):
    """[1, w, w^2, ...] planes for the z-shift (host-built)."""
    return host_planes(_powers_np(gl.omega(log_n), 1 << log_n))


def clear_plane_caches():
    """Resident counterpart of prover.clear_domain_caches."""
    from .fri import fold_challenge_tables_p

    for fn in (
        domain_xs_brev_p,
        l0_brev_p,
        inv_xs_brev_p,
        vanishing_inv_brev_p,
        omega_powers_p,
        fold_challenge_tables_p,
    ):
        fn.cache_clear()


# ---------------------------------------------------------------------------
# Round 2: grand product / lookup twins (stages.py on planes)
# ---------------------------------------------------------------------------


def _bg(bg_arr):
    """(8,) u32 -> (beta_ext, gamma_ext) scalar plane elements."""
    b = ((bg_arr[0], bg_arr[1]), (bg_arr[2], bg_arr[3]))
    g = ((bg_arr[4], bg_arr[5]), (bg_arr[6], bg_arr[7]))
    return b, g


@partial(jax.jit, static_argnums=(4,))
def _all_chunk_num_den_p(copy_p, sigma_p, ks_p, xs_bg, chunks):
    """Plane twin of stages._all_chunk_num_den (same scan structure).
    `xs_bg` bundles (xs planes, (8,) challenge table)."""
    xs_p, bg_arr = xs_bg
    b, g = _bg(bg_arr)
    n = copy_p[0].shape[-1]
    flat = [col for c in chunks for col in c]
    assert flat == list(range(len(flat))), chunks
    w = len(chunks[0])
    K_full = sum(1 for c in chunks if len(c) == w)
    assert all(len(c) == w for c in chunks[:K_full]), chunks
    assert len(chunks) - K_full <= 1, chunks

    def _prod_terms(cv, sv, kv):
        num_p = den_p = None
        for j in range(cv[0].shape[0]):
            wcol = (cv[0][j], cv[1][j])
            kx = limbs.mul(xs_p, (kv[0][j], kv[1][j]))
            num = (
                limbs.add(limbs.add(wcol, limbs.mul(kx, b[0])), g[0]),
                limbs.add(limbs.mul(kx, b[1]), g[1]),
            )
            s = (sv[0][j], sv[1][j])
            den = (
                limbs.add(limbs.add(wcol, limbs.mul(s, b[0])), g[0]),
                limbs.add(limbs.mul(s, b[1]), g[1]),
            )
            num_p = num if num_p is None else limbs.ext_mul(num_p, num)
            den_p = den if den_p is None else limbs.ext_mul(den_p, den)
        return num_p, den_p

    def body(carry, blk):
        cvl, cvh, svl, svh, kvl, kvh = blk
        num_p, den_p = _prod_terms((cvl, cvh), (svl, svh), (kvl, kvh))
        return carry, (
            num_p[0][0], num_p[0][1], num_p[1][0], num_p[1][1],
            den_p[0][0], den_p[0][1], den_p[1][0], den_p[1][1],
        )

    Cw = K_full * w
    _, scanned = jax.lax.scan(
        body,
        None,
        (
            copy_p[0][:Cw].reshape(K_full, w, n),
            copy_p[1][:Cw].reshape(K_full, w, n),
            sigma_p[0][:Cw].reshape(K_full, w, n),
            sigma_p[1][:Cw].reshape(K_full, w, n),
            ks_p[0][:Cw].reshape(K_full, w),
            ks_p[1][:Cw].reshape(K_full, w),
        ),
    )
    n00, n01, n10, n11, d00, d01, d10, d11 = scanned
    if len(chunks) > K_full:
        num_p, den_p = _prod_terms(
            (copy_p[0][Cw:], copy_p[1][Cw:]),
            (sigma_p[0][Cw:], sigma_p[1][Cw:]),
            (ks_p[0][Cw:], ks_p[1][Cw:]),
        )
        n00 = jnp.concatenate([n00, num_p[0][0][None]])
        n01 = jnp.concatenate([n01, num_p[0][1][None]])
        n10 = jnp.concatenate([n10, num_p[1][0][None]])
        n11 = jnp.concatenate([n11, num_p[1][1][None]])
        d00 = jnp.concatenate([d00, den_p[0][0][None]])
        d01 = jnp.concatenate([d01, den_p[0][1][None]])
        d10 = jnp.concatenate([d10, den_p[1][0][None]])
        d11 = jnp.concatenate([d11, den_p[1][1][None]])
    return ((n00, n01), (n10, n11)), ((d00, d01), (d10, d11))


def _ext_prefix_prod_p(a):
    """Inclusive ext prefix product along the last axis on planes
    (field/extension.prefix_product twin)."""
    n = a[0][0].shape[-1]
    shift = 1
    while shift < n:
        ones = jnp.ones((shift,), jnp.uint32)
        zeros = jnp.zeros((shift,), jnp.uint32)
        shifted = (
            (
                jnp.concatenate([ones, a[0][0][:-shift]]),
                jnp.concatenate([zeros, a[0][1][:-shift]]),
            ),
            (
                jnp.concatenate([zeros, a[1][0][:-shift]]),
                jnp.concatenate([zeros, a[1][1][:-shift]]),
            ),
        )
        a = limbs.ext_mul(a, shifted)
        shift *= 2
    return a


@jax.jit
def _z_and_partials_p(num_all, den_inv_all):
    """Plane twin of stages._z_and_partials."""
    K = num_all[0][0].shape[0]
    ratios = limbs.ext_mul(num_all, den_inv_all)

    def row(j):
        return (
            (ratios[0][0][j], ratios[0][1][j]),
            (ratios[1][0][j], ratios[1][1][j]),
        )

    full = row(0)
    for j in range(1, K):
        full = limbs.ext_mul(full, row(j))
    incl = _ext_prefix_prod_p(full)
    one = jnp.ones((1,), jnp.uint32)
    zero = jnp.zeros((1,), jnp.uint32)
    z = (
        (
            jnp.concatenate([one, incl[0][0][:-1]]),
            jnp.concatenate([zero, incl[0][1][:-1]]),
        ),
        (
            jnp.concatenate([zero, incl[1][0][:-1]]),
            jnp.concatenate([zero, incl[1][1][:-1]]),
        ),
    )
    parts = []
    acc = z
    for j in range(K - 1):
        acc = limbs.ext_mul(acc, row(j))
        parts.append(acc)
    if parts:
        stacked = (
            (
                jnp.stack([p[0][0] for p in parts]),
                jnp.stack([p[0][1] for p in parts]),
            ),
            (
                jnp.stack([p[1][0] for p in parts]),
                jnp.stack([p[1][1] for p in parts]),
            ),
        )
        return z, stacked
    e = jnp.zeros((0,) + z[0][0].shape, jnp.uint32)
    return z, ((e, e), (e, e))


@partial(jax.jit, static_argnums=(3, 4))
def _lookup_denominators_p(
    lk_cols_p, tid_table_p, bg_arr, num_repetitions, width
):
    """Plane twin of stages._lookup_denominators. `tid_table_p` bundles
    (table_id planes, stacked table planes)."""
    tid_p, table_p = tid_table_p
    b, g = _bg(bg_arr)
    gpow = lop.ext_powers(g, width + 1)
    dens = []
    for i in range(num_repetitions):
        cols = [
            (lk_cols_p[0][i * width + j], lk_cols_p[1][i * width + j])
            for j in range(width)
        ]
        dens.append(lop.aggregate_columns(cols, tid_p, gpow, b))
    dens.append(
        lop.aggregate_columns(
            [(table_p[0][j], table_p[1][j]) for j in range(width)],
            (table_p[0][width], table_p[1][width]),
            gpow,
            b,
        )
    )
    return (
        (
            jnp.stack([d[0][0] for d in dens]),
            jnp.stack([d[0][1] for d in dens]),
        ),
        (
            jnp.stack([d[1][0] for d in dens]),
            jnp.stack([d[1][1] for d in dens]),
        ),
    )


@jax.jit
def _lookup_denominators_inv_p(dens):
    """The batch inverse of `_lookup_denominators_p`'s output, as a program
    with a name of its own: under `ext_batch_inverse`'s it shared a module
    name with the copy permutation's and the DEEP inversions, and a device
    trace could not give the lookup argument its stage-2 time."""
    return lop.ext_batch_inverse(dens)


def stage2_stack_fn_p(assembly, selector_paths):
    """Plane twin of prover._stage2_stack_fn, cached per assembly."""
    cached = getattr(assembly, "_stage2_stack_p_jit", None)
    if cached is not None:
        return cached

    from .stages import chunk_columns

    lookups = assembly.lookups_enabled
    lk_mode = assembly.lookup_mode
    R_args = assembly.num_lookup_subargs
    num_chunks = len(
        chunk_columns(
            assembly.copy_placement.shape[0] + assembly.num_lookup_cols,
            assembly.geometry.max_allowed_constraint_degree,
        )
    )
    if lookups and lk_mode == "general":
        mk_path = tuple(selector_paths[assembly.lookup_marker_gid()])
    else:
        mk_path = None

    @jax.jit
    def _stage2_stack_p(z, partials_stacked, lk_inv, multiplicities, consts_dev):
        lo_rows = [z[0][0], z[1][0]]
        hi_rows = [z[0][1], z[1][1]]
        for j in range(num_chunks - 1):
            lo_rows += [partials_stacked[0][0][j], partials_stacked[1][0][j]]
            hi_rows += [partials_stacked[0][1][j], partials_stacked[1][1][j]]
        if lookups:
            sel_h = None
            if lk_mode == "general":
                for bdx, bit in enumerate(mk_path):
                    col = (consts_dev[0][bdx], consts_dev[1][bdx])
                    f = col if bit else limbs.sub(lop.ones_like(col[0]), col)
                    sel_h = f if sel_h is None else limbs.mul(sel_h, f)
            for i in range(R_args):
                a0 = (lk_inv[0][0][i], lk_inv[0][1][i])
                a1 = (lk_inv[1][0][i], lk_inv[1][1][i])
                if sel_h is not None:
                    a0 = limbs.mul(a0, sel_h)
                    a1 = limbs.mul(a1, sel_h)
                lo_rows += [a0[0], a1[0]]
                hi_rows += [a0[1], a1[1]]
            t0 = limbs.mul(
                (lk_inv[0][0][R_args], lk_inv[0][1][R_args]), multiplicities
            )
            t1 = limbs.mul(
                (lk_inv[1][0][R_args], lk_inv[1][1][R_args]), multiplicities
            )
            lo_rows += [t0[0], t1[0]]
            hi_rows += [t0[1], t1[1]]
        return jnp.stack(lo_rows), jnp.stack(hi_rows)

    assembly._stage2_stack_p_jit = _stage2_stack_p
    return _stage2_stack_p


# ---------------------------------------------------------------------------
# Round 3: z-shift, coset evaluation, quotient tail (on planes)
# ---------------------------------------------------------------------------


@jax.jit
def _zshift_p(s2_mono2_p, pows_p):
    """(2, n) z monomial planes -> z(w·x) monomial planes (host powers)."""
    return limbs.mul(s2_mono2_p, (pows_p[0][None], pows_p[1][None]))


_SWEEP_EVAL_CHUNK = 128 << 20


@jax.jit
def _coset_eval_p(mono_p, scale_row_p):
    """Plane twin of prover._coset_eval (same chunked barrier posture)."""
    B, n = mono_p[0].shape
    per = max(1, _SWEEP_EVAL_CHUNK // (n * 8))
    if B <= per:
        scaled = limbs.mul(
            mono_p, (scale_row_p[0][None], scale_row_p[1][None])
        )
        return _fft_dispatch(scaled)
    out_lo = jnp.zeros((B, n), jnp.uint32)
    out_hi = jnp.zeros((B, n), jnp.uint32)
    mlo, mhi = mono_p
    for i in range(0, B, per):
        mlo, mhi, out_lo, out_hi = jax.lax.optimization_barrier(
            (mlo, mhi, out_lo, out_hi)
        )
        chunk = limbs.mul(
            (mlo[i : i + per], mhi[i : i + per]),
            (scale_row_p[0][None], scale_row_p[1][None]),
        )
        clo, chi = _fft_dispatch(chunk)
        out_lo = jax.lax.dynamic_update_slice_in_dim(out_lo, clo, i, axis=0)
        out_hi = jax.lax.dynamic_update_slice_in_dim(out_hi, chi, i, axis=0)
    return out_lo, out_hi


def _fft_dispatch(p):
    return LN.fft_natural_to_bitreversed_p(p)


@jax.jit
def _coset_eval_q_p(mono_p, scale_q_p, c_arr):
    """Plane twin of prover._coset_eval_q."""
    row = (
        jax.lax.dynamic_index_in_dim(scale_q_p[0], c_arr, 0, keepdims=False),
        jax.lax.dynamic_index_in_dim(scale_q_p[1], c_arr, 0, keepdims=False),
    )
    return _coset_eval_p(mono_p, row)


@jax.jit
def _coset_eval_row_p(scale_q_p, c_arr):
    return (
        jax.lax.dynamic_index_in_dim(scale_q_p[0], c_arr, 0, keepdims=False),
        jax.lax.dynamic_index_in_dim(scale_q_p[1], c_arr, 0, keepdims=False),
    )


def coset_eval_q_p(mono_p, scale_q_p, c_arr):
    """One group's evaluation on coset `c_arr` of the rate-Q domain: one
    program (`_coset_eval_q_p`) up to 2^16 rows; above, where the forward
    NTT is a program of its own that no jit may hold
    (limb_ntt._hybrid_fwd_p), the scale row's pick and then ONE dispatch a
    column chunk: its slice, the row and the transform in one kernel."""
    if not LN.forward_is_own_program(mono_p[0].shape[-1]):
        return _coset_eval_q_p(mono_p, scale_q_p, c_arr)
    return LN.scaled_fft_p(
        mono_p, _coset_eval_row_p(scale_q_p, c_arr), _SWEEP_EVAL_CHUNK
    )


def coset_eval_kernel_specs(tag: str, B: int, n: int, Q: int) -> list:
    """(name, fn, args) of what `coset_eval_q_p` dispatches for a (B, n)
    group: the one program, or the row's pick and a fused transform for
    each size of column chunk (the chunk's first column is a device
    scalar, so chunks of one size share a program; above 2^18 rows the
    outer program of the leading stages comes with it)."""
    sdsp = LN.sdsp
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    name = f"coset_eval_{tag}_limbres"
    if not LN.forward_is_own_program(n):
        return [(name, _coset_eval_q_p, (sdsp(B, n), sdsp(Q, n), i32))]
    log_n = n.bit_length() - 1
    specs = [(f"{name}:row", _coset_eval_row_p, (sdsp(Q, n), i32))]
    sizes = LN.scaled_fft_chunks(B, n, _SWEEP_EVAL_CHUNK).values()
    for b in sorted(set(sizes)):
        specs += LN.hybrid_fwd_kernel_specs(
            f"{name}:fft_b{b}", (sdsp(B, n), sdsp(n), i32, b),
            log_n, LN._COSET_EVAL_FORWARD,
        )
    return specs


def _quotient_parts_p(T0_parts, T1_parts):
    return tuple(
        (
            jnp.concatenate([t[0] for t in parts]),
            jnp.concatenate([t[1] for t in parts]),
        )
        for parts in (T0_parts, T1_parts)
    )


def _quotient_unshift_p(t):
    """An inverse transform over the coset -> the monomials: times g^-i."""
    return LN.distribute_powers_p(t, gl.inv(gl.MULTIPLICATIVE_GENERATOR))


def _quotient_rows_p(T_mono, Q: int, n: int):
    """The two components' monomials -> the (2 * Q, n) rows of the
    quotient's chunks."""
    lo_rows, hi_rows = [], []
    for i in range(Q):
        for comp in (0, 1):
            lo_rows.append(T_mono[comp][0][i * n : (i + 1) * n])
            hi_rows.append(T_mono[comp][1][i * n : (i + 1) * n])
    return jnp.stack(lo_rows), jnp.stack(hi_rows)


@partial(jax.jit, static_argnums=(2, 3))
def _quotient_interp_p(T0_parts, T1_parts, Q: int, n: int):
    """Plane twin of prover._quotient_interp."""
    T_mono = tuple(
        _quotient_unshift_p(LN.ifft_bitreversed_to_natural_p(t))
        for t in _quotient_parts_p(T0_parts, T1_parts)
    )
    return _quotient_rows_p(T_mono, Q, n)


@partial(jax.jit, static_argnums=(2,))
def _quotient_interp_kernels_p(T0_parts, T1_parts, log_qn: int):
    return tuple(
        LN.hybrid_inv_kernels_p(t, log_qn)
        for t in _quotient_parts_p(T0_parts, T1_parts)
    )


@partial(jax.jit, static_argnums=(1, 2))
def _quotient_interp_outer_p(T_blocks, Q: int, n: int):
    log_qn = (Q * n).bit_length() - 1
    T_mono = tuple(
        _quotient_unshift_p(LN.hybrid_inv_outer_p(t, log_qn))
        for t in T_blocks
    )
    return _quotient_rows_p(T_mono, Q, n)


def quotient_interp_p(T0_parts, T1_parts, Q: int, n: int):
    """The quotient's monomials from the sweeps' outputs: ONE program
    (`_quotient_interp_p`) while the inverse transform's outer stages and
    its kernels return from one; past that
    (`limb_ntt.inverse_is_two_programs`: 2^22 on the v5e) the kernels over
    the joined parts and the outer stages with the rows, a program each."""
    if not LN.inverse_is_two_programs(Q * n):
        return _quotient_interp_p(T0_parts, T1_parts, Q, n)
    blocks = _quotient_interp_kernels_p(
        T0_parts, T1_parts, (Q * n).bit_length() - 1
    )
    return _quotient_interp_outer_p(blocks, Q, n)


def quotient_interp_kernel_specs(Q: int, n: int) -> list:
    """(name, fn, args) of what `quotient_interp_p` dispatches."""
    parts = tuple(LN.sdsp(n) for _ in range(Q))
    if not LN.inverse_is_two_programs(Q * n):
        return [("quotient_interp_limbres", _quotient_interp_p,
                 (parts, parts, Q, n))]
    whole = (LN.sdsp(Q * n), LN.sdsp(Q * n))
    return [
        ("quotient_interp_limbres:kernels", _quotient_interp_kernels_p,
         (parts, parts, (Q * n).bit_length() - 1)),
        ("quotient_interp_limbres:outer", _quotient_interp_outer_p,
         (whole, Q, n)),
    ]


def _quotient_tail_p(T0_parts, T1_parts, Q: int, n: int, L: int, cap: int,
                     stream: bool = False, hasher=POSEIDON2):
    """Plane twin of prover._quotient_tail_fused (same dispatch split)."""
    q_mono = quotient_interp_p(tuple(T0_parts), tuple(T1_parts), Q, n)
    if stream:
        return q_mono, None, streamed_commit_layers_p(q_mono, L, cap)
    q_lde = LN.lde_from_monomial_p(q_mono, L)
    return q_mono, q_lde, hasher.commit_layers_planes(q_lde, cap)


# ---------------------------------------------------------------------------
# Round 4: evaluations at z (on planes)
# ---------------------------------------------------------------------------


def _modsum_p(p):
    """Modular sum along the last axis on planes (ntt._modsum twin)."""
    lo, hi = p
    n = lo.shape[-1]
    while n > 1:
        if n % 2 == 1:
            z = jnp.zeros(lo.shape[:-1] + (1,), jnp.uint32)
            lo = jnp.concatenate([lo, z], axis=-1)
            hi = jnp.concatenate([hi, z], axis=-1)
            n += 1
        lo, hi = limbs.add(
            (lo[..., : n // 2], hi[..., : n // 2]),
            (lo[..., n // 2 :], hi[..., n // 2 :]),
        )
        n //= 2
    return lo[..., 0], hi[..., 0]


def _modsum_axis0_p(p):
    return _modsum_p((jnp.moveaxis(p[0], 0, -1), jnp.moveaxis(p[1], 0, -1)))


@partial(jax.jit, static_argnums=(1,))
def _ext_powers_p_jit(z_tb, count: int):
    """Plane twin of ntt._ext_powers_jit (log-doubling; `z_tb` is the (4,)
    u32 host-built challenge)."""
    p0 = (jnp.ones((1,), jnp.uint32), jnp.zeros((1,), jnp.uint32))
    p1 = (jnp.zeros((1,), jnp.uint32), jnp.zeros((1,), jnp.uint32))
    step = ((z_tb[0], z_tb[1]), (z_tb[2], z_tb[3]))
    cur = 1
    while cur < count:
        n0, n1 = limbs.ext_mul((p0, p1), step)
        p0 = (
            jnp.concatenate([p0[0], n0[0]]),
            jnp.concatenate([p0[1], n0[1]]),
        )
        p1 = (
            jnp.concatenate([p1[0], n1[0]]),
            jnp.concatenate([p1[1], n1[1]]),
        )
        step = limbs.ext_mul(step, step)
        cur *= 2
    return p0, p1


def _eval_with_pows_p(coeffs_p, p0, p1):
    c0 = _modsum_p(limbs.mul(coeffs_p, p0))
    c1 = _modsum_p(limbs.mul(coeffs_p, p1))
    return c0, c1


@jax.jit
def _evals_p(all_mono_p, s2_mono_p, z_tb, zw_tb):
    """Plane twin of prover._evals_fused; outputs stay planes (the caller
    pulls them to host and joins at the transcript edge)."""
    n = all_mono_p[0].shape[-1]
    zp = _ext_powers_p_jit(z_tb, n)
    ev0, ev1 = _eval_with_pows_p(all_mono_p, zp[0], zp[1])
    zwp = _ext_powers_p_jit(zw_tb, n)
    evw0, evw1 = _eval_with_pows_p(
        (s2_mono_p[0][:2], s2_mono_p[1][:2]), zwp[0], zwp[1]
    )
    return ev0, ev1, evw0, evw1


# ---------------------------------------------------------------------------
# Round 5: DEEP on planes
# ---------------------------------------------------------------------------


@jax.jit
def _deep_denoms_p(xs_lde_p, z_tb, zw_tb):
    """Plane twin of prover._deep_denoms_fused."""
    shape = xs_lde_p[0].shape

    def _sub_sc(tb_lo, tb_hi):
        return limbs.sub(xs_lde_p, (tb_lo, tb_hi))

    a = _sub_sc(z_tb[0], z_tb[1])
    b = _sub_sc(zw_tb[0], zw_tb[1])
    c0 = (jnp.stack([a[0], b[0]]), jnp.stack([a[1], b[1]]))
    nz = limbs.neg((z_tb[2], z_tb[3]))
    nzw = limbs.neg((zw_tb[2], zw_tb[3]))
    c1 = (
        jnp.stack(
            [
                jnp.broadcast_to(nz[0], shape),
                jnp.broadcast_to(nzw[0], shape),
            ]
        ),
        jnp.stack(
            [
                jnp.broadcast_to(nz[1], shape),
                jnp.broadcast_to(nzw[1], shape),
            ]
        ),
    )
    return c0, c1


_DEEP_BLOCK_BUDGET = 128 << 20


@jax.jit
def _deep_block_p(blk_p, c0s_p, c1s_p):
    return (
        _modsum_axis0_p(
            limbs.mul(blk_p, (c0s_p[0][:, None], c0s_p[1][:, None]))
        ),
        _modsum_axis0_p(
            limbs.mul(blk_p, (c1s_p[0][:, None], c1s_p[1][:, None]))
        ),
    )


@jax.jit
def _deep_combine_p(t0, t1, y0s_p, y1s_p, c0s_p, c1s_p, inv_xz):
    s = limbs.ext_mul((c0s_p, c1s_p), (y0s_p, y1s_p))
    num = (
        limbs.sub(t0, _modsum_axis0_p(s[0])),
        limbs.sub(t1, _modsum_axis0_p(s[1])),
    )
    return limbs.ext_mul(num, inv_xz)


def deep_source_blocks_p(sources, per_bytes: int):
    """Plane twin of streaming.deep_source_blocks."""
    from .streaming import MonomialPlanesSource, count_lde_columns

    off = 0
    for src in sources:
        if isinstance(src, MonomialPlanesSource):
            count_lde_columns("deep", src.shape[0])
            for i, flat in src.blocks(span="stream.deep_regen"):
                yield flat, off + i
            off += src.shape[0]
        else:
            B, N = src[0].shape
            per = max(1, per_bytes // (N * 8))
            for i in range(0, B, per):
                yield (src[0][i : i + per], src[1][i : i + per]), off + i
            off += B


def _deep_main_sum_p(sources, y0s_p, y1s_p, c0s_p, c1s_p, inv_xz):
    """Plane twin of prover._deep_main_sum."""
    t0 = t1 = None
    for blk, off in deep_source_blocks_p(sources, _DEEP_BLOCK_BUDGET):
        _metrics.count("deep.blocks")
        j = off + blk[0].shape[0]
        b0, b1 = _deep_block_p(
            blk,
            (c0s_p[0][off:j], c0s_p[1][off:j]),
            (c1s_p[0][off:j], c1s_p[1][off:j]),
        )
        t0 = b0 if t0 is None else limbs.add(t0, b0)
        t1 = b1 if t1 is None else limbs.add(t1, b1)
    return _deep_combine_p(t0, t1, y0s_p, y1s_p, c0s_p, c1s_p, inv_xz)


@lru_cache(maxsize=8)
def _deep_extras_fn_p(num_zw: int, num_lk: int, num_pi: int):
    """Plane twin of prover._deep_extras_fn."""

    @jax.jit
    def _deep_extras_p(h, cols_zw, cols_lk, cols_pi, inv_xzw, inv_x,
                       pi_denoms, y_zw, y_lk0, pi_vals, ch0, ch1):
        shape = h[0][0].shape
        t = 0
        for i in range(num_zw):
            ch = ((ch0[0][t], ch0[1][t]), (ch1[0][t], ch1[1][t]))
            ny = limbs.neg((y_zw[1][0][i], y_zw[1][1][i]))
            num = (
                limbs.sub(
                    (cols_zw[0][i], cols_zw[1][i]),
                    (y_zw[0][0][i], y_zw[0][1][i]),
                ),
                (
                    jnp.broadcast_to(ny[0], shape),
                    jnp.broadcast_to(ny[1], shape),
                ),
            )
            h = lop.ext_add(h, limbs.ext_mul(limbs.ext_mul(num, inv_xzw), ch))
            t += 1
        for i in range(num_lk):
            ch = ((ch0[0][t], ch0[1][t]), (ch1[0][t], ch1[1][t]))
            num = (
                limbs.sub(
                    (cols_lk[0][2 * i], cols_lk[1][2 * i]),
                    (y_lk0[0][0][i], y_lk0[0][1][i]),
                ),
                limbs.sub(
                    (cols_lk[0][2 * i + 1], cols_lk[1][2 * i + 1]),
                    (y_lk0[1][0][i], y_lk0[1][1][i]),
                ),
            )
            term = limbs.ext_mul(
                (limbs.mul(num[0], inv_x), limbs.mul(num[1], inv_x)), ch
            )
            h = lop.ext_add(h, term)
            t += 1
        for k in range(num_pi):
            ch = ((ch0[0][t], ch0[1][t]), (ch1[0][t], ch1[1][t]))
            num = limbs.sub(
                (cols_pi[0][k], cols_pi[1][k]),
                (pi_vals[0][k], pi_vals[1][k]),
            )
            term_base = limbs.mul(num, (pi_denoms[0][k], pi_denoms[1][k]))
            h = lop.ext_add(
                h,
                (
                    limbs.mul(term_base, ch[0]),
                    limbs.mul(term_base, ch[1]),
                ),
            )
            t += 1
        return h

    return _deep_extras_p


def _mono_rows_p(mono_p, idxs: tuple):
    sel_idx = jnp.asarray(np.array(idxs, dtype=np.int64))
    return mono_p[0][sel_idx], mono_p[1][sel_idx]


@partial(jax.jit, static_argnums=(1,))
def _deep_cols_take_p(mono_p, idxs: tuple):
    return _mono_rows_p(mono_p, idxs)


def cols_from_mono_p(mono_p, idxs: tuple, L: int):
    """Plane twin of prover.cols_from_mono: the handful of single columns
    round 5 opens at shifted points, regenerated at rate L from a streamed
    oracle's monomials. The picked columns are a block of their own: their
    cut, then the commit's walk (`streaming.lde_block_cols_p`)."""
    from .streaming import count_lde_columns, lde_block_cols_p

    count_lde_columns("deep", len(idxs))
    with _span("stream.deep_regen", columns=len(idxs)):
        sel = _deep_cols_take_p(mono_p, idxs)
        return lde_block_cols_p(sel, 0, len(idxs), L)


def stream_gather_p(source, idx_dev):
    """The query phase's (B, num_queries) leaf values of a streamed
    oracle: the source's own host-driven blocks, a gather a block and
    their join."""
    from .streaming import count_lde_columns

    count_lde_columns("queries", source.shape[0])
    with _span("stream.query_regen", columns=source.shape[0]):
        return source.gather_rows(idx_dev)


def stream_kernel_specs(B: int, n: int, L: int, num_queries: int) -> list:
    """(name, fn, args) of what one streamed oracle of B columns dispatches
    on planes beside its absorbs and DEEP sums: the commit's block
    transforms, the regenerations of DEEP and the query gather, in the
    form `forward_is_own_program(n)` gives each (prover/streaming.py).
    Block and chunk programs are keyed on the block alone, so oracles
    share them: a caller drops the repeats."""
    from . import streaming as ST

    sdsp = LN.sdsp
    N, log_n = n * L, n.bit_length() - 1
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    idx = jax.ShapeDtypeStruct((num_queries,), jnp.int64)
    blocks = sorted({min(ST.COL_BLOCK, B - i) for i in range(0, B, ST.COL_BLOCK)})

    def lde(c):
        return [
            (f"stream:{nm}", fn, args)
            for nm, fn, args in LN.plane_ntt_kernel_specs(
                c, log_n, L, mono=False
            )
        ]

    chunks = ST.block_chunk_sizes(B, n, L)
    specs = [(
        f"stream_gather_join_limbres_b{B}", ST._stream_gather_join_p,
        (tuple(sdsp(c, num_queries) for c in chunks),),
    )]
    for b in blocks:
        specs.append((
            f"lde_block_cols_join_limbres_b{b}", ST._lde_block_cols_join_p,
            (tuple(sdsp(c, L, n) for c in LN.lde_chunk_sizes(b, n, L)),),
        ))
    for c in sorted(set(chunks)):
        if c != B:
            specs.append((
                f"lde_block_cols_take_limbres_b{B}_c{c}",
                ST._lde_block_cols_take_p, (sdsp(B, n), i32, c),
            ))
        specs += lde(c)
        specs.append((
            f"stream_gather_block_limbres_c{c}", ST._stream_gather_block_p,
            (sdsp(c, N), idx),
        ))
    return specs


def cols_from_mono_kernel_specs(tag: str, B: int, n: int, L: int,
                                idxs: tuple) -> list:
    """(name, fn, args) of what `cols_from_mono_p` dispatches for the
    columns `idxs` of a streamed oracle of B columns."""
    from . import streaming as ST

    sdsp = LN.sdsp
    k = len(idxs)
    name = f"deep_cols_{tag}_limbres"
    chunks = LN.lde_chunk_sizes(k, n, L)
    specs = [
        (f"{name}:take", _deep_cols_take_p, (sdsp(B, n), idxs)),
        (f"{name}:join", ST._lde_block_cols_join_p,
         (tuple(sdsp(c, L, n) for c in chunks),)),
    ]
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    for c in sorted(set(chunks)):
        if c != k:
            specs.append((
                f"{name}:take_c{c}", ST._lde_block_cols_take_p,
                (sdsp(k, n), i32, c),
            ))
        specs += [
            (f"{name}:{nm}", fn, args)
            for nm, fn, args in LN.plane_ntt_kernel_specs(
                c, n.bit_length() - 1, L, mono=False
            )
        ]
    return specs


def deep_round5_prep_p(
    assembly, *, log_n, L, N, lookups, num_partials, R_args,
    s2_mono_p, wit_mono_p, s2_lde_flat_p, wit_lde_all_p, xs_lde_p,
    z_tb, zw_tb, omega,
):
    """Plane twin of prover._deep_round5_prep."""
    from .streaming import MonomialPlanesSource

    num_lk = (R_args + 1) if lookups else 0
    num_pi = len(assembly.public_inputs)
    d = _deep_denoms_p(xs_lde_p, z_tb, zw_tb)
    dinv = lop.counted(lop.ext_batch_inverse_jit, d)
    ab_off = 2 + 2 * num_partials
    s2_idxs = [0, 1] + [ab_off + j for j in range(2 * num_lk)]
    if isinstance(s2_lde_flat_p, MonomialPlanesSource):
        s2_cols = cols_from_mono_p(s2_mono_p, tuple(s2_idxs), L)
    else:
        with _transfer.upload("deep_prep", 8 * len(s2_idxs)):
            sel = jnp.asarray(np.array(s2_idxs))
        s2_cols = (s2_lde_flat_p[0][sel], s2_lde_flat_p[1][sel])
    if lookups:
        inv_x = inv_xs_brev_p(log_n, L)
    else:
        z1 = jnp.zeros((1,), jnp.uint32)
        inv_x = (z1, z1)
    if num_pi:
        pi_cols_idx = [c_ for (c_, _r, _v) in assembly.public_inputs]
        if isinstance(wit_lde_all_p, MonomialPlanesSource):
            cols_pi = cols_from_mono_p(wit_mono_p, tuple(pi_cols_idx), L)
        else:
            with _transfer.upload("deep_prep", 8 * num_pi):
                sel = jnp.asarray(np.array(pi_cols_idx))
            cols_pi = (wit_lde_all_p[0][sel], wit_lde_all_p[1][sel])
        with _transfer.upload("deep_prep", 8 * num_pi, 2):
            pi_points = host_planes(
                np.array(
                    [
                        gl.pow_(omega, r)
                        for (_c, r, _v) in assembly.public_inputs
                    ],
                    dtype=np.uint64,
                )
            )
        pi_denoms = lop.counted(
            lop.batch_inverse_jit, _pi_denom_sub_jit(xs_lde_p, pi_points)
        )
        with _transfer.upload("deep_prep", 8 * num_pi, 2):
            pi_vals = host_planes(
                np.array(
                    [v for (_c, _r, v) in assembly.public_inputs],
                    dtype=np.uint64,
                )
            )
    else:
        e = jnp.zeros((0, N), jnp.uint32)
        cols_pi = (e, e)
        pi_denoms = (e, e)
        ze = jnp.zeros((0,), jnp.uint32)
        pi_vals = (ze, ze)
    return {
        "inv_xz": (
            (dinv[0][0][0], dinv[0][1][0]),
            (dinv[1][0][0], dinv[1][1][0]),
        ),
        "inv_xzw": (
            (dinv[0][0][1], dinv[0][1][1]),
            (dinv[1][0][1], dinv[1][1][1]),
        ),
        "s2_cols": s2_cols,
        "inv_x": inv_x,
        "cols_pi": cols_pi,
        "pi_denoms": pi_denoms,
        "pi_vals": pi_vals,
    }


@jax.jit
def _pi_denom_sub_jit(xs_lde_p, pi_points_p):
    return limbs.sub(
        (xs_lde_p[0][None, :], xs_lde_p[1][None, :]),
        (pi_points_p[0][:, None], pi_points_p[1][:, None]),
    )


# ---------------------------------------------------------------------------
# Commit pipeline (on planes)
# ---------------------------------------------------------------------------


def streamed_commit_layers_p(mono_p, L: int, cap: int):
    """The tree layers of a commit streamed from monomial planes: the
    rate-L storage is never made (prover/streaming.py)."""
    from ..merkle import node_layers_planes
    from .streaming import streamed_leaf_digests_blocks_p

    with _span("stream.commit", columns=int(mono_p[0].shape[0])):
        digests = streamed_leaf_digests_blocks_p(mono_p, L)
        _metrics.count("merkle.streamed_commits")
        return node_layers_planes(digests, cap)


def commit_pipeline_p(values_p, L: int, cap: int, stream: bool, sm_mesh=None,
                      hasher=POSEIDON2):
    """Plane twin of prover._commit_pipeline: values over H (B, n) planes
    -> (mono planes, lde planes | None, plane tree layers)."""
    if sm_mesh is not None:
        from ..parallel.shard_sweep import commit_pipeline_sm_p

        with _span("commit_pipeline", stream=stream, sm=True, resident=True):
            return commit_pipeline_sm_p(values_p, L, cap, stream, sm_mesh)
    with _span("commit_pipeline", stream=stream, resident=True):
        mono = LN.monomial_from_values_p(values_p)
        _metrics.count("ntt.monomial_from_values")
        _metrics.count("ntt.resident_transforms")
        if stream:
            _metrics.count("merkle.resident_commits")
            return mono, None, streamed_commit_layers_p(mono, L, cap)
        lde = LN.lde_from_monomial_p(mono, L)
        _metrics.count("ntt.lde_from_monomial")
        _metrics.count("merkle.commits")
        _metrics.count("merkle.resident_commits")
        return mono, lde, hasher.commit_layers_planes(lde, cap)


# ---------------------------------------------------------------------------
# Ingest edges: data born u64 before residency enters planes ONCE per
# holder object (cached), inside an explicit limbs.edge() allowlist scope
# ---------------------------------------------------------------------------


def ingest_planes(arr, label: str):
    """Device u64 -> planes at an allowlisted ingest edge (setup oracles,
    committed trees — built u64 by generate_setup before residency)."""
    with limbs.edge(f"ingest:{label}"):
        return limbs.split(arr)


def setup_tree_planes(setup):
    """The setup's committed Merkle tree as a PlaneMerkleTree (cached on
    the setup object; cap values identical)."""
    from ..merkle import PlaneMerkleTree

    cached = getattr(setup, "_tree_planes", None)
    if cached is not None:
        return cached
    layers = [
        ingest_planes(layer, "setup_tree") for layer in setup.setup_tree.layers
    ]
    tree = PlaneMerkleTree.from_layers(layers, setup.setup_tree.cap_size)
    setup._tree_planes = tree
    return tree


# ---------------------------------------------------------------------------
# Prefetch (round-0 overlap): the plane-table half of
# prover._prefetch_challenge_independent
# ---------------------------------------------------------------------------


def prefetch_plane_tables(config, *, log_n, L, Q, n, lookups):
    from .fri import fold_challenge_tables_p, fold_schedule

    LN.PlaneNTTContext(log_n)
    log_full = log_n + (L.bit_length() - 1)
    LN.PlaneNTTContext(log_full)
    LN._lde_scale_planes(log_n, L, int(gl.MULTIPLICATIVE_GENERATOR))
    LN._lde_scale_planes(log_n, Q, int(gl.MULTIPLICATIVE_GENERATOR))
    domain_xs_brev_p(log_n, L)
    domain_xs_brev_p(log_n, Q)
    l0_brev_p(log_n, Q)
    vanishing_inv_brev_p(log_n, Q)
    omega_powers_p(log_n)
    if lookups:
        inv_xs_brev_p(log_n, L)
    num_folds = sum(
        fold_schedule(
            n, config.fri_final_degree,
            getattr(config, "fri_folding_schedule", None),
        )
    )
    fold_challenge_tables_p(log_full, num_folds)
