"""Limb-domain quotient sweep + FRI fold as fused Pallas TPU kernels.

The quotient-stage cores (`stages._build_gate_sweep`, `_cp_quotient_core`,
`_lookup_quotient_core` / `_lookup_quotient_core_general`) and the FRI fold
(`fri._fold_once_jit`) compute in `field/goldilocks.py`'s XLA-emulated
uint64 — the representation Mosaic rejects and XLA cannot fuse across
kernel boundaries. This module evaluates the SAME math on `(lo, hi)` uint32
limb planes (`field/limbs.py` + `field/limb_ops.py`), tiled over VMEM
column blocks:

- `build_coset_terms(...)`: ONE fused kernel per assembly structure that
  evaluates, per quotient-coset block, the gate-terms contribution, the
  copy-permutation terms, the lookup terms and the 1/Z_H multiply — the
  plane counterpart of `prover._u64_sweep_core`. Trace columns and the
  challenge table are array arguments (new challenges never retrace);
  challenge scalars and alpha/γ-power tables ride SMEM; every gate's
  evaluator is traced directly, the flattened Poseidon2 gate's 2,036
  operations included (at the 8-row step a 130-column sweep runs at, the
  unrolled trace beat a replay from an SMEM op table over a VMEM
  register file on the v5e: PERF.md, PR 32).
- `fri_fold_planes(...)`: one fold round f'(x^2) = (f(x)+f(-x))/2 +
  ch·(f(x)-f(-x))/(2x) on deinterleaved even/odd limb planes.
- standalone `cp_quotient` / `lookup_quotient` / `lookup_quotient_general`
  / `gate_terms_fn` wrappers over the same in-kernel cores, for per-kernel
  parity tests and `bench_micro.py`'s u64-vs-limb sweep section.

Layout: a `(B, n)` column stack is two `(B, R, 128)` uint32 planes
(R = n/128); the grid walks R in sublane tiles, so every field op is an
elementwise VPU op over `(B, T, 128)` tiles resident in VMEM. Planes in,
planes out: nothing here converts from or to uint64 (the plane pipeline,
prover/resident.py, keeps planes from the witness upload to the query
joins; the tests split and join around a kernel). Field ops are exact mod
p and keep values canonical, so outputs (and therefore digests,
checkpoints and proof bytes) are bit-identical to the u64 path
(tests/test_limb_sweep.py pins parity per kernel).

Which representation a prove runs is `utils/pallas_util.resolve_variant`'s
decision; off the TPU the kernels run in interpret mode. Shapes whose
domain is not a multiple of 128 lanes (deep FRI fold tails) run the same
limb cores as plain XLA ops.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..cs.field_like import LimbOps
from ..cs.gates.base import RowView, TermsCollector
from ..field import gl
from ..field import limb_ops as lop
from ..field import limbs
from ..utils import metrics as _metrics
from ..utils.pallas_util import imap32, pick_tile, tpu_compiler_params
from ..utils.spans import span as _span

_LANE = 128
_INV2_PAIR = limbs.const_pair((gl.P + 1) // 2)

# sweep tiles carry every oracle's column block at once; the default
# 16 MiB scoped-vmem budget is too tight for wide geometries
_CP = tpu_compiler_params(128 * 1024 * 1024)


def _interpret() -> bool:
    # interpret mode because the backend IS the CPU, never because the
    # backend failed to start: that error propagates
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Generic tiled dispatch: plane stacks in, ext plane columns out
# ---------------------------------------------------------------------------


def _pack_table(c0s, c1s):
    """Ext scalar columns (two (S,) uint64 arrays) -> (4, S) uint32 SMEM
    table, rows [c0_lo, c0_hi, c1_lo, c1_hi]."""
    l0, h0 = limbs.split(c0s)
    l1, h1 = limbs.split(c1s)
    return jnp.stack([l0, h0, l1, h1])


def _row(p, j):
    """Row j of a (B, ...) limb-plane pair as a base limb pair."""
    return p[0][j], p[1][j]


def _sc_ext(tb, j, like):
    """Scalar-table column j as an ext limb element broadcast to `like`
    (the poseidon2 _rc_row idiom: Mosaic broadcasts SMEM scalars via
    full_like, and the same indexing works on a plain array in the
    direct/interpret path)."""
    return (
        (jnp.full_like(like, tb[0, j]), jnp.full_like(like, tb[1, j])),
        (jnp.full_like(like, tb[2, j]), jnp.full_like(like, tb[3, j])),
    )


def _stack_p(rows):
    """Base plane pairs of one shape -> one stacked (B, ...) plane pair."""
    return (
        jnp.stack([r[0] for r in rows]), jnp.stack([r[1] for r in rows])
    )


def _row1(p):
    """An (n,) plane pair as a one-row (1, n) stack."""
    return p[0][None], p[1][None]


def _tiled_ext_call(body, ins, table, num_ext_out=1, interpret=None):
    """Run `body` over limb planes of the column stacks `ins`.

    ins: list of (lo, hi) u32 plane pairs of shape (B_i, n). table: (4, S)
    uint32 scalar table (SMEM). body(table, pairs) receives pairs[i] =
    (lo, hi) uint32 arrays of block shape (B_i, T, 128) and returns
    `num_ext_out` ext limb elements of shape (T, 128). Returns that many
    ext limb pairs ((lo, hi), (lo, hi)) of (n,) planes.

    Domains that don't tile (n % 128 != 0) run `body` directly on
    (B_i, 1, n) planes — same code, plain XLA."""
    n = int(ins[0][0].shape[-1])
    if interpret is None:
        interpret = _interpret()

    def _planes(x, shape):
        return x[0].reshape(shape), x[1].reshape(shape)

    if n % _LANE != 0:
        pairs = [_planes(x, (int(x[0].shape[0]), 1, n)) for x in ins]
        outs = body(table, pairs)
        return tuple(
            (
                (c0[0].reshape(n), c0[1].reshape(n)),
                (c1[0].reshape(n), c1[1].reshape(n)),
            )
            for (c0, c1) in outs
        )
    R = n // _LANE
    total_rows = sum(int(x[0].shape[0]) for x in ins) + 2 * num_ext_out
    budget_rows = max(8, (4 << 20) // max(total_rows * _LANE * 8, 1))
    tile = pick_tile(R, budget_rows)
    grid = (R // tile,)

    in_specs = [
        pl.BlockSpec(
            table.shape, imap32(lambda *_: (0,) * table.ndim),
            memory_space=pltpu.SMEM,
        )
    ]
    args = [table]
    for x in ins:
        B = int(x[0].shape[0])
        lo, hi = _planes(x, (B, R, _LANE))
        spec = pl.BlockSpec(
            (B, tile, _LANE),
            imap32(lambda r: (0, r, 0)),
            memory_space=pltpu.VMEM,
        )
        in_specs += [spec, spec]
        args += [lo, hi]
    out_spec = pl.BlockSpec(
        (tile, _LANE), imap32(lambda r: (r, 0)), memory_space=pltpu.VMEM
    )
    out_shape = [
        jax.ShapeDtypeStruct((R, _LANE), jnp.uint32)
    ] * (4 * num_ext_out)
    n_in = len(ins)

    def kernel(*refs):
        tb = refs[0]
        in_refs = refs[1 : 1 + 2 * n_in]
        out_refs = refs[1 + 2 * n_in :]
        pairs = [
            (in_refs[2 * i][:], in_refs[2 * i + 1][:]) for i in range(n_in)
        ]
        outs = body(tb, pairs)
        for k, (c0, c1) in enumerate(outs):
            out_refs[4 * k][:] = c0[0]
            out_refs[4 * k + 1][:] = c0[1]
            out_refs[4 * k + 2][:] = c1[0]
            out_refs[4 * k + 3][:] = c1[1]

    planes = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=[out_spec] * (4 * num_ext_out),
        interpret=interpret,
        compiler_params=None if interpret else _CP,
    )(*args)
    return tuple(
        (
            (planes[4 * k].reshape(n), planes[4 * k + 1].reshape(n)),
            (planes[4 * k + 2].reshape(n), planes[4 * k + 3].reshape(n)),
        )
        for k in range(num_ext_out)
    )


# ---------------------------------------------------------------------------
# In-kernel cores (limb mirrors of prover/stages.py)
# ---------------------------------------------------------------------------


def _cp_terms(
    tb, like, s2_p, zs_p, copy_p, sigma_p, xs, l0,
    a_col, beta_col, gamma_col, chunks, non_residues, num_partials,
):
    """Copy-permutation quotient terms (stages._cp_quotient_core), alpha
    powers at scalar-table columns a_col.."""
    b = _sc_ext(tb, beta_col, like)
    g = _sc_ext(tb, gamma_col, like)
    z = (_row(s2_p, 0), _row(s2_p, 1))
    z_shift = (_row(zs_p, 0), _row(zs_p, 1))
    partials = [
        (_row(s2_p, 2 + 2 * j), _row(s2_p, 3 + 2 * j))
        for j in range(num_partials)
    ]
    acc = None
    zm1 = (limbs.sub(z[0], lop.ones_like(z[0])), z[1])
    t0 = (limbs.mul(zm1[0], l0), limbs.mul(zm1[1], l0))
    acc = lop.ext_accumulate(acc, t0, _sc_ext(tb, a_col, like))
    lhs_seq = partials + [z_shift]
    rhs_seq = [z] + partials
    for j, chunk in enumerate(chunks):
        num_p = den_p = None
        for col in chunk:
            w = _row(copy_p, col)
            kx = limbs.mul_const(xs, limbs.const_pair(non_residues[col]))
            num = (
                limbs.add(limbs.add(w, limbs.mul(kx, b[0])), g[0]),
                limbs.add(limbs.mul(kx, b[1]), g[1]),
            )
            s = _row(sigma_p, col)
            den = (
                limbs.add(limbs.add(w, limbs.mul(s, b[0])), g[0]),
                limbs.add(limbs.mul(s, b[1]), g[1]),
            )
            num_p = num if num_p is None else limbs.ext_mul(num_p, num)
            den_p = den if den_p is None else limbs.ext_mul(den_p, den)
        term = lop.ext_sub(
            limbs.ext_mul(lhs_seq[j], den_p), limbs.ext_mul(rhs_seq[j], num_p)
        )
        acc = lop.ext_accumulate(acc, term, _sc_ext(tb, a_col + 1 + j, like))
    return acc


def _lookup_terms(
    tb, like, s2_p, lk_cols_p, tid, table_p, mult, sel,
    a_col, gpow_col, ab_off, num_subargs, width, general,
):
    """Lookup quotient terms (stages._lookup_quotient_core and its
    general-columns twin — `sel` is the marker selector in general mode,
    None in specialized mode where the subtrahend is the constant 1)."""
    gpow = [_sc_ext(tb, gpow_col + j, like) for j in range(width + 1)]
    beta = _sc_ext(tb, gpow_col + width + 1, like)
    acc = None
    for i in range(num_subargs):
        a_i = (
            _row(s2_p, ab_off + 2 * i),
            _row(s2_p, ab_off + 2 * i + 1),
        )
        cols = [_row(lk_cols_p, i * width + j) for j in range(width)]
        den = lop.aggregate_columns(cols, tid, gpow, beta)
        term = limbs.ext_mul(a_i, den)
        if general:
            term = (limbs.sub(term[0], sel), term[1])
        else:
            term = (limbs.sub(term[0], lop.ones_like(term[0])), term[1])
        acc = lop.ext_accumulate(acc, term, _sc_ext(tb, a_col + i, like))
    b_poly = (
        _row(s2_p, ab_off + 2 * num_subargs),
        _row(s2_p, ab_off + 2 * num_subargs + 1),
    )
    t_den = lop.aggregate_columns(
        [_row(table_p, j) for j in range(width)],
        _row(table_p, width),
        gpow,
        beta,
    )
    term = limbs.ext_mul(b_poly, t_den)
    term = (limbs.sub(term[0], mult), term[1])
    return lop.ext_accumulate(
        acc, term, _sc_ext(tb, a_col + num_subargs, like)
    )


def _selector_from_consts(const_p, path):
    """Product over path bits of c_b or (1 - c_b) (stages.selector_poly_lde);
    None = constant 1 (single-gate circuits / empty marker path)."""
    sel = None
    for b, bit in enumerate(path):
        col = _row(const_p, b)
        f = col if bit else limbs.sub(lop.ones_like(col), col)
        sel = f if sel is None else limbs.mul(sel, f)
    return sel


def _gate_terms(tb, like, copy_p, wit_p, const_p, plan, a_col):
    """Gate-terms contribution (stages._build_gate_sweep core): per gate,
    selector-masked sum over instances/terms of alpha^t·term, every gate's
    evaluator traced directly over limb pairs. Returns (acc_ext_or_None,
    alpha powers consumed)."""
    t = 0
    acc = None
    for gate, path, reps in plan:
        sel = _selector_from_consts(const_p, path)
        gate_acc = None
        # the seconds a gate's terms take to trace show in a recording of
        # the library's first lowering (a permutation-sized gate: seconds)
        with _span(
            "gate_kernel_trace", gate=gate.name, reps=reps,
            terms=gate.num_terms,
        ):
            for inst in range(reps):
                row = RowView(
                    lambda i, o=inst * gate.principal_width: _row(
                        copy_p, o + i
                    ),
                    lambda i, o=inst * gate.witness_width: _row(wit_p, o + i),
                    lambda i, o=len(path): _row(const_p, o + i),
                )
                dst = TermsCollector()
                gate.evaluate(LimbOps, row, dst)
                assert len(dst.terms) == gate.num_terms, gate.name
                for term in dst.terms:
                    gate_acc = lop.accumulate(
                        gate_acc, term, _sc_ext(tb, a_col + t, like)
                    )
                    t += 1
        if gate_acc is not None:
            if sel is not None:
                gate_acc = (
                    limbs.mul(gate_acc[0], sel),
                    limbs.mul(gate_acc[1], sel),
                )
            acc = gate_acc if acc is None else lop.ext_add(acc, gate_acc)
    return acc, t


def _ext_scalar_cols(s):
    """Ext scalar as two (1,) uint64 arrays (table columns)."""
    return (
        jnp.asarray(s[0], jnp.uint64).reshape(1),
        jnp.asarray(s[1], jnp.uint64).reshape(1),
    )


# ---------------------------------------------------------------------------
# The fused per-coset terms kernel (prover._coset_sweep_fn's limb body)
# ---------------------------------------------------------------------------


def build_coset_terms(gates, selector_paths, geometry, lk_ctx, non_residues):
    """One fused sweep kernel per assembly structure: gate terms +
    copy-permutation terms + lookup terms + 1/Z_H, per quotient-coset
    block. Alpha-power consumption order matches the u64 body exactly
    (gates, then cp, then lookups) — same per-TERM challenge sequence the
    verifier replays. Returns call(wit_p, setup_p, s2_p, zs_p, xs_p, l0_p,
    zh_p, table) -> (t0, t1) plane pairs, traceable inside the outer
    per-coset jit."""
    from .stages import gate_sweep_plan

    (
        lookups, lk_mode, R_args, width, num_partials, chunks,
        total_alpha_terms, Cg, Ct, W, K, M, mk_path,
    ) = lk_ctx
    non_residues = tuple(int(k) for k in non_residues)
    plan = gate_sweep_plan(gates, selector_paths, geometry)
    total_gate_terms = sum(
        reps * gate.num_terms for gate, _path, reps in plan
    )
    expected = (
        total_gate_terms + 1 + len(chunks) + ((R_args + 1) if lookups else 0)
    )
    assert expected == total_alpha_terms, (expected, total_alpha_terms)
    ab_off = 2 + 2 * num_partials
    _metrics.count("pallas_sweep.builds")

    def body(tb, pairs, A):
        wit_p, setup_p, s2_p, zs_p, xs_p, l0_p, zh_p = pairs
        like = xs_p[0][0]
        xs = _row(xs_p, 0)
        l0 = _row(l0_p, 0)
        zh = _row(zh_p, 0)
        copy_p = (wit_p[0][:Ct], wit_p[1][:Ct])
        gate_wit_p = (
            (wit_p[0][Ct : Ct + W], wit_p[1][Ct : Ct + W]) if W else None
        )
        sigma_p = (setup_p[0][:Ct], setup_p[1][:Ct])
        const_p = (setup_p[0][Ct : Ct + K], setup_p[1][Ct : Ct + K])
        table_p = (setup_p[0][Ct + K :], setup_p[1][Ct + K :])
        t = 0
        acc = None
        if total_gate_terms:
            gcopy_p = (copy_p[0][:Cg], copy_p[1][:Cg])
            acc, t = _gate_terms(
                tb, like, gcopy_p, gate_wit_p, const_p, plan, a_col=0
            )
            assert t == total_gate_terms
        cp = _cp_terms(
            tb, like, s2_p, zs_p, copy_p, sigma_p, xs, l0,
            a_col=t, beta_col=A, gamma_col=A + 1,
            chunks=chunks, non_residues=non_residues,
            num_partials=num_partials,
        )
        acc = cp if acc is None else lop.ext_add(acc, cp)
        t += 1 + len(chunks)
        if lookups:
            mult = _row(wit_p, Ct + W)
            if lk_mode == "specialized":
                lk_cols_p = (copy_p[0][Cg:], copy_p[1][Cg:])
                tid = _row(const_p, K - 1)
                sel = None
            else:
                lk_cols_p = (copy_p[0][:Cg], copy_p[1][:Cg])
                tid = _row(const_p, len(mk_path))
                sel = _selector_from_consts(const_p, mk_path)
                if sel is None:
                    sel = lop.ones_like(like)
            lk = _lookup_terms(
                tb, like, s2_p, lk_cols_p, tid, table_p, mult, sel,
                a_col=t, gpow_col=A + 4, ab_off=ab_off,
                num_subargs=R_args, width=width,
                general=(lk_mode != "specialized"),
            )
            acc = lop.ext_add(acc, lk)
        return ((limbs.mul(acc[0], zh), limbs.mul(acc[1], zh)),)

    # scalar-table columns past the alpha block: [beta, gamma, lkb, lkg]
    # and, with lookups, [γ'^0..γ'^width, beta'] (beta' rides right after
    # the γ powers, see _lookup_terms)
    _extra_cols = 4 + ((width + 2) if lookups else 0)

    def call(wit_p, setup_p, s2_p, zs_p, xs_p, l0_p, zh_p, table):
        """Every oracle stack arrives as a (lo, hi) u32 plane pair and the
        terms come back as an ext plane pair. `table` is the (4, S) u32
        scalar table built on HOST from the transcript challenges
        (resident.sweep_table_np, in the column layout above)."""
        A = int(table.shape[1]) - _extra_cols
        (out,) = _tiled_ext_call(
            partial(body, A=A),
            [
                wit_p, setup_p, s2_p, zs_p,
                _row1(xs_p), _row1(l0_p), _row1(zh_p),
            ],
            table,
        )
        return out

    return call


# ---------------------------------------------------------------------------
# Standalone per-family wrappers (parity tests + bench_micro sweep section)
# ---------------------------------------------------------------------------


def cp_quotient(
    z_p, z_shift_p, partials_p, copy_p, sigma_p, xs_p, l0_p,
    b, g, a0, a1, chunks, non_residues, interpret=None,
):
    """Plane twin of stages._cp_quotient_core: the same arguments with
    every column (or (C, n) column stack) a (lo, hi) plane pair; ext
    columns are pairs of those. `b`, `g`, `a0`, `a1` stay uint64 scalars
    (the small challenge table is packed here)."""
    num_partials = len(partials_p)
    s2_rows = [z_p[0], z_p[1]]
    for p in partials_p:
        s2_rows += [p[0], p[1]]
    A = int(a0.shape[0])
    bc0, bc1 = _ext_scalar_cols(b)
    gc0, gc1 = _ext_scalar_cols(g)
    table = _pack_table(
        jnp.concatenate([a0, bc0, gc0]), jnp.concatenate([a1, bc1, gc1])
    )
    chunks = tuple(tuple(c) for c in chunks)
    non_residues = tuple(int(k) for k in non_residues)

    def body(tb, pairs):
        s2_p, zs_p, copy_pp, sigma_pp, xs_pp, l0_pp = pairs
        like = xs_pp[0][0]
        acc = _cp_terms(
            tb, like, s2_p, zs_p, copy_pp, sigma_pp,
            _row(xs_pp, 0), _row(l0_pp, 0),
            a_col=0, beta_col=A, gamma_col=A + 1,
            chunks=chunks, non_residues=non_residues,
            num_partials=num_partials,
        )
        return (acc,)

    (out,) = _tiled_ext_call(
        body,
        [
            _stack_p(s2_rows), _stack_p([z_shift_p[0], z_shift_p[1]]),
            copy_p, sigma_p, _row1(xs_p), _row1(l0_p),
        ],
        table,
        interpret=interpret,
    )
    return out


def _lookup_quotient_shared(
    a_ps, b_p, cols_p, tid_p, table_p, mult_p, sel_p,
    b, g, a0, a1, num_subargs, width, general, interpret,
):
    s2_rows = []
    for a in a_ps:
        s2_rows += [a[0], a[1]]
    s2_rows += [b_p[0], b_p[1]]
    from .stages import _ext_powers_traced

    gpow = _ext_powers_traced(g, width + 1)
    bc0, bc1 = _ext_scalar_cols(b)
    A = int(a0.shape[0])
    table = _pack_table(
        jnp.concatenate([a0] + [jnp.reshape(p[0], (1,)) for p in gpow] + [bc0]),
        jnp.concatenate([a1] + [jnp.reshape(p[1], (1,)) for p in gpow] + [bc1]),
    )
    ins = [_stack_p(s2_rows), cols_p, _row1(tid_p), table_p, _row1(mult_p)]
    if general:
        ins.append(_row1(sel_p))

    def body(tb, pairs):
        if general:
            s2_pp, cols_pp, tid_pp, table_pp, mult_pp, sel_pp = pairs
            sel = _row(sel_pp, 0)
        else:
            s2_pp, cols_pp, tid_pp, table_pp, mult_pp = pairs
            sel = None
        like = tid_pp[0][0]
        acc = _lookup_terms(
            tb, like, s2_pp, cols_pp, _row(tid_pp, 0), table_pp,
            _row(mult_pp, 0), sel,
            a_col=0, gpow_col=A, ab_off=0,
            num_subargs=num_subargs, width=width, general=general,
        )
        return (acc,)

    (out,) = _tiled_ext_call(body, ins, table, interpret=interpret)
    return out


def lookup_quotient(
    a_ps, b_p, lookup_cols_p, table_id_p, table_p, mult_p,
    b, g, a0, a1, num_repetitions, width, interpret=None,
):
    """Plane twin of stages._lookup_quotient_core (columns as plane
    pairs, challenges as uint64 scalars)."""
    return _lookup_quotient_shared(
        a_ps, b_p, lookup_cols_p, table_id_p, table_p, mult_p,
        None, b, g, a0, a1, int(num_repetitions), int(width),
        general=False, interpret=interpret,
    )


def lookup_quotient_general(
    a_ps, b_p, gen_cols_p, tid_p, table_p, mult_p, sel_p,
    b, g, a0, a1, num_subargs, width, interpret=None,
):
    """Plane twin of stages._lookup_quotient_core_general."""
    return _lookup_quotient_shared(
        a_ps, b_p, gen_cols_p, tid_p, table_p, mult_p,
        sel_p, b, g, a0, a1, int(num_subargs), int(width),
        general=True, interpret=interpret,
    )


def gate_terms_fn(gates, selector_paths, geometry, interpret=None):
    """Plane twin of stages._build_gate_sweep: returns fn(copy_p, wit_p,
    const_p, table) -> ext plane pair over plane stacks and a prebuilt
    (4, S) u32 alpha table (`_pack_table(a0, a1)`)."""
    from .stages import gate_sweep_plan

    plan = gate_sweep_plan(
        tuple(gates), tuple(tuple(p) for p in selector_paths), geometry
    )

    def fn(copy_p, wit_p, const_p, table):
        ins = [copy_p]
        has_wit = wit_p is not None
        if has_wit:
            ins.append(wit_p)
        ins.append(const_p)

        def body(tb, pairs):
            if has_wit:
                copy_pp, wit_pp, const_pp = pairs
            else:
                copy_pp, const_pp = pairs
                wit_pp = None
            like = copy_pp[0][0]
            acc, _t = _gate_terms(
                tb, like, copy_pp, wit_pp, const_pp, plan, a_col=0
            )
            return (acc,)

        (out,) = _tiled_ext_call(body, ins, table, interpret=interpret)
        return out

    return fn


# ---------------------------------------------------------------------------
# FRI fold
# ---------------------------------------------------------------------------


def _fold_body(tb, pairs):
    quad, inv = pairs
    like = quad[0][0]
    a = (_row(quad, 0), _row(quad, 1))
    bm = (_row(quad, 2), _row(quad, 3))
    invx = _row(inv, 0)
    s = lop.ext_add(a, bm)
    d = lop.ext_sub(a, bm)
    d_over_x = (limbs.mul(d[0], invx), limbs.mul(d[1], invx))
    ch = _sc_ext(tb, 0, like)
    t = lop.ext_add(s, limbs.ext_mul(d_over_x, ch))
    return (
        (
            limbs.mul_const(t[0], _INV2_PAIR),
            limbs.mul_const(t[1], _INV2_PAIR),
        ),
    )


def fri_fold_planes(values_p, table, inv_x_p, interpret=None):
    """Plane twin of fri._fold_once_jit: one fold round over the
    bit-reversed codeword (pairs adjacent). `values_p` is an ext plane pair
    over the round domain, `table` the (4, 1) u32 challenge table,
    `inv_x_p` the 1/x plane pair at pair positions. Returns the half-size
    ext plane pair, so a fold CHAIN stays on planes across rounds. The
    even/odd deinterleave happens outside the kernel (one strided XLA
    slice) so the kernel body is fully elementwise."""
    c0p, c1p = values_p
    quad = (
        jnp.stack([c0p[0][0::2], c1p[0][0::2], c0p[0][1::2], c1p[0][1::2]]),
        jnp.stack([c0p[1][0::2], c1p[1][0::2], c0p[1][1::2], c1p[1][1::2]]),
    )
    (out,) = _tiled_ext_call(
        _fold_body, [quad, _row1(inv_x_p)], table, interpret=interpret
    )
    return out
