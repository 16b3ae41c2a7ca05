"""Prover stage computations: copy-permutation grand product and the
gate-constraint quotient sweep.

Counterparts: `/root/reference/src/cs/implementations/copy_permutation.rs`
(pointwise rational accumulation :30, shifted grand product :367, partial
products chunked by degree :525, quotient terms :1000) and the general-purpose
gate sweep of `prover.rs:813-1130`.

TPU-first shape: everything is computed on whole (…, n) or (…, lde·n) arrays;
the grand product is ONE `jax.lax.associative_scan` over the row axis (the
scan counterpart of the reference's chunked sequential products), and the gate
sweep evaluates every allowed gate's evaluator over the entire LDE domain at
once, masked by its selector-path polynomial — the "static masked evaluation"
form that suits SIMD/MXU hardware.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field import gl
from ..field import extension as ext_f
from ..field import goldilocks as gf
from ..ntt import (
    bitreverse_indices,
    get_ntt_context,
    lde_from_monomial,
    monomial_from_values,
    powers_device,
)
from ..cs.field_like import ArrayOps
from ..cs.gates.base import RowView, TermsCollector
from ..utils import metrics as _metrics
from ..utils.spans import span as _span


def ext_scalar(s):
    """Host (int, int) ext scalar -> pair of u64 array scalars; jax-array
    components (fused-round tracing) pass through unchanged."""
    a, b = s[0], s[1]
    if isinstance(a, jax.Array):
        return (a, b)
    return (jnp.uint64(int(a)), jnp.uint64(int(b)))


def chunk_columns(num_cols: int, max_degree: int):
    """Split copy columns into chunks of size <= max_degree (the relation
    degree cap; reference copy_permutation.rs:525)."""
    cs = max(1, max_degree)
    return [list(range(i, min(i + cs, num_cols))) for i in range(0, num_cols, cs)]


@partial(jax.jit, static_argnums=(6,))
def _all_chunk_num_den(copy_vals, sigma_vals, ks, xs, b, g, chunks):
    """Per-chunk products of numerator (w + β·k·x + γ) and denominator
    (w + β·σ + γ), ALL chunks in one dispatch -> (num_chunks, n) stacked
    ext pairs.

    The loop over the uniform-width chunk prefix runs under `lax.scan`, so
    the traced module holds ONE chunk's field ops instead of every chunk's
    (the fully unrolled form's remote compile was 251 s on the 2^16 SHA
    geometry — BASELINE.md round 4); a trailing ragged chunk unrolls into
    the same graph. chunk_columns' chunks are contiguous column ranges, so
    the blocked view is a reshape, never a gather. The denominator
    inversion happens OUTSIDE this jit: batch_inverse must stay a
    top-level jit boundary — inlining its Fermat-chain into larger
    XLA:CPU modules has produced never-terminating executables on this
    backend (miscompile class, not a slowness issue)."""
    n = copy_vals.shape[-1]
    flat = [col for c in chunks for col in c]
    assert flat == list(range(len(flat))), chunks
    w = len(chunks[0])
    K_full = sum(1 for c in chunks if len(c) == w)
    assert all(len(c) == w for c in chunks[:K_full]), chunks
    assert len(chunks) - K_full <= 1, chunks

    def _prod_terms(cv, sv, kv):
        # cv/sv: (w', n) column blocks; kv: (w',) non-residues
        num_p = den_p = None
        for j in range(cv.shape[0]):
            wcol = cv[j]
            kx = gf.mul(xs, kv[j])
            num = (
                gf.add(gf.add(wcol, gf.mul(kx, b[0])), g[0]),
                gf.add(gf.mul(kx, b[1]), g[1]),
            )
            s = sv[j]
            den = (
                gf.add(gf.add(wcol, gf.mul(s, b[0])), g[0]),
                gf.add(gf.mul(s, b[1]), g[1]),
            )
            num_p = num if num_p is None else ext_f.mul(num_p, num)
            den_p = den if den_p is None else ext_f.mul(den_p, den)
        return num_p, den_p

    def body(carry, blk):
        num_p, den_p = _prod_terms(*blk)
        return carry, (num_p[0], num_p[1], den_p[0], den_p[1])

    Cw = K_full * w
    _, (n0, n1, d0, d1) = jax.lax.scan(
        body,
        None,
        (
            copy_vals[:Cw].reshape(K_full, w, n),
            sigma_vals[:Cw].reshape(K_full, w, n),
            ks[:Cw].reshape(K_full, w),
        ),
    )
    if len(chunks) > K_full:
        num_p, den_p = _prod_terms(copy_vals[Cw:], sigma_vals[Cw:], ks[Cw:])
        n0 = jnp.concatenate([n0, num_p[0][None]])
        n1 = jnp.concatenate([n1, num_p[1][None]])
        d0 = jnp.concatenate([d0, den_p[0][None]])
        d1 = jnp.concatenate([d1, den_p[1][None]])
    return (n0, n1), (d0, d1)


@jax.jit
def _z_and_partials(num_all, den_inv_all):
    """Chunk ratios -> full-row ratio -> exclusive prefix product z ->
    cumulative partial products, one compiled graph. Inputs are
    (num_chunks, n) stacked ext pairs (den already inverted)."""
    K = num_all[0].shape[0]
    ratios = ext_f.mul(num_all, den_inv_all)
    full = (ratios[0][0], ratios[1][0])
    for j in range(1, K):
        full = ext_f.mul(full, (ratios[0][j], ratios[1][j]))
    incl = ext_f.prefix_product(full)
    one = jnp.ones((1,), jnp.uint64)
    zero = jnp.zeros((1,), jnp.uint64)
    z = (
        jnp.concatenate([one, incl[0][:-1]]),
        jnp.concatenate([zero, incl[1][:-1]]),
    )
    parts0, parts1 = [], []
    acc = z
    for j in range(K - 1):
        acc = ext_f.mul(acc, (ratios[0][j], ratios[1][j]))
        parts0.append(acc[0])
        parts1.append(acc[1])
    if parts0:
        return z, (jnp.stack(parts0), jnp.stack(parts1))
    return z, (jnp.zeros((0,) + z[0].shape, jnp.uint64),) * 2


def compute_copy_permutation_stage2(
    copy_vals, sigma_vals, non_residues, beta, gamma, max_degree
):
    """Grand product z and partial products over H.

    copy_vals/sigma_vals: (C, n) device base arrays (natural row order);
    beta/gamma host ext scalars. Returns (z_pair, partial_pairs, chunks)
    where z(w^0)=1 and for the last chunk relation
    z(w*x)·prod_den_last = p_last·prod_num_last holds.

    Deliberately NOT one fused jit: XLA:CPU optimization time is superlinear
    in module size, so this sequences a handful of small jitted kernels
    (per-chunk ratio, batch inverse, prefix product) instead.
    """
    C, n = copy_vals.shape
    ctx = get_ntt_context(n.bit_length() - 1)
    xs = powers_device(ctx.omega, n)  # w^r natural order
    b = ext_scalar(beta)
    g = ext_scalar(gamma)
    chunks = chunk_columns(C, max_degree)
    # a real h2d upload seam (the fused path's equivalent rides
    # prover._dev_cached): keep the transfer ledger complete
    ks = _metrics.count_upload(
        jnp.asarray(np.array([int(k) for k in non_residues], dtype=np.uint64))
    )

    _metrics.count("stage2.chunk_scans")
    with _span("stage2_grand_product"):
        num_all, den_all = _all_chunk_num_den(
            copy_vals, sigma_vals, ks, xs, b, g,
            tuple(tuple(c) for c in chunks),
        )
        # ONE stacked inversion for every chunk denominator
        den_inv_all = ext_f.batch_inverse(den_all)
        z, partials_stacked = _z_and_partials(num_all, den_inv_all)
    partials = [
        (partials_stacked[0][j], partials_stacked[1][j])
        for j in range(len(chunks) - 1)
    ]
    return z, partials, chunks


class LdeRowView:
    """RowView over flattened LDE arrays for one gate-instance chunk."""

    def __init__(self, copy_lde_flat, wit_lde_flat, const_lde_flat, var_off, wit_off, const_off):
        self._c = copy_lde_flat
        self._w = wit_lde_flat
        self._k = const_lde_flat
        self._vo = var_off
        self._wo = wit_off
        self._ko = const_off

    def v(self, i):
        return self._c[self._vo + i]

    def w(self, i):
        return self._w[self._wo + i]

    def c(self, i):
        return self._k[self._ko + i]


def selector_poly_lde(const_lde_flat, path):
    """Product over path bits of c_b or (1 - c_b), over the LDE domain."""
    sel = None
    one = jnp.uint64(1)
    for b, bit in enumerate(path):
        col = const_lde_flat[b]
        f = col if bit else gf.sub(jnp.broadcast_to(one, col.shape), col)
        sel = f if sel is None else gf.mul(sel, f)
    return sel  # None = constant 1 (single-gate circuits)


class AlphaPows:
    """Challenge-power supply for the quotient sweep: a device array of ext
    powers consumed sequentially (so jitted stages take them as array
    arguments and new challenges never retrace)."""

    def __init__(self, alpha, count: int):
        from ..ntt import ext_powers_device

        cap = 1
        while cap < max(count, 1):
            cap *= 2
        self.p0, self.p1 = ext_powers_device(alpha, cap)
        self.count = count
        self.cursor = 0

    @classmethod
    def from_arrays(cls, p0, p1, count: int) -> "AlphaPows":
        """Wrap an existing device power table (fused-round tracing: the
        table is built once outside and passed as an array argument)."""
        self = cls.__new__(cls)
        self.p0, self.p1 = p0, p1
        self.count = count
        self.cursor = 0
        return self

    def take(self, k: int):
        """(k,)-shaped ext power pair slice. Over-consumption is a prover
        term-count bug; fail loudly (a silent short slice would corrupt the
        challenge combination into an invalid proof)."""
        assert self.cursor + k <= self.count, (
            f"AlphaPows over-consumed: {self.cursor}+{k} > {self.count}"
        )
        s = slice(self.cursor, self.cursor + k)
        self.cursor += k
        return (self.p0[s], self.p1[s])


def accumulate_ext(acc, term_base, ch):
    """acc += ch * term for base-field term arrays, ext array scalar ch."""
    t0 = gf.mul(term_base, ch[0])
    t1 = gf.mul(term_base, ch[1])
    if acc is None:
        return (t0, t1)
    return (gf.add(acc[0], t0), gf.add(acc[1], t1))


def accumulate_ext_ext(acc, term_ext, ch):
    t = ext_f.mul(term_ext, ch)
    if acc is None:
        return t
    return ext_f.add(acc, t)


def num_gate_sweep_terms(assembly) -> int:
    return sum(
        g.num_repetitions(assembly.geometry) * g.num_terms
        for g in assembly.gates
        if g.num_terms
    )


def gate_terms_contribution(
    assembly, selector_paths, copy_lde_flat, wit_lde_flat, const_lde_flat,
    alpha_pows: AlphaPows,
):
    """Sum over gates/instances/terms of alpha^t * selector_g * term.

    One jitted graph per assembly structure (cached on the assembly object);
    the trace columns and alpha powers are array arguments.
    """
    total = num_gate_sweep_terms(assembly)
    if total == 0:
        return None
    a0, a1 = alpha_pows.take(total)
    fn = getattr(assembly, "_gate_sweep_jit", None)
    if fn is None:
        fn = _build_gate_sweep(
            tuple(assembly.gates), tuple(tuple(p) for p in selector_paths),
            assembly.geometry,
        )
        assembly._gate_sweep_jit = fn
    return fn(copy_lde_flat, wit_lde_flat, const_lde_flat, a0, a1)


def gate_sweep_plan(gates, selector_paths, geometry):
    """Static per-gate schedule of the limb-domain Pallas kernel builder
    (prover/pallas_sweep.py): one (gate, selector_path, repetitions) tuple
    per gate with quotient terms, in gate order — the order in which the
    u64 sweep below consumes terms (and therefore alpha powers): both
    backends MUST keep to it or challenges desync."""
    return [
        (gate, tuple(selector_paths[gid]), gate.num_repetitions(geometry))
        for gid, gate in enumerate(gates)
        if gate.num_terms
    ]


def gate_sweep_ops_per_row(gates, geometry) -> int:
    """Field operations one row costs the gate sweep: each gate's captured
    program (additions, subtractions, multiplications, doublings,
    negations, one each) times its repetitions, summed over the gates with
    quotient terms. The accumulation by the alpha powers and the selector
    products are not in it. Static: a function of the gate set."""
    from ..cs.gate_capture import program_for

    return sum(
        gate.num_repetitions(geometry) * len(program_for(gate).ops)
        for gate in gates
        if gate.num_terms
    )


def _build_gate_sweep(gates, selector_paths, geometry):
    from ..cs.gate_capture import packed_program_for, scan_evaluate

    _metrics.count("gate_sweep.builds")

    def core(copy_lde_flat, wit_lde_flat, const_lde_flat, a0, a1):
        t = 0
        acc = None
        for gid, gate in enumerate(gates):
            if gate.num_terms == 0:
                continue
            sel = selector_poly_lde(const_lde_flat, selector_paths[gid])
            reps = gate.num_repetitions(geometry)
            # permutation-sized gate programs replay under ONE lax.scan
            # (constant graph size) instead of unrolling thousands of field
            # ops into the trace — the recursion circuit's flattened
            # Poseidon2 gate made the unrolled sweep uncompilable
            packed = packed_program_for(gate)
            gate_acc = None
            with _span(
                "gate_kernel_trace", gate=gate.name, reps=reps,
                terms=gate.num_terms, packed=packed is not None,
            ):
                for inst in range(reps):
                    row = LdeRowView(
                        copy_lde_flat,
                        wit_lde_flat,
                        const_lde_flat,
                        inst * gate.principal_width,
                        inst * gate.witness_width,
                        # variable-depth selectors: a gate's constants
                        # start right after ITS OWN path bits
                        len(selector_paths[gid]),
                    )
                    if packed is not None:
                        terms = scan_evaluate(packed, row)
                    else:
                        dst = TermsCollector()
                        gate.evaluate(ArrayOps, row, dst)
                        terms = dst.terms
                    assert len(terms) == gate.num_terms, gate.name
                    for term in terms:
                        gate_acc = accumulate_ext(
                            gate_acc, term, (a0[t], a1[t])
                        )
                        t += 1
            if gate_acc is not None:
                if sel is not None:
                    gate_acc = (
                        gf.mul(gate_acc[0], sel), gf.mul(gate_acc[1], sel)
                    )
                acc = gate_acc if acc is None else ext_f.add(acc, gate_acc)
        return acc

    return jax.jit(core)


def _ext_powers_traced(g, count: int):
    """[1, g, ..., g^(count-1)] as host-loop of traced ext scalar muls."""
    pows = [(jnp.uint64(1), jnp.uint64(0))]
    for _ in range(count - 1):
        pows.append(ext_f.mul(pows[-1], g))
    return pows


def aggregate_lookup_columns(cols, table_id_col, gpow, beta):
    """Σ_j γ^j·col_j (+ γ^w·table_id) + β over whole base arrays -> ext pair.

    cols: list of (n,)-or-(N,) base arrays; table_id_col: same-shape base
    array or None; gpow: list of ext array scalars [1, γ, γ², …]; beta: ext
    array scalar. Returns the log-derivative denominator before inversion
    (reference lookup_argument_in_ext.rs:424 'aggregated_lookup_columns').
    """
    acc0 = jnp.broadcast_to(beta[0], cols[0].shape)
    acc1 = jnp.broadcast_to(beta[1], cols[0].shape)
    seq = list(cols) + ([table_id_col] if table_id_col is not None else [])
    for j, col in enumerate(seq):
        acc0 = gf.add(acc0, gf.mul(col, gpow[j][0]))
        acc1 = gf.add(acc1, gf.mul(col, gpow[j][1]))
    return (acc0, acc1)


def compute_lookup_polys(
    lookup_cols, table_id_col, table_cols, multiplicities,
    lookup_beta, lookup_gamma, num_repetitions, width,
):
    """A_i and B polys over H (reference compute_lookup_poly_pairs_specialized,
    lookup_argument_in_ext.rs:320).

    lookup_cols: (R*w, n) base device array of the specialized columns;
    table_id_col: (n,) base; table_cols: (w+1, n) stacked tables incl. id;
    multiplicities: (n,). Returns (a_polys list of ext pairs, b_poly ext pair):
      A_i(x) = 1 / (Σ_j γ^j·w_{i,j}(x) + γ^w·table_id(x) + β)
      B(x)   = M(x) / (Σ_j γ^j·t_j(x) + γ^w·t_id(x) + β)
    """
    b = ext_scalar(lookup_beta)
    g = ext_scalar(lookup_gamma)
    R = int(num_repetitions)
    _metrics.count("stage2.lookup_denominator_builds")
    dens = _lookup_denominators(
        lookup_cols, table_id_col, table_cols, b, g, R, int(width),
    )
    # ONE stacked inversion for all R+1 denominators (batch_inverse stays a
    # top-level jit boundary; see _all_chunk_num_den)
    inv = ext_f.batch_inverse(dens)
    a_polys = [(inv[0][i], inv[1][i]) for i in range(R)]
    t_inv = (inv[0][R], inv[1][R])
    b_poly = (gf.mul(t_inv[0], multiplicities), gf.mul(t_inv[1], multiplicities))
    return a_polys, b_poly


@partial(jax.jit, static_argnums=(5, 6))
def _lookup_denominators(
    lookup_cols, table_id_col, table_cols, b, g, num_repetitions, width
):
    """(R+1, n) stacked ext pairs: the R sub-argument denominators plus the
    table denominator, ready for one batched inversion."""
    gpow = _ext_powers_traced(g, width + 1)
    dens = []
    for i in range(num_repetitions):
        cols = [lookup_cols[i * width + j] for j in range(width)]
        dens.append(aggregate_lookup_columns(cols, table_id_col, gpow, b))
    dens.append(
        aggregate_lookup_columns(
            [table_cols[j] for j in range(width)], table_cols[width], gpow, b
        )
    )
    return (
        jnp.stack([d[0] for d in dens]),
        jnp.stack([d[1] for d in dens]),
    )


def compute_lookup_polys_general(
    gen_cols, tid_col, table_cols, multiplicities, sel_h,
    lookup_beta, lookup_gamma, num_subargs, width,
):
    """A_i and B polys over H for the GENERAL-PURPOSE-columns mode
    (reference lookup_argument.rs / lookup_placement.rs:21): sub-arguments
    tile the general copy columns, the table id is the marker row's gate
    constant column, and A_i = selector(x)/agg_i(x) — zero off the marker
    rows, where agg_i may be arbitrary (Fermat inversion maps 0 to 0)."""
    b = ext_scalar(lookup_beta)
    g = ext_scalar(lookup_gamma)
    R = int(num_subargs)
    dens = _lookup_denominators(
        gen_cols, tid_col, table_cols, b, g, R, int(width),
    )
    inv = ext_f.batch_inverse(dens)
    a_polys = [
        (gf.mul(inv[0][i], sel_h), gf.mul(inv[1][i], sel_h))
        for i in range(R)
    ]
    t_inv = (inv[0][R], inv[1][R])
    b_poly = (
        gf.mul(t_inv[0], multiplicities),
        gf.mul(t_inv[1], multiplicities),
    )
    return a_polys, b_poly


def lookup_quotient_terms_general(
    a_ldes, b_lde, gen_lde_cols, tid_lde, table_ldes, mult_lde, sel_lde,
    lookup_beta, lookup_gamma, num_subargs, width, alpha_pows: AlphaPows,
):
    """General-mode quotient contributions: per sub-arg
    A_i(x)·agg_i(x) − selector(x); for B: B(x)·t_agg(x) − M(x)
    (reference lookup_argument.rs quotient terms over general columns)."""
    a0, a1 = alpha_pows.take(num_subargs + 1)
    return _lookup_quotient_core_general(
        a_ldes, b_lde, gen_lde_cols, tid_lde, table_ldes, mult_lde, sel_lde,
        ext_scalar(lookup_beta), ext_scalar(lookup_gamma), a0, a1,
        int(num_subargs), int(width),
    )


@partial(jax.jit, static_argnums=(11, 12))
def _lookup_quotient_core_general(
    a_ldes, b_lde, gen_lde_cols, tid_lde, table_ldes, mult_lde, sel_lde,
    b, g, a0, a1, num_subargs, width,
):
    gpow = _ext_powers_traced(g, width + 1)
    acc = None
    for i in range(num_subargs):
        cols = [gen_lde_cols[i * width + j] for j in range(width)]
        den = aggregate_lookup_columns(cols, tid_lde, gpow, b)
        term = ext_f.mul(a_ldes[i], den)
        term = (gf.sub(term[0], sel_lde), term[1])
        acc = accumulate_ext_ext(acc, term, (a0[i], a1[i]))
    t_den = aggregate_lookup_columns(
        [table_ldes[j] for j in range(width)], table_ldes[width], gpow, b
    )
    term = ext_f.mul(b_lde, t_den)
    term = (gf.sub(term[0], mult_lde), term[1])
    acc = accumulate_ext_ext(
        acc, term, (a0[num_subargs], a1[num_subargs])
    )
    return acc


def lookup_quotient_terms(
    a_ldes, b_lde, lookup_lde_cols, table_id_lde, table_ldes, mult_lde,
    lookup_beta, lookup_gamma, num_repetitions, width, alpha_pows: AlphaPows,
):
    """Quotient contributions over the LDE domain (reference
    compute_quotient_terms_for_lookup_specialized,
    lookup_argument_in_ext.rs:949):

      per sub-arg i: A_i(x)·(Σ γ^j·w_{i,j}(x) + γ^w·tid(x) + β) − 1
      for B:         B(x)·(Σ γ^j·t_j(x) + γ^w·t_id(x) + β) − M(x)
    """
    a0, a1 = alpha_pows.take(num_repetitions + 1)
    return _lookup_quotient_core(
        a_ldes, b_lde, lookup_lde_cols, table_id_lde, table_ldes, mult_lde,
        ext_scalar(lookup_beta), ext_scalar(lookup_gamma), a0, a1,
        int(num_repetitions), int(width),
    )


@partial(jax.jit, static_argnums=(10, 11))
def _lookup_quotient_core(
    a_ldes, b_lde, lookup_lde_cols, table_id_lde, table_ldes, mult_lde,
    b, g, a0, a1, num_repetitions, width,
):
    gpow = _ext_powers_traced(g, width + 1)
    acc = None
    one = jnp.uint64(1)
    for i in range(num_repetitions):
        cols = [lookup_lde_cols[i * width + j] for j in range(width)]
        den = aggregate_lookup_columns(cols, table_id_lde, gpow, b)
        term = ext_f.mul(a_ldes[i], den)
        term = (gf.sub(term[0], jnp.broadcast_to(one, term[0].shape)), term[1])
        acc = accumulate_ext_ext(acc, term, (a0[i], a1[i]))
    t_den = aggregate_lookup_columns(
        [table_ldes[j] for j in range(width)], table_ldes[width], gpow, b
    )
    term = ext_f.mul(b_lde, t_den)
    term = (gf.sub(term[0], mult_lde), term[1])
    acc = accumulate_ext_ext(
        acc, term, (a0[num_repetitions], a1[num_repetitions])
    )
    return acc


def copy_permutation_quotient_terms(
    z_lde, z_shift_lde, partial_ldes, chunks, copy_lde, sigma_lde,
    non_residues, xs_lde, l0_lde, beta, gamma, alpha_pows: AlphaPows,
):
    """Quotient contributions of the copy-permutation argument over the LDE
    domain (reference copy_permutation.rs:1000):

      t0: L_0(x) · (z(x) − 1)
      per chunk j:  lhs_j(x)·prod_den_j(x) − rhs_j(x)·prod_num_j(x)
        where (lhs, rhs) walk z, p_0, …, p_last, z(w·x).
    """
    a0, a1 = alpha_pows.take(1 + len(chunks))
    return _cp_quotient_core(
        z_lde, z_shift_lde, partial_ldes, copy_lde, sigma_lde, xs_lde,
        l0_lde, ext_scalar(beta), ext_scalar(gamma), a0, a1,
        tuple(tuple(c) for c in chunks),
        tuple(int(k) for k in non_residues),
    )


@partial(jax.jit, static_argnums=(11, 12))
def _cp_quotient_core(
    z_lde, z_shift_lde, partial_ldes, copy_lde, sigma_lde, xs_lde, l0_lde,
    b, g, a0, a1, chunks, non_residues,
):
    one = jnp.uint64(1)
    acc = None
    # L_0(x)(z(x)-1)
    zm1 = (gf.sub(z_lde[0], jnp.broadcast_to(one, z_lde[0].shape)), z_lde[1])
    t0 = (gf.mul(zm1[0], l0_lde), gf.mul(zm1[1], l0_lde))
    acc = accumulate_ext_ext(acc, t0, (a0[0], a1[0]))
    lhs_seq = list(partial_ldes) + [z_shift_lde]
    rhs_seq = [z_lde] + list(partial_ldes)
    ks = non_residues
    for j, chunk in enumerate(chunks):
        num_p = None
        den_p = None
        for col in chunk:
            w = copy_lde[col]
            kx = gf.mul(xs_lde, jnp.uint64(ks[col]))
            num = (
                gf.add(gf.add(w, gf.mul(kx, b[0])), g[0]),
                gf.add(gf.mul(kx, b[1]), g[1]),
            )
            s = sigma_lde[col]
            den = (
                gf.add(gf.add(w, gf.mul(s, b[0])), g[0]),
                gf.add(gf.mul(s, b[1]), g[1]),
            )
            num_p = num if num_p is None else ext_f.mul(num_p, num)
            den_p = den if den_p is None else ext_f.mul(den_p, den)
        term = ext_f.sub(
            ext_f.mul(lhs_seq[j], den_p), ext_f.mul(rhs_seq[j], num_p)
        )
        acc = accumulate_ext_ext(acc, term, (a0[1 + j], a1[1 + j]))
    return acc
