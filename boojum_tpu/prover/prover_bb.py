"""The full PLONKish prover over BabyBear (ISSUE 20 tentpole).

`prove_full_babybear` runs the REAL gate/CS pipeline — the same 5-round
IOP, checkpoint labels, and clock stages as `prover._prove_impl` — with
every polynomial phase on bare u32 lanes: witness/setup ingestion is one
`.astype(uint32)` (no `limbs.split` anywhere; the plane-free claim is
structural), stage 2 runs the GF(p^4) grand-product/lookup kernels, the
quotient is ONE fused sweep over the whole rate-Q coset, DEEP opens at a
GF(p^4) z, and the FRI chain folds factor-2 over Poseidon2-BB oracles.

The prover core is backend-agnostic (np-in/np-out kernel seam, exactly
the mini-STARK's `bb_prover` discipline): `DeviceBackendBBFull`
dispatches the jitted `_bb` kernels; the numpy twin lives in
`compat/prove_reference_bb.NumpyBackendBBFull`. Both run THIS function,
so transcript bytes, challenge schedule, checkpoint stream and proof
assembly are shared — parity reduces to per-kernel mod-p exactness.

Protocol deltas vs the Goldilocks prover, all forced by the field:
- ext degree 4: the z poly / partials / lookup sums are 4 base columns
  each; values-at-z entries are 4-tuples; DEEP spends one challenge
  power per base column of the z-poly at z*omega (4, not 2).
- commits use PAIRED leaves — leaf j of a (B, N) oracle holds columns'
  values at j AND j + N/2, so one auth path serves both FRI halves.
- FRI folds factor-2 per round over the natural-order coset (no 2^k
  leaf grouping), committing every layer including the DEEP codeword.
- PoW grinds blake2s over the 31-bit challenge stream.
"""

from __future__ import annotations

import numpy as np

from ..field import babybear as bb
from ..field.spec import BABYBEAR as BB_SPEC
from ..transcript import BitSource, make_transcript
from ..utils import metrics as _metrics
from ..utils.spans import span as _span
from ..utils.report import checkpoint as _checkpoint
from . import bb_kernels as K
from . import stages_bb as S
from .bb_prover import (
    coset_descale,
    eval_base_at_ext,
    ext_powers_table,
    _fri_pair_cols,
)
from .config import ProofConfig
from .pow import blake2s_pow_grind
from .proof import OracleQuery, Proof, SingleRoundQueries
from .stages import chunk_columns, num_gate_sweep_terms

SHIFT = int(BB_SPEC.multiplicative_generator)  # coset shift = 31


class _NoClock:
    def start(self, name):
        pass

    def stop(self, error=None):
        pass


class DeviceBackendBBFull:
    """Dispatches the jitted full-prover `_bb` kernels; numpy in, numpy
    out (2^10-class domains — transfers are noise), every dispatch
    counted so the zero-limb acceptance can also assert the `_bb`
    counters MOVED."""

    name = "device"

    def intt(self, values):
        import jax.numpy as jnp

        from ..ntt.bb_ntt import monomial_from_values_bb

        _metrics.count("ntt.bb_dispatches")
        values = np.asarray(values, dtype=np.uint32)
        log_m = values.shape[-1].bit_length() - 1
        return np.asarray(
            monomial_from_values_bb(jnp.asarray(values), log_m)
        )

    def lde(self, mono, rate, shift=SHIFT):
        import jax.numpy as jnp

        from ..ntt.bb_ntt import lde_from_monomial_bb

        _metrics.count("lde.bb_dispatches")
        mono = np.asarray(mono, dtype=np.uint32)
        log_m = mono.shape[-1].bit_length() - 1
        return np.asarray(
            lde_from_monomial_bb(jnp.asarray(mono), log_m, rate, shift)
        )

    def commit(self, cols, cap_size):
        import jax.numpy as jnp

        _metrics.count("merkle.bb_commits")
        digests = K.leaf_digests_bb(jnp.asarray(np.asarray(cols, np.uint32)))
        layers = K.node_layers_bb(digests, cap_size)
        return K.BBMerkleTree([np.asarray(l) for l in layers], cap_size)

    def stage2(self, copy_vals, sigma_vals, ks, xs, beta, gamma, chunks):
        import jax.numpy as jnp

        _metrics.count("stage2.bb_scans")
        return np.asarray(
            S.stage2_z_partials_bb(
                jnp.asarray(copy_vals), jnp.asarray(sigma_vals),
                tuple(int(k) for k in ks), jnp.asarray(xs),
                jnp.asarray(beta), jnp.asarray(gamma),
                tuple(tuple(c) for c in chunks),
            )
        )

    def lookup_polys(
        self, lookup_cols, tid_col, table_cols, mults, lkb, lkg, R, width
    ):
        import jax.numpy as jnp

        _metrics.count("lookup.bb_polys")
        return np.asarray(
            S.lookup_polys_bb(
                jnp.asarray(lookup_cols), jnp.asarray(tid_col),
                jnp.asarray(table_cols), jnp.asarray(mults),
                jnp.asarray(lkb), jnp.asarray(lkg), R, width,
            )
        )

    def sweep(self, assembly, sweep_ctx, arrays):
        import jax.numpy as jnp

        _metrics.count("quotient.bb_full_sweeps")
        gates, selector_paths, geometry, lk_ctx, non_residues = sweep_ctx
        fn = getattr(assembly, "_bb_sweep_jit", None)
        if fn is None:
            fn = S.build_full_sweep_bb(
                gates, selector_paths, geometry, lk_ctx, non_residues
            )
            assembly._bb_sweep_jit = fn
        return np.asarray(fn(*[jnp.asarray(a) for a in arrays]))

    def deep(self, all_lde, zw_cols, lk_cols, pi_cols, xs, z4, zw4,
             ch_tbl, at_z_const, y_zw, y_lk, pi_vals, pi_inv,
             num_lk, num_pi):
        import jax.numpy as jnp

        _metrics.count("deep.bb_accumulates")
        return np.asarray(
            S.deep_full_bb(
                jnp.asarray(all_lde), jnp.asarray(zw_cols),
                jnp.asarray(lk_cols), jnp.asarray(pi_cols),
                jnp.asarray(xs), jnp.asarray(z4), jnp.asarray(zw4),
                jnp.asarray(ch_tbl), jnp.asarray(at_z_const),
                jnp.asarray(y_zw), jnp.asarray(y_lk),
                jnp.asarray(pi_vals), jnp.asarray(pi_inv),
                num_lk, num_pi,
            )
        )

    def fri_fold(self, codeword, beta4, inv2x):
        import jax.numpy as jnp

        _metrics.count("fri.bb_folds")
        return np.asarray(
            K.fri_fold_bb(
                jnp.asarray(np.asarray(codeword, np.uint32)),
                jnp.asarray(np.asarray(beta4, np.uint32)),
                jnp.asarray(inv2x),
            )
        )


def _u32_cols(arr):
    a = np.asarray(arr)
    assert a.dtype != np.uint32 or True
    return a.astype(np.uint32)


def _ext_np(e):
    return np.array([int(c) % bb.P for c in e], dtype=np.uint32)


def _abs_ext(t, e):
    t.witness_field_elements([int(c) for c in e])


def prove_full_babybear(
    assembly, setup, config: ProofConfig, clock=None, backend=None
) -> Proof:
    """The shared full-prover core; see module docstring. `setup` must
    come from `generate_setup` under the babybear field (its VK carries
    the poseidon2_babybear transcript and the host-committed setup
    oracle both backends share)."""
    clock = clock or _NoClock()
    backend = backend or DeviceBackendBBFull()
    n = assembly.trace_len
    log_n = n.bit_length() - 1
    L = config.fri_lde_factor
    log_full = log_n + (L.bit_length() - 1)
    N = n * L
    half = N // 2
    cap = config.merkle_tree_cap_size
    geometry = assembly.geometry
    Cg = assembly.copy_placement.shape[0]
    LC = assembly.num_lookup_cols
    Ct = Cg + LC
    W = assembly.wit_placement.shape[0]
    lookups = assembly.lookups_enabled
    R_args = assembly.num_lookup_subargs
    M = 1 if lookups else 0
    Kc = geometry.num_constant_columns + (1 if lookups else 0)
    lp = assembly.lookup_params
    width = lp.width if lookups else 0
    TW = (width + 1) if lookups else 0
    assert not lookups or assembly.lookup_mode == "specialized", (
        "babybear full prover supports specialized lookup columns only"
    )
    assert setup.vk.transcript.endswith("babybear"), setup.vk.transcript
    from .config import require_poseidon2_tree

    require_poseidon2_tree(
        getattr(setup.vk, "tree_hasher", "poseidon2"), "in the BabyBear prover"
    )
    Q = setup.vk.effective_quotient_degree()
    num_pi = len(assembly.public_inputs)
    num_lk = (R_args + 1) if lookups else 0
    omega = bb.omega(log_n)

    t = make_transcript(setup.vk.transcript)
    t.witness_merkle_tree_cap(setup.vk.setup_merkle_cap)
    _checkpoint(0, "setup_cap", setup.vk.setup_merkle_cap)
    pi_values = [int(v) for (_c, _r, v) in assembly.public_inputs]
    t.witness_field_elements(pi_values)
    _checkpoint(0, "public_inputs", pi_values)

    # ---- round 1: witness commitment -------------------------------------
    clock.start("round1_witness_commit")
    host_cols = [_u32_cols(assembly.copy_cols_values)]
    if LC:
        host_cols.append(_u32_cols(assembly.lookup_cols_values))
    if W:
        host_cols.append(_u32_cols(assembly.wit_cols_values))
    if M:
        host_cols.append(_u32_cols(assembly.multiplicities)[None, :])
    wit_vals = np.concatenate(host_cols, axis=0)  # (Ct+W+M, n) u32
    with _span("bb_witness_commit"):
        wit_mono = backend.intt(wit_vals)
        wit_lde = backend.lde(wit_mono, L)
        wit_tree = backend.commit(
            np.concatenate([wit_lde[:, :half], wit_lde[:, half:]]), cap
        )
    t.witness_merkle_tree_cap(wit_tree.get_cap())
    _checkpoint(1, "witness_cap", wit_tree.get_cap())
    beta = t.get_ext_challenge()
    gamma = t.get_ext_challenge()
    r1_challenges = [beta, gamma]
    if lookups:
        lookup_beta = t.get_ext_challenge()
        lookup_gamma = t.get_ext_challenge()
        r1_challenges += [lookup_beta, lookup_gamma]
    else:
        lookup_beta = lookup_gamma = bb.ZERO_S
    _checkpoint(1, "challenges", r1_challenges)

    # ---- round 2: copy-permutation + lookup stage 2 ----------------------
    clock.start("round2_stage2_commit")
    chunks = chunk_columns(Ct, geometry.max_allowed_constraint_degree)
    num_partials = len(chunks) - 1
    sigma_u32 = _u32_cols(setup.sigma_cols)
    consts_u32 = _u32_cols(setup.constant_cols)
    xs_h = bb.powers_np(omega, n)
    with _span("bb_stage2"):
        zp = backend.stage2(
            wit_vals[:Ct], sigma_u32, setup.non_residues, xs_h,
            _ext_np(beta), _ext_np(gamma), chunks,
        )  # (1 + num_partials, 4, n)
        s2_rows = [zp[j, k] for j in range(1 + num_partials)
                   for k in range(4)]
        if lookups:
            ab = backend.lookup_polys(
                wit_vals[Cg:Cg + R_args * width], consts_u32[Kc - 1],
                _u32_cols(
                    assembly.stacked_table_columns(width)
                ),
                wit_vals[Ct + W], _ext_np(lookup_beta),
                _ext_np(lookup_gamma), R_args, width,
            )  # (R_args + 1, 4, n)
            s2_rows += [ab[i, k] for i in range(R_args + 1)
                        for k in range(4)]
        s2_vals = np.stack(s2_rows)  # (S, n)
        s2_mono = backend.intt(s2_vals)
        s2_lde = backend.lde(s2_mono, L)
        s2_tree = backend.commit(
            np.concatenate([s2_lde[:, :half], s2_lde[:, half:]]), cap
        )
    t.witness_merkle_tree_cap(s2_tree.get_cap())
    _checkpoint(2, "stage2_cap", s2_tree.get_cap())
    alpha = t.get_ext_challenge()
    _checkpoint(2, "alpha", alpha)

    # ---- round 3: quotient (ONE fused sweep over the rate-Q coset) -------
    clock.start("round3_quotient")
    total_alpha_terms = (
        num_gate_sweep_terms(assembly)
        + 1 + len(chunks)
        + ((R_args + 1) if lookups else 0)
    )
    setup_mono = np.asarray(setup.setup_monomials, dtype=np.uint32)
    setup_lde = np.asarray(setup.setup_lde, dtype=np.uint32)
    with _span("bb_quotient"):
        wit_q = backend.lde(wit_mono, Q)
        setup_q = backend.lde(setup_mono, Q)
        s2_q = backend.lde(s2_mono, Q)
        # z(omega*x): the z poly's 4 base monomial rows scaled by omega^i
        zs_mono = bb.mul_np(
            s2_mono[:4], bb.powers_np(omega, n)[None, :]
        )
        zs_q = backend.lde(zs_mono, Q)
        xs_q = K.domain_xs_bb(log_n, Q, SHIFT)
        zh_inv_q = K.zh_inv_bb(log_n, Q, SHIFT)
        l0_q = S.l0_lde_bb(log_n, Q, SHIFT)
        apows = ext_powers_table(alpha, total_alpha_terms)
        lk_ctx = (
            lookups, R_args, width, num_partials,
            tuple(tuple(c) for c in chunks),
            Cg, Ct, W, Kc, M, total_alpha_terms,
        )
        sweep_ctx = (
            tuple(assembly.gates),
            tuple(tuple(p) for p in setup.selector_paths),
            geometry, lk_ctx,
            tuple(int(k) for k in setup.non_residues),
        )
        acc = backend.sweep(
            assembly, sweep_ctx,
            (wit_q, setup_q, s2_q, zs_q, xs_q, l0_q, zh_inv_q, apows,
             _ext_np(beta), _ext_np(gamma), _ext_np(lookup_beta),
             _ext_np(lookup_gamma)),
        )  # (4, Q*n) — the quotient T over the sweep domain
        t_mono = coset_descale(backend.intt(acc), SHIFT)
        q_mono = np.stack(
            [t_mono[k][i * n:(i + 1) * n]
             for i in range(Q) for k in range(4)]
        )  # (4Q, n)
        q_lde = backend.lde(q_mono, L)
        q_tree = backend.commit(
            np.concatenate([q_lde[:, :half], q_lde[:, half:]]), cap
        )
    t.witness_merkle_tree_cap(q_tree.get_cap())
    _checkpoint(3, "quotient_cap", q_tree.get_cap())
    z_chal = t.get_ext_challenge()
    _checkpoint(3, "z", z_chal)

    # ---- round 4: evaluations at z (and z*omega, 0) ----------------------
    clock.start("round4_evaluations")
    all_mono = np.concatenate([wit_mono, setup_mono, s2_mono, q_mono])
    B_all = all_mono.shape[0]
    zpows = ext_powers_table(z_chal, n)
    values_at_z = [eval_base_at_ext(all_mono[i], zpows)
                   for i in range(B_all)]
    zw = tuple(bb.mul_s(int(c), omega) for c in z_chal)
    zwpows = ext_powers_table(zw, n)
    values_at_z_omega = [eval_base_at_ext(s2_mono[i], zwpows)
                         for i in range(4)]
    ab4_off = 4 + 4 * num_partials
    values_at_0 = [
        tuple(int(s2_mono[ab4_off + 4 * i + k][0]) for k in range(4))
        for i in range(num_lk)
    ]
    for v in values_at_z:
        _abs_ext(t, v)
    for v in values_at_z_omega:
        _abs_ext(t, v)
    for v in values_at_0:
        _abs_ext(t, v)
    _checkpoint(
        4, "evaluations", [values_at_z, values_at_z_omega, values_at_0]
    )
    deep_ch = t.get_ext_challenge()
    _checkpoint(4, "deep_challenge", deep_ch)

    # ---- round 5: DEEP + FRI ---------------------------------------------
    clock.start("round5_deep_fri")
    num_deep_terms = B_all + 4 + num_lk + num_pi
    ch_tbl = ext_powers_table(deep_ch, num_deep_terms)
    at_z = bb.ZERO_S
    for i in range(B_all):
        ch = tuple(int(ch_tbl[k, i]) for k in range(4))
        at_z = bb.ext_add_s(at_z, bb.ext_mul_s(ch, values_at_z[i]))
    xs_lde = K.domain_xs_bb(log_n, L, SHIFT)
    all_lde = np.concatenate([wit_lde, setup_lde, s2_lde, q_lde])
    pi_rows = [r for (_c, r, _v) in assembly.public_inputs]
    pi_cols = (
        np.stack([wit_lde[c] for (c, _r, _v) in assembly.public_inputs])
        if num_pi else np.zeros((0, N), dtype=np.uint32)
    )
    pi_inv = (
        np.stack([
            K._host_batch_inv(
                bb.sub_np(xs_lde, np.uint32(bb.pow_s(omega, r)))
            )
            for r in pi_rows
        ])
        if num_pi else np.zeros((0, N), dtype=np.uint32)
    )
    lk_cols = (
        s2_lde[ab4_off:ab4_off + 4 * num_lk]
        if num_lk else np.zeros((0, N), dtype=np.uint32)
    )
    y_zw = np.array(values_at_z_omega, dtype=np.uint32).T  # (4 comps, 4)
    y_lk = (
        np.array(values_at_0, dtype=np.uint32)
        if num_lk else np.zeros((0, 4), dtype=np.uint32)
    )
    with _span("bb_deep"):
        h = backend.deep(
            all_lde, s2_lde[:4], lk_cols, pi_cols, xs_lde,
            _ext_np(z_chal), _ext_np(zw), ch_tbl, _ext_np(at_z),
            y_zw, y_lk,
            np.array(pi_values, dtype=np.uint32), pi_inv,
            num_lk, num_pi,
        )  # (4, N)

    num_fri_rounds = (n // config.fri_final_degree).bit_length() - 1
    assert num_fri_rounds >= 1, "fri_final_degree leaves nothing to fold"
    fold_tables = K.fri_fold_tables_bb(log_full, SHIFT, num_fri_rounds)
    fri_trees, fri_layers, cur = [], [], h
    with _span("bb_fri"):
        for r in range(num_fri_rounds):
            fri_layers.append(cur)
            tree = backend.commit(
                _fri_pair_cols(cur), min(cap, cur.shape[-1] // 2)
            )
            fri_trees.append(tree)
            t.witness_merkle_tree_cap(tree.get_cap())
            _checkpoint(5, f"fri_cap_{r}", tree.get_cap())
            ch = t.get_ext_challenge()
            _checkpoint(5, f"fri_challenge_{r}", ch)
            cur = backend.fri_fold(cur, _ext_np(ch), fold_tables[r])
        final_mono = coset_descale(
            backend.intt(cur), bb.pow_s(SHIFT, 1 << num_fri_rounds)
        )
    final_fri_monomials = [
        tuple(int(final_mono[k][i]) for k in range(4))
        for i in range(config.fri_final_degree)
    ]
    for c in final_fri_monomials:
        _abs_ext(t, c)
    _checkpoint(5, "fri_final_monomials", final_fri_monomials)
    pow_nonce = blake2s_pow_grind(t, config.pow_bits)
    _checkpoint(5, "pow_nonce", [pow_nonce])

    # ---- queries ----------------------------------------------------------
    clock.start("queries")
    bs = BitSource(log_full, challenge_bits=BB_SPEC.challenge_bits)
    idxs = [bs.get_index(t, log_full) for _ in range(config.num_queries)]
    _checkpoint(5, "query_indices", idxs)

    paired = {
        "witness": np.concatenate([wit_lde[:, :half], wit_lde[:, half:]]),
        "stage2": np.concatenate([s2_lde[:, :half], s2_lde[:, half:]]),
        "quotient": np.concatenate([q_lde[:, :half], q_lde[:, half:]]),
        "setup": np.concatenate([setup_lde[:, :half],
                                 setup_lde[:, half:]]),
    }
    trees = {
        "witness": wit_tree, "stage2": s2_tree,
        "quotient": q_tree, "setup": setup.setup_tree,
    }

    def _oracle_query(name, j0):
        cols = paired[name]
        return OracleQuery(
            leaf_values=[int(x) for x in cols[:, j0]],
            path=trees[name].get_path(j0),
        )

    queries = []
    for pos in idxs:
        j0 = pos % half
        fri_qs = []
        p = pos
        for r in range(num_fri_rounds):
            layer = fri_layers[r]
            h_r = layer.shape[-1] // 2
            leaf = p % h_r
            fri_qs.append(
                OracleQuery(
                    leaf_values=[
                        int(layer[k][leaf + off])
                        for off in (0, h_r) for k in range(4)
                    ],
                    path=fri_trees[r].get_path(leaf),
                )
            )
            p %= h_r
        queries.append(
            SingleRoundQueries(
                witness=_oracle_query("witness", j0),
                stage2=_oracle_query("stage2", j0),
                quotient=_oracle_query("quotient", j0),
                setup=_oracle_query("setup", j0),
                fri=fri_qs,
            )
        )

    return Proof(
        public_inputs=pi_values,
        witness_cap=wit_tree.get_cap(),
        stage2_cap=s2_tree.get_cap(),
        quotient_cap=q_tree.get_cap(),
        values_at_z=values_at_z,
        values_at_z_omega=values_at_z_omega,
        values_at_0=values_at_0,
        fri_caps=[tr.get_cap() for tr in fri_trees],
        final_fri_monomials=final_fri_monomials,
        queries=queries,
        pow_challenge=pow_nonce,
        config={
            "fri_lde_factor": L,
            "quotient_degree": Q,
            "merkle_tree_cap_size": cap,
            "num_queries": config.num_queries,
            "pow_bits": config.pow_bits,
            "fri_final_degree": config.fri_final_degree,
            "field": "babybear",
        },
    )


# ---------------------------------------------------------------------------
# Quotient identity self-check at z (the mini-verifier acceptance leg)
# ---------------------------------------------------------------------------


def _replay_challenges(assembly, setup, proof):
    """Re-derive every drawn challenge by replaying the transcript from
    the proof's own contents (exactly what a verifier does)."""
    cfg = proof.config
    t = make_transcript(setup.vk.transcript)
    t.witness_merkle_tree_cap(setup.vk.setup_merkle_cap)
    t.witness_field_elements([int(v) for v in proof.public_inputs])
    t.witness_merkle_tree_cap(proof.witness_cap)
    out = {"beta": t.get_ext_challenge(), "gamma": t.get_ext_challenge()}
    if assembly.lookups_enabled:
        out["lookup_beta"] = t.get_ext_challenge()
        out["lookup_gamma"] = t.get_ext_challenge()
    t.witness_merkle_tree_cap(proof.stage2_cap)
    out["alpha"] = t.get_ext_challenge()
    t.witness_merkle_tree_cap(proof.quotient_cap)
    out["z"] = t.get_ext_challenge()
    for v in proof.values_at_z:
        _abs_ext(t, v)
    for v in proof.values_at_z_omega:
        _abs_ext(t, v)
    for v in proof.values_at_0:
        _abs_ext(t, v)
    out["deep"] = t.get_ext_challenge()
    return out


def quotient_identity_at_z(assembly, setup, proof) -> bool:
    """acc(z) == T(z) * (z^n - 1): reconstruct the alpha-weighted
    constraint accumulator at z from the proof's openings via
    `BBExtScalarOps` (the SAME gate evaluators the sweep ran, now over
    GF(p^4) scalars) and compare against the committed quotient
    recombined at z. This is the verifier-side half of the quotient
    protocol, used as the full-prover self-check."""
    from ..cs.field_like import BBExtScalarOps as E
    from ..cs.gates.base import TermsCollector

    n = assembly.trace_len
    log_n = n.bit_length() - 1
    geometry = assembly.geometry
    Cg = assembly.copy_placement.shape[0]
    Ct = Cg + assembly.num_lookup_cols
    W = assembly.wit_placement.shape[0]
    lookups = assembly.lookups_enabled
    R_args = assembly.num_lookup_subargs
    Kc = geometry.num_constant_columns + (1 if lookups else 0)
    width = assembly.lookup_params.width if lookups else 0
    Q = setup.vk.effective_quotient_degree()
    M = 1 if lookups else 0
    omega = bb.omega(log_n)
    chs = _replay_challenges(assembly, setup, proof)
    z = tuple(int(c) for c in chs["z"])
    vz = [tuple(int(c) for c in v) for v in proof.values_at_z]
    B_wit = Ct + W + M
    B_setup = Ct + Kc + ((width + 1) if lookups else 0)
    wit_z = vz[:B_wit]
    setup_z = vz[B_wit:B_wit + B_setup]
    s2_z = vz[B_wit + B_setup:len(vz) - 4 * Q]
    q_z = vz[len(vz) - 4 * Q:]
    sigma_z = setup_z[:Ct]
    const_z = setup_z[Ct:Ct + Kc]
    table_z = setup_z[Ct + Kc:]
    # ext helpers over the opened 4-tuples
    z_pow_n = bb.ext_pow_s(z, n)
    zh_z = bb.ext_sub_s(z_pow_n, bb.ONE_S)
    chunks = chunk_columns(Ct, geometry.max_allowed_constraint_degree)
    num_partials = len(chunks) - 1
    z_v = _group_ext(s2_z, 0)
    partial_v = [_group_ext(s2_z, 1 + j) for j in range(num_partials)]
    zw_v = [tuple(int(c) for c in v) for v in proof.values_at_z_omega]
    z_shift_v = _recombine_ext_cols(zw_v)
    total_alpha_terms = (
        num_gate_sweep_terms(assembly) + 1 + len(chunks)
        + ((R_args + 1) if lookups else 0)
    )
    apows = [bb.ONE_S]
    alpha = tuple(int(c) for c in chs["alpha"])
    for _ in range(total_alpha_terms - 1):
        apows.append(bb.ext_mul_s(apows[-1], alpha))
    ap_it = iter(apows)
    acc = bb.ZERO_S

    class _Row:
        def __init__(self, vo, wo, ko):
            self.vo, self.wo, self.ko = vo, wo, ko

        def v(self, i):
            return wit_z[self.vo + i]

        def w(self, i):
            return wit_z[Ct + self.wo + i]

        def c(self, i):
            return const_z[self.ko + i]

    for gid, gate in enumerate(assembly.gates):
        if gate.num_terms == 0:
            continue
        path = setup.selector_paths[gid]
        sel = bb.ONE_S
        for b, bit in enumerate(path):
            f = (const_z[b] if bit
                 else bb.ext_sub_s(bb.ONE_S, const_z[b]))
            sel = bb.ext_mul_s(sel, f)
        gate_acc = bb.ZERO_S
        for inst in range(gate.num_repetitions(geometry)):
            row = _Row(
                inst * gate.principal_width,
                inst * gate.witness_width, len(path),
            )
            dst = TermsCollector()
            gate.evaluate(E, row, dst)
            for term in dst.terms:
                gate_acc = bb.ext_add_s(
                    gate_acc, bb.ext_mul_s(term, next(ap_it))
                )
        acc = bb.ext_add_s(acc, bb.ext_mul_s(gate_acc, sel))
    # copy permutation
    l0_z = bb.ext_mul_s(
        zh_z,
        bb.ext_inv_s(
            bb.ext_scale_s(bb.ext_sub_s(z, bb.ONE_S), n)
        ),
    )
    t0 = bb.ext_mul_s(l0_z, bb.ext_sub_s(z_v, bb.ONE_S))
    acc = bb.ext_add_s(acc, bb.ext_mul_s(t0, next(ap_it)))
    lhs_seq = partial_v + [z_shift_v]
    rhs_seq = [z_v] + partial_v
    for j, chunk in enumerate(chunks):
        num_p = den_p = bb.ONE_S
        for col in chunk:
            kx = bb.ext_scale_s(z, int(setup.non_residues[col]))
            num = bb.ext_add_s(
                bb.ext_add_s(
                    wit_z[col],
                    bb.ext_mul_s(tuple(int(c) for c in chs["beta"]), kx),
                ),
                tuple(int(c) for c in chs["gamma"]),
            )
            den = bb.ext_add_s(
                bb.ext_add_s(
                    wit_z[col],
                    bb.ext_mul_s(
                        tuple(int(c) for c in chs["beta"]), sigma_z[col]
                    ),
                ),
                tuple(int(c) for c in chs["gamma"]),
            )
            num_p = bb.ext_mul_s(num_p, num)
            den_p = bb.ext_mul_s(den_p, den)
        term = bb.ext_sub_s(
            bb.ext_mul_s(lhs_seq[j], den_p),
            bb.ext_mul_s(rhs_seq[j], num_p),
        )
        acc = bb.ext_add_s(acc, bb.ext_mul_s(term, next(ap_it)))
    if lookups:
        lkb = tuple(int(c) for c in chs["lookup_beta"])
        lkg = tuple(int(c) for c in chs["lookup_gamma"])
        gpow = [bb.ONE_S]
        for _ in range(width):
            gpow.append(bb.ext_mul_s(gpow[-1], lkg))
        ab_off = 1 + num_partials
        tid_z = const_z[Kc - 1]
        for i in range(R_args):
            den = lkb
            for j in range(width):
                den = bb.ext_add_s(
                    den,
                    bb.ext_mul_s(wit_z[Cg + i * width + j], gpow[j]),
                )
            den = bb.ext_add_s(den, bb.ext_mul_s(tid_z, gpow[width]))
            a_i = _group_ext(s2_z, ab_off + i)
            term = bb.ext_sub_s(bb.ext_mul_s(a_i, den), bb.ONE_S)
            acc = bb.ext_add_s(acc, bb.ext_mul_s(term, next(ap_it)))
        t_den = lkb
        for j in range(width):
            t_den = bb.ext_add_s(
                t_den, bb.ext_mul_s(table_z[j], gpow[j])
            )
        t_den = bb.ext_add_s(
            t_den, bb.ext_mul_s(table_z[width], gpow[width])
        )
        b_v = _group_ext(s2_z, ab_off + R_args)
        term = bb.ext_sub_s(
            bb.ext_mul_s(b_v, t_den), wit_z[Ct + W]
        )
        acc = bb.ext_add_s(acc, bb.ext_mul_s(term, next(ap_it)))
    # T(z): recombine the 4Q committed base columns
    w_basis = [
        tuple(1 if k == i else 0 for k in range(4)) for i in range(4)
    ]
    t_z = bb.ZERO_S
    zn_pow = bb.ONE_S
    for i in range(Q):
        chunk_v = bb.ZERO_S
        for k in range(4):
            chunk_v = bb.ext_add_s(
                chunk_v, bb.ext_mul_s(w_basis[k], q_z[4 * i + k])
            )
        t_z = bb.ext_add_s(t_z, bb.ext_mul_s(chunk_v, zn_pow))
        zn_pow = bb.ext_mul_s(zn_pow, z_pow_n)
    return acc == bb.ext_mul_s(t_z, zh_z)


def _group_ext(vals, idx):
    """4 consecutive opened base-column values (each a 4-tuple at z) of
    ext poly `idx` -> the poly's ext value: sum_k w^k * col_k(z)."""
    out = bb.ZERO_S
    for k in range(4):
        basis = tuple(1 if j == k else 0 for j in range(4))
        out = bb.ext_add_s(out, bb.ext_mul_s(basis, vals[4 * idx + k]))
    return out


def _recombine_ext_cols(cols4):
    out = bb.ZERO_S
    for k in range(4):
        basis = tuple(1 if j == k else 0 for j in range(4))
        out = bb.ext_add_s(out, bb.ext_mul_s(basis, cols4[k]))
    return out
