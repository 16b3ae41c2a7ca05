"""The recursive verifier: a Boojum proof verified inside a circuit.

Counterpart of `/root/reference/src/gadgets/recursion/recursive_verifier.rs:380`
(`RecursiveVerifier::verify`). Mirrors the host verifier
(`boojum_tpu.prover.verifier.verify`) step for step — transcript replay,
quotient reconstruction at z by running the inner circuit's own gate
evaluators over `CircuitExtOps`, copy-permutation and lookup relations,
DEEP recomputation, Merkle path checks and the FRI fold chain — but every
field op is a gadget constraint and every hash is a flattened-Poseidon2-gate
sponge. Validity is ENFORCED (the witness cannot satisfy the circuit unless
the proof verifies) rather than returned as a Boolean; structural/shape
checks are host-side asserts at synthesis time since they depend only on the
(host-known) VK. This is the one deliberate deviation from the reference,
which returns an `(is_valid, public_inputs)` pair.

Returns (inner_public_input_vars, setup_cap_vars) for the caller to expose.
"""

from __future__ import annotations

from ...field import gl
from ...prover.setup import non_residues_for_copy_permutation
from ...prover.stages import chunk_columns
from ...prover.verifier import _ZRowView, _brev
from ...cs.gates.base import TermsCollector
from ...cs.gates.simple import ConditionalSwapGate, FmaGate
from ...utils import metrics as _metrics
from ...utils.spans import span as _span
from ..field_like_circuit import CircuitExtOps, CircuitOps
from ..poseidon2_rf import circuit_hash_leaf, circuit_hash_node
from .allocated_proof import AllocatedProof, AllocatedVerificationKey
from .transcript import (
    CircuitBitSource,
    CircuitTranscript,
    decompose_challenge_canonical,
)

INV2 = (gl.P + 1) // 2


def _ext_from_pair(ops, a, b):
    """Opening value of an ext-coefficient poly from its two base-poly
    openings: a + b·w (w = sqrt(7))."""
    w = (ops.cs.zero_var(), ops.cs.one_var())
    return ops.add(a, ops.mul(b, w))


class _PowIter:
    def __init__(self, ops, base):
        self.ops = ops
        self.base = base
        self.cur = ops.one()

    def __next__(self):
        out = self.cur
        self.cur = self.ops.mul(self.cur, self.base)
        return out


def _mux_digest(bops: CircuitOps, bits, digests):
    """Select digests[index] with LE index bit variables (tree of selects)."""
    values = list(digests)
    for b in bits:
        assert len(values) % 2 == 0
        values = [
            [
                bops.select(b, values[2 * i + 1][e], values[2 * i][e])
                for e in range(4)
            ]
            for i in range(len(values) // 2)
        ]
    assert len(values) == 1
    return values[0]


def _verify_merkle_path(cs, bops, leaf_vars, path, cap, idx_bits):
    """Enforce that leaf_vars opens against the cap at the index encoded by
    idx_bits (LE). Mirrors host verify_proof_over_cap (merkle.py:61)."""
    digest = circuit_hash_leaf(cs, leaf_vars)
    for level, sib in enumerate(path):
        bit = idx_bits[level]
        left, right = [], []
        for e in range(4):
            l_e, r_e = ConditionalSwapGate.swap(cs, bit, digest[e], sib[e])
            left.append(l_e)
            right.append(r_e)
        digest = circuit_hash_node(cs, left, right)
    cap_bits = idx_bits[len(path) :]
    assert len(cap) == 1 << len(cap_bits)
    expected = _mux_digest(bops, cap_bits, cap)
    for e in range(4):
        bops.enforce_equal(digest[e], expected[e])


def _point_from_bits(bops: CircuitOps, bits_nat_high_to_low, omega: int, shift: int):
    """g·ω^nat where nat's bits are given bit-reversed: bits list is LE index
    bits; nat bit (m-1-j) = idx bit j. Computed as shift·Π_j
    select(idx_j, ω^{2^{m-1-j}}, 1)."""
    m = len(bits_nat_high_to_low)
    acc = bops.constant(shift)
    for j, bit in enumerate(bits_nat_high_to_low):
        w_pow = gl.pow_(omega, 1 << (m - 1 - j))
        factor = bops.select(bit, bops.constant(w_pow), bops.one())
        acc = bops.mul(acc, factor)
    return acc


def recursive_verify(cs, vk, proof, gates):
    """Synthesize the verification of `proof` (host object) against `vk`
    into `cs`. `gates` is the inner circuit's gate list (the verifier is
    built from the same gate configuration, reference
    recursive_verifier_builder.rs)."""
    from ...prover.config import require_poseidon2_tree

    # a Blake2s tree is the hasher of proofs nothing recurses over: the
    # circuit hashes leaves and nodes with the Poseidon2 gate alone
    require_poseidon2_tree(
        getattr(vk, "tree_hasher", "poseidon2"),
        "as the inner key of recursive_verify",
    )
    first_row = cs.next_row
    with _span("recursion.allocate_proof"):
        ap = AllocatedProof(cs, proof)
        avk = AllocatedVerificationKey(cs, vk)
    ops = CircuitExtOps(cs)
    bops = CircuitOps(cs)

    geometry = vk.geometry
    n = vk.trace_len
    log_n = n.bit_length() - 1
    L = vk.fri_lde_factor
    Q = vk.effective_quotient_degree()
    log_full = log_n + (L.bit_length() - 1)
    Ct = vk.num_copy_cols
    Cg = geometry.num_columns_under_copy_permutation
    W = vk.num_wit_cols
    lp = vk.lookup_params
    lookups = lp is not None and lp.is_enabled
    transcript_kind = getattr(vk, "transcript", "poseidon2")
    assert transcript_kind in ("poseidon2", "poseidon"), (
        "the in-circuit verifier replays algebraic transcripts only "
        "(Poseidon2 or legacy Poseidon — byte transcripts are not "
        "circuit-replayable, matching the reference's recursion-compatible "
        "configurations)"
    )
    if transcript_kind == "poseidon":
        from ..poseidon_rf import circuit_permutation as transcript_perm
    else:
        from ..poseidon2_rf import circuit_permutation as transcript_perm
    lk_specialized = lookups and lp.use_specialized_columns
    M = 1 if lookups else 0
    wdt = lp.width if lookups else 0
    if lk_specialized:
        R = lp.num_repetitions
    elif lookups:
        R = Cg // wdt  # general mode: sub-arguments tile general columns
    else:
        R = 0
    K = geometry.num_constant_columns + (1 if lk_specialized else 0)
    TW = (wdt + 1) if lookups else 0
    assert Ct == (Cg + R * wdt if lk_specialized else Cg)
    assert [g.name for g in gates] == list(vk.gate_names)
    assert len(proof.public_inputs) == len(vk.public_input_locations)

    num_chunks = len(chunk_columns(Ct, geometry.max_allowed_constraint_degree))
    S = 2 * (1 + (num_chunks - 1)) + 2 * R + 2 * M
    B = (Ct + W + M) + (Ct + K + TW) + S + 2 * Q
    assert len(proof.values_at_z) == B and len(proof.values_at_z_omega) == 2
    assert len(proof.values_at_0) == R + M

    # ---- transcript replay ------------------------------------------------
    with _span("recursion.transcript"):
        t = CircuitTranscript(cs, permutation=transcript_perm)
        t.witness_merkle_tree_cap(avk.setup_merkle_cap)
        t.witness_field_elements(ap.public_inputs)
        t.witness_merkle_tree_cap(ap.witness_cap)
        beta = t.get_ext_challenge()
        gamma = t.get_ext_challenge()
        if lookups:
            lookup_beta = t.get_ext_challenge()
            lookup_gamma = t.get_ext_challenge()
        t.witness_merkle_tree_cap(ap.stage2_cap)
        alpha = t.get_ext_challenge()
        t.witness_merkle_tree_cap(ap.quotient_cap)
        z_chal = t.get_ext_challenge()
        for v in ap.values_at_z:
            t.witness_field_elements(list(v))
        for v in ap.values_at_z_omega:
            t.witness_field_elements(list(v))
        for v in ap.values_at_0:
            t.witness_field_elements(list(v))
        deep_ch = t.get_ext_challenge()
        from ...prover.fri import fold_schedule

        schedule = fold_schedule(
            n, vk.fri_final_degree, getattr(vk, "fri_folding_schedule", None)
        )
        num_folds = sum(schedule)
        assert len(proof.fri_caps) == len(schedule)
        fri_challenges = []
        for r in range(len(schedule)):
            t.witness_merkle_tree_cap(ap.fri_caps[r])
            fri_challenges.append(t.get_ext_challenge())
        assert len(proof.final_fri_monomials) == (n >> num_folds)
        for c0, c1 in ap.final_fri_monomials:
            t.witness_field_elements([c0, c1])

    # ---- split openings ---------------------------------------------------
    with _span("recursion.quotient_at_z"):
        vals = ap.values_at_z
        wit_vals = vals[: Ct + W + M]
        sigma_vals = vals[Ct + W + M : 2 * Ct + W + M]
        const_vals = vals[2 * Ct + W + M : 2 * Ct + W + M + K]
        table_vals = vals[2 * Ct + W + M + K : 2 * Ct + W + M + K + TW]
        s2_vals = vals[2 * Ct + W + M + K + TW : 2 * Ct + W + M + K + TW + S]
        q_vals = vals[2 * Ct + W + M + K + TW + S :]

        # ---- quotient identity at z ------------------------------------------
        alpha_pows = _PowIter(ops, alpha)
        total = ops.zero()
        for gid, gate in enumerate(gates):
            if gate.num_terms == 0:
                continue
            path = vk.selector_paths[gid]
            sel = ops.one()
            for b, bit in enumerate(path):
                cb = const_vals[b]
                sel = ops.mul(sel, cb if bit else ops.sub(ops.one(), cb))
            reps = gate.num_repetitions(geometry)
            gate_acc = ops.zero()
            for inst in range(reps):
                row = _ZRowView(
                    wit_vals, const_vals, inst * gate.principal_width,
                    inst * gate.witness_width, len(path), Ct,
                )
                dst = TermsCollector()
                gate.evaluate(ops, row, dst)
                assert len(dst.terms) == gate.num_terms
                for term in dst.terms:
                    gate_acc = ops.add(
                        gate_acc, ops.mul(term, next(alpha_pows))
                    )
            total = ops.add(total, ops.mul(sel, gate_acc))

        # copy-permutation terms at z
        z_at_z = _ext_from_pair(ops, s2_vals[0], s2_vals[1])
        z_at_zw = _ext_from_pair(ops, ap.values_at_z_omega[0], ap.values_at_z_omega[1])
        partial_at_z = [
            _ext_from_pair(ops, s2_vals[2 + 2 * j], s2_vals[3 + 2 * j])
            for j in range(num_chunks - 1)
        ]
        non_residues = non_residues_for_copy_permutation(Ct)
        chunks = chunk_columns(Ct, geometry.max_allowed_constraint_degree)
        z_pow_n = ops.pow(z_chal, n)
        zh_at_z = ops.sub(z_pow_n, ops.one())
        l0_at_z = ops.mul(
            ops.mul_by_base_constant(zh_at_z, gl.inv(n)),
            ops.inv(ops.sub(z_chal, ops.one())),
        )
        term = ops.mul(l0_at_z, ops.sub(z_at_z, ops.one()))
        total = ops.add(total, ops.mul(term, next(alpha_pows)))
        lhs_seq = partial_at_z + [z_at_zw]
        rhs_seq = [z_at_z] + partial_at_z
        for j, chunk in enumerate(chunks):
            num_p = ops.one()
            den_p = ops.one()
            for col in chunk:
                w = wit_vals[col]
                kx = ops.mul_by_base_constant(z_chal, non_residues[col])
                num = ops.add(ops.add(w, ops.mul(beta, kx)), gamma)
                den = ops.add(
                    ops.add(w, ops.mul(beta, sigma_vals[col])), gamma
                )
                num_p = ops.mul(num_p, num)
                den_p = ops.mul(den_p, den)
            rel = ops.sub(
                ops.mul(lhs_seq[j], den_p), ops.mul(rhs_seq[j], num_p)
            )
            total = ops.add(total, ops.mul(rel, next(alpha_pows)))

        # lookup terms at z + the sum check at 0 (both placement families —
        # reference lookup_placement.rs:21 + recursive_verifier.rs:380)
        if lookups:
            ab_off = 2 * (1 + (num_chunks - 1))
            gpow = [ops.one()]
            for _ in range(wdt + 1):
                gpow.append(ops.mul(gpow[-1], lookup_gamma))
            if lk_specialized:
                tid_at_z = const_vals[K - 1]
                a_numerator = ops.one()
                col_base = Cg
            else:
                # general mode: the table id is the marker row's constant and
                # each A relation is gated by the marker's SELECTOR at z
                mk_gid = next(
                    (
                        i for i, g in enumerate(gates)
                        if getattr(g, "is_lookup_marker", False)
                    ),
                    None,
                )
                assert mk_gid is not None, (
                    "general-mode VK but no marker gate supplied"
                )
                mk_path = vk.selector_paths[mk_gid]
                tid_at_z = const_vals[len(mk_path)]
                sel_at_z = ops.one()
                for bdx, bit in enumerate(mk_path):
                    cb = const_vals[bdx]
                    sel_at_z = ops.mul(
                        sel_at_z, cb if bit else ops.sub(ops.one(), cb)
                    )
                a_numerator = sel_at_z
                col_base = 0
            for i in range(R):
                a_i = _ext_from_pair(
                    ops, s2_vals[ab_off + 2 * i], s2_vals[ab_off + 2 * i + 1]
                )
                den = lookup_beta
                for j in range(wdt):
                    wv = wit_vals[col_base + i * wdt + j]
                    den = ops.add(den, ops.mul(gpow[j], wv))
                den = ops.add(den, ops.mul(gpow[wdt], tid_at_z))
                rel = ops.sub(ops.mul(a_i, den), a_numerator)
                total = ops.add(total, ops.mul(rel, next(alpha_pows)))
            b_at_z = _ext_from_pair(
                ops, s2_vals[ab_off + 2 * R], s2_vals[ab_off + 2 * R + 1]
            )
            den = lookup_beta
            for j in range(wdt + 1):
                den = ops.add(den, ops.mul(gpow[j], table_vals[j]))
            m_at_z = wit_vals[Ct + W]
            rel = ops.sub(ops.mul(b_at_z, den), m_at_z)
            total = ops.add(total, ops.mul(rel, next(alpha_pows)))
            a_sum = ops.zero()
            for i in range(R):
                a_sum = ops.add(a_sum, ap.values_at_0[i])
            ops.enforce_equal(a_sum, ap.values_at_0[R])

        # T(z)·Z_H(z) == total
        t_at_z = ops.zero()
        z_pows = _PowIter(ops, z_pow_n)
        for i in range(Q):
            q_i = _ext_from_pair(ops, q_vals[2 * i], q_vals[2 * i + 1])
            t_at_z = ops.add(t_at_z, ops.mul(q_i, next(z_pows)))
        ops.enforce_equal(total, ops.mul(t_at_z, zh_at_z))

    # ---- PoW --------------------------------------------------------------
    if vk.pow_bits > 0:
        seed = t.get_multiple_challenges(4)
        h = circuit_hash_leaf(cs, seed + [ap.pow_challenge])
        h_bits = decompose_challenge_canonical(cs, h[0])
        for b in h_bits[: vk.pow_bits]:
            FmaGate.enforce_fma(
                cs, cs.one_var(), b, cs.zero_var(), cs.zero_var(), 1, 0
            )
        t.witness_field_elements([ap.pow_challenge])

    # ---- queries ----------------------------------------------------------
    with _span("recursion.queries"):
        assert len(proof.queries) == vk.num_queries
        omega = gl.omega(log_n)
        zw = ops.mul_by_base_constant(z_chal, omega)
        pi_locs = vk.public_input_locations
        bs = CircuitBitSource(cs, log_full)
        omega_full = gl.omega(log_full)
        g = gl.MULTIPLICATIVE_GENERATOR
        for q in ap.queries:
            idx_bits = bs.get_index_bits(t, log_full)
            _verify_merkle_path(
                cs, bops, q.witness.leaf_values, q.witness.path, ap.witness_cap,
                idx_bits,
            )
            _verify_merkle_path(
                cs, bops, q.stage2.leaf_values, q.stage2.path, ap.stage2_cap,
                idx_bits,
            )
            _verify_merkle_path(
                cs, bops, q.quotient.leaf_values, q.quotient.path,
                ap.quotient_cap, idx_bits,
            )
            _verify_merkle_path(
                cs, bops, q.setup.leaf_values, q.setup.path,
                avk.setup_merkle_cap, idx_bits,
            )
            assert len(q.witness.leaf_values) == Ct + W + M
            assert len(q.setup.leaf_values) == Ct + K + TW
            assert len(q.stage2.leaf_values) == S
            assert len(q.quotient.leaf_values) == 2 * Q

            # x = g·ω^brev(idx): nat bit (log-1-j) = idx bit j
            x = _point_from_bits(bops, idx_bits, omega_full, g)
            f_all = (
                [ops.from_base_var(v) for v in q.witness.leaf_values]
                + [ops.from_base_var(v) for v in q.setup.leaf_values]
                + [ops.from_base_var(v) for v in q.stage2.leaf_values]
                + [ops.from_base_var(v) for v in q.quotient.leaf_values]
            )
            inv_xz = ops.inv(ops.sub(ops.from_base_var(x), z_chal))
            inv_xzw = ops.inv(ops.sub(ops.from_base_var(x), zw))
            h_val = ops.zero()
            ch_iter = _PowIter(ops, deep_ch)
            for i in range(B):
                diff = ops.sub(f_all[i], vals[i])
                h_val = ops.add(
                    h_val, ops.mul(ops.mul(diff, inv_xz), next(ch_iter))
                )
            for i in range(2):
                f = ops.from_base_var(q.stage2.leaf_values[i])
                diff = ops.sub(f, ap.values_at_z_omega[i])
                h_val = ops.add(
                    h_val, ops.mul(ops.mul(diff, inv_xzw), next(ch_iter))
                )
            if lookups:
                inv_x = bops.inv(x)
                ab_off = 2 * (1 + (num_chunks - 1))
                for i in range(R + 1):
                    ch = next(ch_iter)
                    f_pair = (
                        q.stage2.leaf_values[ab_off + 2 * i],
                        q.stage2.leaf_values[ab_off + 2 * i + 1],
                    )
                    diff = ops.sub(f_pair, ap.values_at_0[i])
                    h_val = ops.add(
                        h_val, ops.mul(ops.mul_by_base(diff, inv_x), ch)
                    )
            for k_pi, (col, row) in enumerate(pi_locs):
                ch = next(ch_iter)
                pt = gl.pow_(omega, row)
                diff = bops.sub(
                    q.witness.leaf_values[col], ap.public_inputs[k_pi]
                )
                denom = bops.inv(
                    FmaGate.fma(cs, bops.one(), x, cs.allocate_constant(pt),
                                1, gl.P - 1)
                )
                tb = bops.mul(diff, denom)
                h_val = ops.add(h_val, ops.mul_by_base(ch, tb))

            # FRI chain (grouped oracles per the folding schedule): each leaf
            # carries a whole 2^k fold subtree; the circuit folds the entire
            # leaf with sub-challenges ch, ch^2, ... (reference fri/mod.rs:362)
            assert len(q.fri) == len(schedule)
            cur_expected = None
            off = 0
            for r, (k_r, oq) in enumerate(zip(schedule, q.fri)):
                block = 1 << k_r
                assert len(oq.leaf_values) == 2 * block
                leaf_idx_bits = idx_bits[off + k_r :]
                _verify_merkle_path(
                    cs, bops, oq.leaf_values, oq.path, ap.fri_caps[r],
                    leaf_idx_bits,
                )
                points = [
                    (oq.leaf_values[2 * j], oq.leaf_values[2 * j + 1])
                    for j in range(block)
                ]
                # the value this query tracks = points muxed by the in-block bits
                sel_vals = list(points)
                for b in idx_bits[off : off + k_r]:
                    sel_vals = [
                        ops.select(b, sel_vals[2 * i + 1], sel_vals[2 * i])
                        for i in range(len(sel_vals) // 2)
                    ]
                mine = sel_vals[0]
                if cur_expected is None:
                    ops.enforce_equal(mine, h_val)
                else:
                    ops.enforce_equal(mine, cur_expected)
                # fold the whole leaf down k_r times
                dbits = idx_bits[off + k_r : log_full]
                fold_vals = points
                ch = fri_challenges[r]
                for j in range(k_r):
                    fr = off + j
                    log_nr = log_full - fr
                    omega_r = gl.pow_(omega_full, 1 << fr)
                    shift_r = gl.pow_(g, 1 << fr)
                    # the dbits product is invariant in m: synthesize it once
                    # per sub-fold, then scale by the per-m host constant
                    base_point = _point_from_bits(bops, dbits, omega_r, 1)
                    nxt = []
                    for m in range(len(fold_vals) // 2):
                        # even element's global index: low bit 0, then the
                        # STATIC bits of m, then the leaf index bits
                        static_nat = 0
                        for tbit in range(k_r - j - 1):
                            if (m >> tbit) & 1:
                                static_nat += 1 << (log_nr - 2 - tbit)
                        shift_eff = gl.mul(
                            shift_r, gl.pow_(omega_r, static_nat)
                        )
                        x_r = bops.mul(base_point, bops.constant(shift_eff))
                        even, odd = fold_vals[2 * m], fold_vals[2 * m + 1]
                        s = ops.add(even, odd)
                        d = ops.sub(even, odd)
                        dox = ops.mul_by_base(d, bops.inv(x_r))
                        folded = ops.add(s, ops.mul(dox, ch))
                        nxt.append(ops.mul_by_base_constant(folded, INV2))
                    fold_vals = nxt
                    ch = ops.mul(ch, ch)
                cur_expected = fold_vals[0]
                off += k_r

            # final monomial evaluation at the fully folded point
            log_fin = log_full - num_folds
            fin_bits = idx_bits[num_folds : num_folds + log_fin]
            shift_fin = gl.pow_(g, 1 << num_folds)
            x_fin = _point_from_bits(bops, fin_bits, gl.omega(log_fin), shift_fin)
            acc = ops.zero()
            xp = ops.one()
            for c in ap.final_fri_monomials:
                acc = ops.add(acc, ops.mul(c, xp))
                xp = ops.mul_by_base(xp, x_fin)
            ops.enforce_equal(acc, cur_expected)

    # what this inner proof cost the outer trace (rows opened since entry;
    # a shared constants row or a tail row a later proof fills counts for
    # the proof that opened it)
    _metrics.count("recursion.inner_proofs")
    _metrics.count("recursion.verifier_rows", cs.next_row - first_row)
    placed = cs.row_gate[first_row : cs.next_row]
    _metrics.count("recursion.permutation_rows", sum(
        int((placed == cs.gate_index[name]).sum())
        for name in ("poseidon2_flat", "poseidon_flat")
        if name in cs.gate_index
    ))
    return ap.public_inputs, avk.setup_merkle_cap
