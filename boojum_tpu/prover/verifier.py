"""Plain (host) verifier.

Counterpart of `/root/reference/src/cs/implementations/verifier.rs:888`:
transcript replay, quotient reconstruction at z via the same gate evaluators
(over ExtScalarOps — the verifier-side face of the field-like contract),
copy-permutation and log-derivative lookup relations at z, the lookup
sum check over the openings at 0 (verifier.rs:1242), and DEEP/FRI query
checking against Merkle caps. Pure python ints: the verifier is tiny compared
to proving and needs no device.
"""

from __future__ import annotations

from ..field import gl
from ..field import extension as ext_f
from ..merkle import verify_proof_over_cap
from ..transcript import BitSource, make_transcript
from ..cs.field_like import ExtScalarOps
from ..cs.gates.base import RowView, TermsCollector
from .fri import fri_verify_queries, INV2
from .pow import pow_verify
from .stages import chunk_columns
from .setup import non_residues_for_copy_permutation

W_EXT = (0, 1)  # the extension generator (sqrt of 7)


class _ZRowView:
    """RowView over values-at-z for one gate instance chunk."""

    def __init__(self, wit_vals, const_vals, var_off, wit_off, const_off, num_copy):
        self.wit_vals = wit_vals
        self.const_vals = const_vals
        self.var_off = var_off
        self.wit_off = wit_off
        self.const_off = const_off
        self.num_copy = num_copy

    def v(self, i):
        return self.wit_vals[self.var_off + i]

    def w(self, i):
        return self.wit_vals[self.num_copy + self.wit_off + i]

    def c(self, i):
        return self.const_vals[self.const_off + i]


def ext_from_pair(c0, c1):
    """Value of an ext-coefficient poly from its two base-poly openings."""
    return ext_f.add_s(c0, ext_f.mul_s(c1, W_EXT))


def verify(vk, proof, gates) -> bool:
    geometry = vk.geometry
    n = vk.trace_len
    log_n = n.bit_length() - 1
    L = vk.fri_lde_factor
    Q = vk.effective_quotient_degree()
    log_full = log_n + (L.bit_length() - 1)
    N = n * L
    Ct = vk.num_copy_cols  # ALL columns under copy permutation
    Cg = geometry.num_columns_under_copy_permutation
    W = vk.num_wit_cols
    lp = vk.lookup_params
    lookups = lp is not None and lp.is_enabled
    lk_specialized = lookups and lp.use_specialized_columns
    M = 1 if lookups else 0
    wdt = lp.width if lookups else 0
    if lk_specialized:
        R = lp.num_repetitions
    elif lookups:
        R = Cg // wdt  # general mode: sub-arguments tile the general columns
    else:
        R = 0
    K = geometry.num_constant_columns + (1 if lk_specialized else 0)
    TW = (wdt + 1) if lookups else 0
    if not lk_specialized and Ct != Cg:
        return False
    if lk_specialized and Ct != Cg + R * wdt:
        return False
    if [g.name for g in gates] != list(vk.gate_names):
        return False
    if len(proof.public_inputs) != len(vk.public_input_locations):
        return False

    num_chunks = len(chunk_columns(Ct, geometry.max_allowed_constraint_degree))
    S = 2 * (1 + (num_chunks - 1)) + 2 * R + 2 * M  # z, partials, A_i, B
    B = (Ct + W + M) + (Ct + K + TW) + S + 2 * Q
    if len(proof.values_at_z) != B or len(proof.values_at_z_omega) != 2:
        return False
    if len(proof.values_at_0) != R + M:
        return False

    # ---- transcript replay ------------------------------------------------
    # every opened leaf and path is checked on the host under the key's
    # tree hasher (Blake2s through hashlib, compat/blake2s_tree.py)
    tree_hasher = getattr(vk, "tree_hasher", "poseidon2")
    t = make_transcript(getattr(vk, 'transcript', 'poseidon2'))
    t.witness_merkle_tree_cap(vk.setup_merkle_cap)
    t.witness_field_elements(proof.public_inputs)
    t.witness_merkle_tree_cap(proof.witness_cap)
    beta = t.get_ext_challenge()
    gamma = t.get_ext_challenge()
    if lookups:
        lookup_beta = t.get_ext_challenge()
        lookup_gamma = t.get_ext_challenge()
    t.witness_merkle_tree_cap(proof.stage2_cap)
    alpha = t.get_ext_challenge()
    t.witness_merkle_tree_cap(proof.quotient_cap)
    z_chal = t.get_ext_challenge()
    for v in proof.values_at_z:
        t.witness_field_elements(v)
    for v in proof.values_at_z_omega:
        t.witness_field_elements(v)
    for v in proof.values_at_0:
        t.witness_field_elements(v)
    deep_ch = t.get_ext_challenge()
    # FRI replay — ALL security parameters come from the VK, never the proof
    from .fri import fold_schedule

    try:
        schedule = fold_schedule(
            n, vk.fri_final_degree, getattr(vk, "fri_folding_schedule", None)
        )
    except AssertionError:
        return False
    num_folds = sum(schedule)
    if len(proof.fri_caps) != len(schedule):
        return False
    fri_challenges = []
    for r in range(len(schedule)):
        t.witness_merkle_tree_cap(proof.fri_caps[r])
        fri_challenges.append(t.get_ext_challenge())
    if len(proof.final_fri_monomials) != (n >> num_folds):
        return False
    for c0, c1 in proof.final_fri_monomials:
        t.witness_field_elements([c0, c1])

    # ---- split openings ---------------------------------------------------
    vals = [tuple(v) for v in proof.values_at_z]
    wit_vals = vals[: Ct + W + M]
    sigma_vals = vals[Ct + W + M : 2 * Ct + W + M]
    const_vals = vals[2 * Ct + W + M : 2 * Ct + W + M + K]
    table_vals = vals[2 * Ct + W + M + K : 2 * Ct + W + M + K + TW]
    s2_vals = vals[2 * Ct + W + M + K + TW : 2 * Ct + W + M + K + TW + S]
    q_vals = vals[2 * Ct + W + M + K + TW + S :]

    # ---- quotient identity at z ------------------------------------------
    alpha_pows = _powers_iter(alpha)
    total = ExtScalarOps.zero()
    for gid, gate in enumerate(gates):
        if gate.num_terms == 0:
            continue
        path = vk.selector_paths[gid]
        sel = ExtScalarOps.one()
        for b, bit in enumerate(path):
            cb = const_vals[b]
            sel = ext_f.mul_s(sel, cb if bit else ext_f.sub_s((1, 0), cb))
        reps = gate.num_repetitions(geometry)
        gate_acc = ExtScalarOps.zero()
        for inst in range(reps):
            row = _ZRowView(
                wit_vals, const_vals, inst * gate.principal_width,
                inst * gate.witness_width, len(path), Ct,
            )
            dst = TermsCollector()
            gate.evaluate(ExtScalarOps, row, dst)
            if len(dst.terms) != gate.num_terms:
                return False
            for term in dst.terms:
                gate_acc = ext_f.add_s(
                    gate_acc, ext_f.mul_s(term, next(alpha_pows))
                )
        total = ext_f.add_s(total, ext_f.mul_s(sel, gate_acc))

    # copy-permutation terms at z
    z_at_z = ext_from_pair(s2_vals[0], s2_vals[1])
    z_at_zw = ext_from_pair(
        tuple(proof.values_at_z_omega[0]), tuple(proof.values_at_z_omega[1])
    )
    partial_at_z = [
        ext_from_pair(s2_vals[2 + 2 * j], s2_vals[3 + 2 * j])
        for j in range(num_chunks - 1)
    ]
    non_residues = non_residues_for_copy_permutation(Ct)
    chunks = chunk_columns(Ct, geometry.max_allowed_constraint_degree)
    # L_0(z) = (z^n - 1)/(n (z - 1))
    z_pow_n = ext_f.pow_s(z_chal, n)
    zh_at_z = ext_f.sub_s(z_pow_n, ext_f.ONE_S)
    l0_at_z = ext_f.mul_s(
        ext_f.mul_s(zh_at_z, (gl.inv(n), 0)),
        ext_f.inv_s(ext_f.sub_s(z_chal, ext_f.ONE_S)),
    )
    term = ext_f.mul_s(l0_at_z, ext_f.sub_s(z_at_z, ext_f.ONE_S))
    total = ext_f.add_s(total, ext_f.mul_s(term, next(alpha_pows)))
    lhs_seq = partial_at_z + [z_at_zw]
    rhs_seq = [z_at_z] + partial_at_z
    for j, chunk in enumerate(chunks):
        num_p = ext_f.ONE_S
        den_p = ext_f.ONE_S
        for col in chunk:
            w = wit_vals[col]
            kx = ext_f.mul_by_base_s(z_chal, non_residues[col])
            num = ext_f.add_s(ext_f.add_s(w, ext_f.mul_s(beta, kx)), gamma)
            den = ext_f.add_s(
                ext_f.add_s(w, ext_f.mul_s(beta, sigma_vals[col])), gamma
            )
            num_p = ext_f.mul_s(num_p, num)
            den_p = ext_f.mul_s(den_p, den)
        rel = ext_f.sub_s(
            ext_f.mul_s(lhs_seq[j], den_p), ext_f.mul_s(rhs_seq[j], num_p)
        )
        total = ext_f.add_s(total, ext_f.mul_s(rel, next(alpha_pows)))

    # lookup terms at z (A_i·den − 1, B·den_t − M) + the sum check at 0
    if lookups:
        ab_off = 2 * (1 + (num_chunks - 1))
        gpow = ext_f.powers_s(lookup_gamma, wdt + 1)
        if lk_specialized:
            tid_at_z = const_vals[K - 1]
            a_numerator = ext_f.ONE_S
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
            if mk_gid is None:
                return False  # general-mode VK but no marker gate supplied
            mk_path = vk.selector_paths[mk_gid]
            tid_at_z = const_vals[len(mk_path)]
            sel_at_z = ext_f.ONE_S
            for bdx, bit in enumerate(mk_path):
                cb = const_vals[bdx]
                sel_at_z = ext_f.mul_s(
                    sel_at_z,
                    cb if bit else ext_f.sub_s((1, 0), cb),
                )
            a_numerator = sel_at_z
            col_base = 0
        for i in range(R):
            a_i = ext_from_pair(
                s2_vals[ab_off + 2 * i], s2_vals[ab_off + 2 * i + 1]
            )
            den = lookup_beta
            for j in range(wdt):
                wv = wit_vals[col_base + i * wdt + j]
                den = ext_f.add_s(den, ext_f.mul_s(gpow[j], wv))
            den = ext_f.add_s(den, ext_f.mul_s(gpow[wdt], tid_at_z))
            rel = ext_f.sub_s(ext_f.mul_s(a_i, den), a_numerator)
            total = ext_f.add_s(total, ext_f.mul_s(rel, next(alpha_pows)))
        b_at_z = ext_from_pair(
            s2_vals[ab_off + 2 * R], s2_vals[ab_off + 2 * R + 1]
        )
        den = lookup_beta
        for j in range(wdt + 1):
            den = ext_f.add_s(den, ext_f.mul_s(gpow[j], table_vals[j]))
        m_at_z = wit_vals[Ct + W]
        rel = ext_f.sub_s(ext_f.mul_s(b_at_z, den), m_at_z)
        total = ext_f.add_s(total, ext_f.mul_s(rel, next(alpha_pows)))
        # sum over H of (sum_i A_i - B) must vanish:  sum_i A_i(0) == B(0)
        a_sum = ext_f.ZERO_S
        for i in range(R):
            a_sum = ext_f.add_s(a_sum, tuple(proof.values_at_0[i]))
        if tuple(a_sum) != tuple(proof.values_at_0[R]):
            return False

    # T(z) from quotient chunks: sum z^{i n} * q_i(z)
    t_at_z = ext_f.ZERO_S
    for i in range(Q):
        q_i = ext_from_pair(q_vals[2 * i], q_vals[2 * i + 1])
        t_at_z = ext_f.add_s(
            t_at_z, ext_f.mul_s(q_i, ext_f.pow_s(z_chal, i * n))
        )
    if total != ext_f.mul_s(t_at_z, zh_at_z):
        return False

    # ---- PoW + queries ----------------------------------------------------
    if not pow_verify(t, vk.pow_bits, proof.pow_challenge):
        return False
    if len(proof.queries) != vk.num_queries:
        return False
    omega = gl.omega(log_n)
    zw = ext_f.mul_by_base_s(z_chal, omega)
    pi_locs = vk.public_input_locations
    bs = BitSource(log_full)
    for q in proof.queries:
        idx = bs.get_index(t, log_full)
        # oracle membership
        if not verify_proof_over_cap(
            q.witness.leaf_values, q.witness.path, proof.witness_cap, idx,
            tree_hasher,
        ):
            return False
        if not verify_proof_over_cap(
            q.stage2.leaf_values, q.stage2.path, proof.stage2_cap, idx,
            tree_hasher,
        ):
            return False
        if not verify_proof_over_cap(
            q.quotient.leaf_values, q.quotient.path, proof.quotient_cap, idx,
            tree_hasher,
        ):
            return False
        if not verify_proof_over_cap(
            q.setup.leaf_values, q.setup.path, vk.setup_merkle_cap, idx,
            tree_hasher,
        ):
            return False
        if (
            len(q.witness.leaf_values) != Ct + W + M
            or len(q.setup.leaf_values) != Ct + K + TW
            or len(q.stage2.leaf_values) != S
            or len(q.quotient.leaf_values) != 2 * Q
        ):
            return False
        # recompute the DEEP codeword value h(x) at the queried point
        x = gl.mul(
            gl.MULTIPLICATIVE_GENERATOR, gl.pow_(gl.omega(log_full), _brev(idx, log_full))
        )
        f_all = (
            [ (v, 0) for v in q.witness.leaf_values ]
            + [ (v, 0) for v in q.setup.leaf_values ]
            + [ (v, 0) for v in q.stage2.leaf_values ]
            + [ (v, 0) for v in q.quotient.leaf_values ]
        )
        inv_xz = ext_f.inv_s(ext_f.sub_s((x, 0), z_chal))
        inv_xzw = ext_f.inv_s(ext_f.sub_s((x, 0), zw))
        h = ext_f.ZERO_S
        ch_iter = _powers_iter(deep_ch)
        for i in range(B):
            diff = ext_f.sub_s(f_all[i], vals[i])
            h = ext_f.add_s(
                h, ext_f.mul_s(ext_f.mul_s(diff, inv_xz), next(ch_iter))
            )
        for i in range(2):
            f = (q.stage2.leaf_values[i], 0)
            diff = ext_f.sub_s(f, tuple(proof.values_at_z_omega[i]))
            h = ext_f.add_s(
                h, ext_f.mul_s(ext_f.mul_s(diff, inv_xzw), next(ch_iter))
            )
        if lookups:
            inv_x = gl.inv(x)
            ab_off = 2 * (1 + (num_chunks - 1))
            for i in range(R + 1):
                ch = next(ch_iter)
                f_pair = (
                    q.stage2.leaf_values[ab_off + 2 * i],
                    q.stage2.leaf_values[ab_off + 2 * i + 1],
                )
                diff = ext_f.sub_s(f_pair, tuple(proof.values_at_0[i]))
                h = ext_f.add_s(
                    h, ext_f.mul_s(ext_f.mul_by_base_s(diff, inv_x), ch)
                )
        for k, (col, row) in enumerate(pi_locs):
            ch = next(ch_iter)
            pt = gl.pow_(omega, row)
            diff = gl.sub(q.witness.leaf_values[col], proof.public_inputs[k])
            tb = gl.mul(diff, gl.inv(gl.sub(x, pt)))
            h = ext_f.add_s(h, ext_f.mul_by_base_s(ch, tb))
        # FRI chain (grouped oracles per the folding schedule)
        if len(q.fri) != len(schedule):
            return False
        leaves = []
        fidx = idx
        for r, (k, oq) in enumerate(zip(schedule, q.fri)):
            block = 1 << k
            leaf_idx = fidx >> k
            if len(oq.leaf_values) != 2 * block:
                return False
            if not verify_proof_over_cap(
                oq.leaf_values, oq.path, proof.fri_caps[r], leaf_idx,
                tree_hasher,
            ):
                return False
            leaves.append(
                [
                    (oq.leaf_values[2 * j], oq.leaf_values[2 * j + 1])
                    for j in range(block)
                ]
            )
            fidx = leaf_idx
        # base oracle value must equal recomputed h
        if tuple(leaves[0][idx % (1 << schedule[0])]) != tuple(h):
            return False
        if not fri_verify_queries(
            schedule, fri_challenges,
            [tuple(c) for c in proof.final_fri_monomials],
            idx, leaves, log_full,
        ):
            return False
    return True


def _powers_iter(a):
    cur = ext_f.ONE_S
    aa = (int(a[0]), int(a[1]))
    while True:
        yield cur
        cur = ext_f.mul_s(cur, aa)


def _brev(i: int, bits: int) -> int:
    out = 0
    for b in range(bits):
        out |= ((i >> b) & 1) << (bits - 1 - b)
    return out
