"""Full reference-dialect verifier (host).

A faithful reimplementation of `Verifier::verify`
(`/root/reference/src/cs/implementations/verifier.rs:888-2520`) over the
parsed artifacts: transcript replay, challenge derivation, the quotient
identity at z (lookup + specialized + general-purpose gate terms + copy
permutation), DEEP quotening, FRI fold simulation with the reference's
folding schedule (`prover.rs:2281`), Merkle/cap checks, and final monomial
evaluation. Gate term order comes from `compat.gates`; the selector paths
come from the VK's `selectors_placement` tree.
"""

from __future__ import annotations

from ..field import gl
from .gates import (
    Boolean,
    ConstantsAllocator,
    DotProduct4,
    Fma,
    ONE,
    ParallelSelection4,
    Poseidon2Flattened,
    Reduction4,
    Selection,
    U8x4Fma,
    UIntXAdd,
    ZERO,
    ZeroCheck,
    e_add,
    e_inv,
    e_mul,
    e_mul_base,
    e_pow,
    e_sub,
)
from .serde import ReferenceProof, ReferenceVk
from .transcript import (
    BoolsBuffer,
    ReferenceTranscript,
    u64_from_lsb_first_bits,
)
from ..hashes.poseidon2 import Poseidon2SpongeHost


def era_main_vm_verifier_config():
    """Gate configuration of the Era main-VM circuit the golden artifacts
    belong to. The general-purpose order is pinned by the golden VK's
    selector tree (gate_idx -> (num_constants, degree) uniquely identifies
    each gate; see /root/reference/vk.json selectors_placement and the gate
    set reconstructed in recursive_verifier.rs:2290-2460)."""
    return {
        "general_purpose_gates": [
            ("constants_allocator", ConstantsAllocator),
            ("u8x4_fma", U8x4Fma),
            ("poseidon2_flattened", Poseidon2Flattened),
            ("dot_product_4", DotProduct4),
            ("zero_check", ZeroCheck),
            ("fma", Fma),
            ("uintx_add_32", UIntXAdd),
            ("selection", Selection),
            ("parallel_selection_4", ParallelSelection4),
            ("nop", None),
            ("reduction_4", Reduction4),
        ],
        # (name, evaluator, num_repetitions, share_constants); order matters
        # for specialized column offsets and challenge consumption. The
        # lookup's specialized columns always come first.
        "specialized_gates": [("boolean", Boolean, 1, False)],
    }


import functools


@functools.lru_cache(maxsize=None)
def make_non_residues(num: int, domain_size: int) -> tuple[int, ...]:
    """Reference utils.rs:636 — successive integers that are quadratic
    non-residues and lie in distinct multiplicative cosets of the domain.
    Cached: the reference-dialect prover hits this once per quotient-coset
    point through `t_accumulator_at`."""
    out: list[int] = []
    current = 1
    legendre_exp = (gl.P - 1) // 2
    while len(out) < num:
        current += 1
        if gl.pow_(current, legendre_exp) != gl.P - 1:
            continue
        tmp = gl.pow_(current, domain_size)
        if tmp == 1:
            continue
        if any(gl.pow_(t, domain_size) == tmp for t in out):
            continue
        out.append(current)
    return tuple(out)


def non_residues_for_copy_permutation(domain_size: int, num_columns: int):
    return [1] + list(make_non_residues(num_columns - 1, domain_size))


def pow_seed_challenges(t):
    """Transcript challenges seeding the Blake2s PoW (verifier.rs:1960):
    256/CHAR_BITS = 4 challenges, plus one because 4 % CHAR_BITS != 0 — a
    reference quirk kept for byte parity. Shared by the verifier and the
    reference-dialect prover so the two transcripts cannot desynchronize."""
    num_chal = 256 // 64
    if num_chal % 64 != 0:
        num_chal += 1
    return t.get_multiple_challenges(num_chal)


def compute_fri_schedule(
    security_bits: int,
    cap_size: int,
    pow_bits: int,
    rate_log_two: int,
    initial_degree_log_two: int,
):
    """Reference prover.rs:2281 — (new_pow_bits, num_queries, schedule,
    final_expected_degree)."""
    assert security_bits > pow_bits
    raw = security_bits - pow_bits
    new_pow_bits = pow_bits
    if raw % rate_log_two != 0:
        deficit = rate_log_two - (raw % rate_log_two)
        if new_pow_bits >= deficit:
            new_pow_bits -= deficit
    raw = security_bits - new_pow_bits
    num_queries = raw // rate_log_two + (1 if raw % rate_log_two else 0)
    candidate = cap_size >> rate_log_two
    folding_stop_degree = max(1, candidate)
    stop_log2 = folding_stop_degree.bit_length() - 1
    degree = initial_degree_log_two
    cap_log2 = cap_size.bit_length() - 1
    schedule = []
    while degree > stop_log2:
        if degree + rate_log_two <= cap_log2:
            break
        if degree - stop_log2 >= 3:
            degree -= 3
            schedule.append(3)
        elif degree - stop_log2 == 2:
            degree -= 2
            schedule.append(2)
        else:
            degree -= 1
            schedule.append(1)
            break
        if degree + rate_log_two <= cap_log2:
            break
    assert degree + rate_log_two >= cap_log2
    return new_pow_bits, num_queries, schedule, 1 << degree


def _verify_merkle_path(
    leaf_elements, path, cap, idx, tree_hasher="poseidon2"
):
    if tree_hasher == "blake2s":
        from .blake2s_tree import verify_path

        return verify_path(leaf_elements, path, cap, idx)
    cur = tuple(Poseidon2SpongeHost.hash_leaf(leaf_elements))
    i = idx
    for sib in path:
        if i & 1 == 0:
            cur = tuple(Poseidon2SpongeHost.hash_node(cur, sib))
        else:
            cur = tuple(Poseidon2SpongeHost.hash_node(sib, cur))
        i >>= 1
    return cur == tuple(cap[i])


def _compute_selector_subpath_at_z(path, buffer, constants):
    """verifier.rs:278 — product over path prefixes of c_b / (1-c_b)."""
    key = tuple(path)
    if key in buffer or not path:
        return
    idx = len(path) - 1
    if len(path) == 1:
        poly = constants[idx]
        buffer[key] = poly if path[0] else e_sub(ONE, poly)
        return
    parent = path[:-1]
    _compute_selector_subpath_at_z(parent, buffer, constants)
    prefix = buffer[tuple(parent)]
    other = constants[idx]
    if path[-1]:
        buffer[key] = e_mul(other, prefix)
    else:
        buffer[key] = e_mul(e_sub(ONE, other), prefix)


def _quotening(acc, sources, values_at, domain_element, at, challenges):
    """(sum of ch_i*(f_i - y_i)) / (x - at) added to acc
    (verifier.rs:2498 quotening_operation)."""
    assert len(sources) == len(values_at) == len(challenges)
    denom = e_inv(e_sub((domain_element % gl.P, 0), at))
    local = ZERO
    for poly_value, value_at, ch in zip(sources, values_at, challenges):
        local = e_add(local, e_mul(ch, e_sub(poly_value, value_at)))
    return e_add(acc, e_mul(local, denom))


def verify_reference_proof(
    vk: ReferenceVk,
    proof: ReferenceProof,
    config=None,
    check_quotient_identity: bool = True,
) -> bool:
    """Run the reference verification algorithm over parsed golden artifacts.

    With ``check_quotient_identity=False`` the algebraic quotient identity at
    z (the only step needing the CIRCUIT's gate configuration, which lives in
    the external era-zkevm_circuits crate, not in the VK) is skipped; all
    byte-level checks still run: transcript replay and challenge derivation,
    lookup sumcheck, proof-shape checks against the VK, FRI schedule
    reproduction, per-query Merkle/cap verification of all oracles, DEEP
    quotening consistency, FRI fold simulation, and final monomial
    evaluation. The gate configuration in `era_main_vm_verifier_config` is a
    best-effort reconstruction pinned by the VK's selector tree; the repo's
    own reconstruction (recursive_verifier.rs:2290) names a gate set whose
    selector tree would differ from this VK's, so the artifacts predate it.

    Malformed/hostile proofs are rejected with False, never an exception.
    """
    try:
        return _verify_impl(vk, proof, config, check_quotient_identity)
    except (KeyError, IndexError, ValueError, TypeError, AssertionError):
        # attacker-controlled JSON with missing fields or bad shapes must
        # reject, not crash the verifier
        return False


def derive_counts(vk, config):
    """Poly/term counts the reference derives from VK + gate config
    (verifier.rs:888 locals). Shared between `_verify_impl` and the
    reference-dialect prover (`compat.prove_reference`) so both sides
    agree on leaf widths, opening counts and challenge partition sizes."""
    lp = vk.lookup_parameters
    num_lookup_subarguments = lp.num_repetitions if lp.is_lookup else 0
    num_multiplicities_polys = 1 if lp.is_lookup else 0
    total_num_lookup_argument_terms = (
        num_lookup_subarguments + num_multiplicities_polys
    )
    lookup_specialized_vars = (
        lp.specialized_columns_per_subargument() * lp.num_repetitions
        if lp.is_lookup
        else 0
    )
    spec_gates = config["specialized_gates"]
    spec_gate_vars = sum(
        g.per_chunk[0] * reps for (_n, g, reps, _s) in spec_gates
    )
    total_vars_specialized = lookup_specialized_vars + spec_gate_vars
    num_variable_polys = (
        vk.num_columns_under_copy_permutation + total_vars_specialized
    )
    num_witness_polys = vk.num_witness_columns
    spec_gate_constants = sum(
        (0 if share else g.per_chunk[2] * reps)
        for (_n, g, reps, share) in spec_gates
    )
    # specialized lookup w/ table id as constant contributes 1 constant col
    lookup_specialized_constants = (
        1
        if (lp.mode == "UseSpecializedColumnsWithTableIdAsConstant")
        else 0
    )
    num_constant_polys = (
        vk.num_constant_columns
        + vk.extra_constant_polys_for_selectors
        + lookup_specialized_constants
        + spec_gate_constants
    )
    quotient_degree = vk.quotient_degree
    num_copy_permutation_polys = num_variable_polys
    c = num_copy_permutation_polys
    num_intermediate = 0
    if c > quotient_degree:
        num_intermediate = (
            c // quotient_degree + (1 if c % quotient_degree else 0) - 1
        )

    geom = {
        "num_columns_under_copy_permutation": (
            vk.num_columns_under_copy_permutation
        ),
        "num_witness_columns": vk.num_witness_columns,
        "num_constant_columns": vk.num_constant_columns,
    }
    gp_gates = config["general_purpose_gates"]
    gp_term_counts = [
        (g.num_terms * g.num_repetitions(geom)) if g is not None else 0
        for (_n, g) in gp_gates
    ]
    total_gp_terms = sum(gp_term_counts)
    spec_term_counts = [
        g.num_terms * reps for (_n, g, reps, _s) in spec_gates
    ]
    total_spec_terms = sum(spec_term_counts)

    total_num_terms = (
        total_num_lookup_argument_terms
        + total_spec_terms
        + total_gp_terms
        + 1
        + 1
        + num_intermediate
    )
    expected_lookup_polys_total = (
        (
            num_lookup_subarguments
            + num_multiplicities_polys * 2
            + lp.width
            + 1
        )
        if lp.is_lookup
        else 0
    )
    num_poly_values_at_z = (
        num_variable_polys
        + num_witness_polys
        + num_constant_polys
        + num_copy_permutation_polys
        + 1
        + num_intermediate
        + expected_lookup_polys_total
        + quotient_degree
    )
    return {
        "num_lookup_subarguments": num_lookup_subarguments,
        "num_multiplicities_polys": num_multiplicities_polys,
        "total_num_lookup_argument_terms": total_num_lookup_argument_terms,
        "lookup_specialized_vars": lookup_specialized_vars,
        "lookup_specialized_constants": lookup_specialized_constants,
        "num_variable_polys": num_variable_polys,
        "num_witness_polys": num_witness_polys,
        "num_constant_polys": num_constant_polys,
        "num_copy_permutation_polys": num_copy_permutation_polys,
        "num_intermediate": num_intermediate,
        "quotient_degree": quotient_degree,
        "geom": geom,
        "total_gp_terms": total_gp_terms,
        "total_spec_terms": total_spec_terms,
        "total_num_terms": total_num_terms,
        "expected_lookup_polys_total": expected_lookup_polys_total,
        "num_poly_values_at_z": num_poly_values_at_z,
    }


def split_alpha_powers(alpha, counts):
    """[1, a, a^2, ...] partitioned lookup | specialized | general | rest
    (copy-permutation) — the reference challenge consumption order."""
    powers = [ONE]
    for _ in range(1, counts["total_num_terms"]):
        powers.append(e_mul(powers[-1], alpha))
    tl = counts["total_num_lookup_argument_terms"]
    ts = counts["total_spec_terms"]
    tg = counts["total_gp_terms"]
    return {
        "lookup": powers[:tl],
        "specialized": powers[tl : tl + ts],
        "general": powers[tl + ts : tl + ts + tg],
        "remaining": powers[tl + ts + tg :],
    }


def t_accumulator_at(point, opened, ch, vk, config, counts):
    """The quotient-identity numerator T(x) at one evaluation point
    (verifier.rs:1242-1650): lookup terms, specialized-gate terms,
    general-purpose gate terms (selector-gated), and the copy-permutation
    terms, each weighted by its alpha-power partition.

    `point`: ext (c0, c1) evaluation point (z for the verifier; quotient-
    coset points for the reference-dialect prover).
    `opened`: dict of poly values at `point` — keys variables, witness,
    constants, sigmas, copy_z, copy_z_shifted, intermediates,
    multiplicities, lookup_a, mult_encoding, tables (lists of ext tuples).
    `ch`: dict with beta, gamma, lookup_beta, lookup_gamma and the alpha
    partitions from `split_alpha_powers`.
    """
    lp = vk.lookup_parameters
    spec_gates = config["specialized_gates"]
    gp_gates = config["general_purpose_gates"]
    geom = counts["geom"]
    quotient_degree = counts["quotient_degree"]
    num_lookup_subarguments = counts["num_lookup_subarguments"]

    variables_polys_values = opened["variables"]
    witness_polys_values = opened["witness"]
    constant_poly_values = opened["constants"]
    sigmas_values = opened["sigmas"]
    copy_permutation_z_at_z = opened["copy_z"]
    copy_permutation_z_at_z_omega = opened["copy_z_shifted"]
    grand_product_intermediate_polys = opened["intermediates"]
    multiplicities_polys_values = opened["multiplicities"]
    lookup_witness_encoding_polys_values = opened["lookup_a"]
    multiplicities_encoding_polys_values = opened["mult_encoding"]
    lookup_tables_columns = opened["tables"]

    t_accumulator = ZERO

    selectors_buffer = {}
    for gate_idx, (_name, g) in enumerate(gp_gates):
        path = vk.selectors_placement.output_placement(gate_idx)
        if path is not None:
            _compute_selector_subpath_at_z(
                path, selectors_buffer, constant_poly_values
            )
        else:
            assert g is None or g.num_terms == 0, _name

    if lp.is_lookup:
        lookup_beta = ch["lookup_beta"]
        lookup_gamma = ch["lookup_gamma"]
        assert lp.mode.startswith("UseSpecializedColumns"), (
            "only the specialized-columns lookup mode is implemented"
        )
        col_per_subarg = lp.specialized_columns_per_subargument()
        capacity = col_per_subarg + (
            1 if len(vk.table_ids_column_idxes) == 1 else 0
        )
        powers_of_gamma = [ONE]
        for _ in range(1, capacity):
            powers_of_gamma.append(
                e_mul(powers_of_gamma[-1], lookup_gamma)
            )
        lookup_table_columns_aggregated = lookup_beta
        for gpow, column in zip(powers_of_gamma, lookup_tables_columns):
            lookup_table_columns_aggregated = e_add(
                lookup_table_columns_aggregated, e_mul(gpow, column)
            )
        ch_it = iter(ch["lookup"])
        base = vk.num_columns_under_copy_permutation
        variables_for_lookup = variables_polys_values[
            base : base + col_per_subarg * num_lookup_subarguments
        ]
        table_id = (
            [constant_poly_values[vk.table_ids_column_idxes[0]]]
            if vk.table_ids_column_idxes
            else []
        )
        for i, a_poly in enumerate(lookup_witness_encoding_polys_values):
            cols = variables_for_lookup[
                i * col_per_subarg : (i + 1) * col_per_subarg
            ]
            contribution = lookup_beta
            for gpow, column in zip(powers_of_gamma, list(cols) + table_id):
                contribution = e_add(contribution, e_mul(gpow, column))
            contribution = e_mul(contribution, a_poly)
            contribution = e_sub(contribution, ONE)
            contribution = e_mul(contribution, next(ch_it))
            t_accumulator = e_add(t_accumulator, contribution)
        for b_poly, mult in zip(
            multiplicities_encoding_polys_values, multiplicities_polys_values
        ):
            contribution = e_mul(lookup_table_columns_aggregated, b_poly)
            contribution = e_sub(contribution, mult)
            contribution = e_mul(contribution, next(ch_it))
            t_accumulator = e_add(t_accumulator, contribution)

    constants_for_gp = (
        vk.num_constant_columns + vk.extra_constant_polys_for_selectors
    )

    # specialized gates (each with selector ONE, own column subranges)
    ch_off = 0
    var_off = (
        vk.num_columns_under_copy_permutation
        + counts["lookup_specialized_vars"]
    )
    const_off = constants_for_gp + counts["lookup_specialized_constants"]
    for (_name, g, reps, share) in spec_gates:
        vw, ww, cw = g.per_chunk
        gate_acc = ZERO
        term_i = 0
        for rep in range(reps):
            vo = var_off + rep * vw
            co = const_off + (0 if share else rep * cw)

            def var(i, _vo=vo):
                return variables_polys_values[_vo + i]

            def wit(i):
                return witness_polys_values[i]

            def const(i, _co=co):
                return constant_poly_values[_co + i]

            terms = []
            g.evaluate_once(var, wit, const, g.load_shared(const), terms.append)
            for term in terms:
                gate_acc = e_add(
                    gate_acc,
                    e_mul(term, ch["specialized"][ch_off + term_i]),
                )
                term_i += 1
        t_accumulator = e_add(t_accumulator, gate_acc)
        ch_off += g.num_terms * reps
        var_off += vw * reps
        const_off += 0 if share else cw * reps
    assert ch_off == counts["total_spec_terms"]

    # general purpose gates
    ch_off = 0
    for gate_idx, (_name, g) in enumerate(gp_gates):
        if g is None or g.num_terms == 0:
            continue
        path = vk.selectors_placement.output_placement(gate_idx)
        selector = selectors_buffer.pop(tuple(path))
        constant_placement_offset = len(path)
        reps = g.num_repetitions(geom)
        vw, _ww, cw = g.per_chunk

        def const_shared(i, _o=constant_placement_offset):
            return constant_poly_values[_o + i]

        shared = g.load_shared(const_shared)
        gate_acc = ZERO
        term_i = 0
        for rep in range(reps):
            vo = rep * vw
            co = constant_placement_offset + rep * cw

            def var(i, _vo=vo):
                return variables_polys_values[_vo + i]

            def wit(i):
                return witness_polys_values[i]

            def const(i, _co=co):
                return constant_poly_values[_co + i]

            terms = []
            g.evaluate_once(var, wit, const, shared, terms.append)
            assert len(terms) == g.num_terms, _name
            for term in terms:
                gate_acc = e_add(
                    gate_acc, e_mul(term, ch["general"][ch_off + term_i])
                )
                term_i += 1
        # destination.advance(): accumulator *= selector, once per gate
        t_accumulator = e_add(t_accumulator, e_mul(gate_acc, selector))
        ch_off += g.num_terms * reps
    assert ch_off == counts["total_gp_terms"]

    # copy permutation
    beta = ch["beta"]
    gamma = ch["gamma"]
    non_residues = non_residues_for_copy_permutation(
        vk.domain_size, counts["num_variable_polys"]
    )
    z_in_domain_size = e_pow(point, vk.domain_size)
    vanishing_at_z = e_sub(z_in_domain_size, ONE)
    ch_it = iter(ch["remaining"])
    # z(1) == 1 via unnormalized L1
    unnorm_l1_inv_at_z = e_mul(vanishing_at_z, e_inv(e_sub(point, ONE)))
    contribution = e_sub(copy_permutation_z_at_z, ONE)
    contribution = e_mul(contribution, unnorm_l1_inv_at_z)
    contribution = e_mul(contribution, next(ch_it))
    t_accumulator = e_add(t_accumulator, contribution)

    lhs_seq = grand_product_intermediate_polys + [
        copy_permutation_z_at_z_omega
    ]
    rhs_seq = [copy_permutation_z_at_z] + grand_product_intermediate_polys

    def chunks(seq, k):
        return [seq[i : i + k] for i in range(0, len(seq), k)]

    for lhs, rhs, chal, nr_chunk, var_chunk, sigma_chunk in zip(
        lhs_seq,
        rhs_seq,
        ch_it,
        chunks(non_residues, quotient_degree),
        chunks(variables_polys_values, quotient_degree),
        chunks(sigmas_values, quotient_degree),
    ):
        lhs_acc = lhs
        for variable, sigma in zip(var_chunk, sigma_chunk):
            subres = e_mul(sigma, beta)
            subres = e_add(subres, variable)
            subres = e_add(subres, gamma)
            lhs_acc = e_mul(lhs_acc, subres)
        rhs_acc = rhs
        for non_res, variable in zip(nr_chunk, var_chunk):
            subres = e_mul_base(point, non_res)
            subres = e_mul(subres, beta)
            subres = e_add(subres, variable)
            subres = e_add(subres, gamma)
            rhs_acc = e_mul(rhs_acc, subres)
        contribution = e_mul(e_sub(lhs_acc, rhs_acc), chal)
        t_accumulator = e_add(t_accumulator, contribution)
    return t_accumulator


def _verify_impl(vk, proof, config, check_quotient_identity):
    if config is None:
        config = era_main_vm_verifier_config()

    lp = vk.lookup_parameters
    pc = proof.proof_config
    if vk.cap_size != pc["merkle_tree_cap_size"]:
        return False
    if vk.fri_lde_factor != pc["fri_lde_factor"]:
        return False
    if vk.cap_size != len(vk.setup_merkle_tree_cap):
        return False
    if len(proof.public_inputs) != len(vk.public_inputs_locations):
        return False

    t = ReferenceTranscript()
    t.witness_merkle_tree_cap(vk.setup_merkle_tree_cap)
    public_inputs_with_values = []
    for (column, row), value in zip(
        vk.public_inputs_locations, proof.public_inputs
    ):
        public_inputs_with_values.append((column, row, value))
        t.witness_field_elements([value])
    if vk.cap_size != len(proof.witness_oracle_cap):
        return False
    t.witness_merkle_tree_cap(proof.witness_oracle_cap)
    beta = (t.get_challenge(), t.get_challenge())
    gamma = (t.get_challenge(), t.get_challenge())
    if lp.is_lookup:
        lookup_beta = (t.get_challenge(), t.get_challenge())
        lookup_gamma = (t.get_challenge(), t.get_challenge())
    if vk.cap_size != len(proof.stage_2_oracle_cap):
        return False
    t.witness_merkle_tree_cap(proof.stage_2_oracle_cap)
    alpha = (t.get_challenge(), t.get_challenge())

    counts = derive_counts(vk, config)
    num_lookup_subarguments = counts["num_lookup_subarguments"]
    num_multiplicities_polys = counts["num_multiplicities_polys"]
    total_num_lookup_argument_terms = counts[
        "total_num_lookup_argument_terms"
    ]
    num_variable_polys = counts["num_variable_polys"]
    num_witness_polys = counts["num_witness_polys"]
    num_constant_polys = counts["num_constant_polys"]
    num_copy_permutation_polys = counts["num_copy_permutation_polys"]
    num_intermediate = counts["num_intermediate"]
    quotient_degree = counts["quotient_degree"]
    alpha_partitions = split_alpha_powers(alpha, counts)

    if vk.cap_size != len(proof.quotient_oracle_cap):
        return False
    t.witness_merkle_tree_cap(proof.quotient_oracle_cap)
    z = (t.get_challenge(), t.get_challenge())
    for v in proof.values_at_z:
        t.witness_field_elements(v)
    for v in proof.values_at_z_omega:
        t.witness_field_elements(v)
    for v in proof.values_at_0:
        t.witness_field_elements(v)

    omega = gl.omega(vk.domain_size.bit_length() - 1)
    # public input opening tuples grouped by opening point
    public_input_opening_tuples = []
    for column, row, value in public_inputs_with_values:
        open_at = gl.pow_(omega, row)
        for el in public_input_opening_tuples:
            if el[0] == open_at:
                el[1].append((column, value))
                break
        else:
            public_input_opening_tuples.append([open_at, [(column, value)]])

    if len(proof.values_at_z) != counts["num_poly_values_at_z"]:
        return False
    if len(proof.values_at_z_omega) != 1:
        return False
    if len(proof.values_at_0) != total_num_lookup_argument_terms:
        return False

    # ---- quotient identity at z ------------------------------------------
    it = iter(proof.values_at_z)

    def take(n):
        return [next(it) for _ in range(n)]

    opened = {
        "variables": take(num_variable_polys),
        "witness": take(num_witness_polys),
        "constants": take(num_constant_polys),
        "sigmas": take(num_copy_permutation_polys),
        "copy_z": take(1)[0],
        "intermediates": take(num_intermediate),
        "multiplicities": take(num_multiplicities_polys),
        "lookup_a": take(num_lookup_subarguments),
        "mult_encoding": take(num_multiplicities_polys),
        "tables": take((lp.width + 1) if lp.is_lookup else 0),
        "copy_z_shifted": proof.values_at_z_omega[0],
    }
    quotient_chunks = list(it)
    assert len(quotient_chunks) == quotient_degree

    if lp.is_lookup:
        # sumcheck: sum A_i(0) == sum B(0)
        a_sum = ZERO
        for v in proof.values_at_0[:num_lookup_subarguments]:
            a_sum = e_add(a_sum, v)
        b_sum = ZERO
        for v in proof.values_at_0[num_lookup_subarguments:]:
            b_sum = e_add(b_sum, v)
        if a_sum != b_sum:
            return False

    challenges = dict(alpha_partitions)
    challenges["beta"] = beta
    challenges["gamma"] = gamma
    if lp.is_lookup:
        challenges["lookup_beta"] = lookup_beta
        challenges["lookup_gamma"] = lookup_gamma
    t_accumulator = t_accumulator_at(z, opened, challenges, vk, config, counts)

    z_in_domain_size = e_pow(z, vk.domain_size)
    vanishing_at_z = e_sub(z_in_domain_size, ONE)
    t_from_chunks = ZERO
    pow_acc = ONE
    for el in quotient_chunks:
        t_from_chunks = e_add(t_from_chunks, e_mul(el, pow_acc))
        pow_acc = e_mul(pow_acc, z_in_domain_size)
    t_from_chunks = e_mul(t_from_chunks, vanishing_at_z)
    if check_quotient_identity and t_accumulator != t_from_chunks:
        return False

    # ---- DEEP + FRI -------------------------------------------------------
    c0 = t.get_challenge()
    c1 = t.get_challenge()
    total_num_challenges = (
        len(proof.values_at_z)
        + len(proof.values_at_z_omega)
        + len(proof.values_at_0)
        + sum(len(s[1]) for s in public_input_opening_tuples)
    )
    deep_challenges = [ONE, (c0, c1)]
    cur = (c0, c1)
    for _ in range(2, total_num_challenges):
        cur = e_mul(cur, (c0, c1))
        deep_challenges.append(cur)
    deep_challenges = deep_challenges[:total_num_challenges]

    rate_log_two = vk.fri_lde_factor.bit_length() - 1
    new_pow_bits, num_queries, schedule, final_expected_degree = (
        compute_fri_schedule(
            pc["security_level"],
            pc["merkle_tree_cap_size"],
            pc["pow_bits"],
            rate_log_two,
            vk.domain_size.bit_length() - 1,
        )
    )
    if new_pow_bits != pc["pow_bits"]:
        return False

    expected_degree = vk.domain_size
    fri_intermediate_challenges = []
    if vk.cap_size != len(proof.fri_base_oracle_cap):
        return False
    t.witness_merkle_tree_cap(proof.fri_base_oracle_cap)
    c0 = t.get_challenge()
    c1 = t.get_challenge()
    chs = [(c0, c1)]
    cur = (c0, c1)
    for _ in range(1, schedule[0]):
        cur = e_mul(cur, cur)
        chs.append(cur)
    fri_intermediate_challenges.append(chs)
    expected_degree >>= schedule[0]

    if len(schedule[1:]) != len(proof.fri_intermediate_oracles_caps):
        return False
    for deg_log2, cap in zip(
        schedule[1:], proof.fri_intermediate_oracles_caps
    ):
        if vk.cap_size != len(cap):
            return False
        t.witness_merkle_tree_cap(cap)
        c0 = t.get_challenge()
        c1 = t.get_challenge()
        chs = [(c0, c1)]
        cur = (c0, c1)
        for _ in range(1, deg_log2):
            cur = e_mul(cur, cur)
            chs.append(cur)
        fri_intermediate_challenges.append(chs)
        expected_degree >>= deg_log2
    if final_expected_degree != expected_degree:
        return False
    if expected_degree != len(proof.final_fri_monomials[0]):
        return False
    if expected_degree != len(proof.final_fri_monomials[1]):
        return False
    t.witness_field_elements(proof.final_fri_monomials[0])
    t.witness_field_elements(proof.final_fri_monomials[1])

    if new_pow_bits != 0:
        challenges = pow_seed_challenges(t)
        # Blake2s PoW runner semantics (pow.rs:8,93): seed = challenges as
        # LE bytes; digest's first LE u64 needs pow_bits trailing zeros
        import hashlib

        seed = b"".join(int(c).to_bytes(8, "little") for c in challenges)
        digest = hashlib.blake2s(
            seed + int(proof.pow_challenge).to_bytes(8, "little")
        ).digest()
        word = int.from_bytes(digest[:8], "little")
        if word & ((1 << pc["pow_bits"]) - 1) != 0:
            return False
        low = proof.pow_challenge & 0xFFFFFFFF
        high = proof.pow_challenge >> 32
        t.witness_field_elements([low, high])

    lde_domain_size = vk.domain_size * vk.fri_lde_factor
    max_needed_bits = lde_domain_size.bit_length() - 1
    bools_buffer = BoolsBuffer(max_needed=max_needed_bits)
    num_bits_for_in_coset_index = max_needed_bits - rate_log_two
    base_tree_index_shift = vk.domain_size.bit_length() - 1
    assert num_bits_for_in_coset_index == base_tree_index_shift

    precomputed_powers = []
    precomputed_powers_inversed = []
    for i in range(lde_domain_size.bit_length()):
        w = gl.omega(i) if i else 1
        precomputed_powers.append(w)
        precomputed_powers_inversed.append(gl.inv(w))

    # interpolation steps: [1, w4^-1, w8^-1, w4^-1 * w8^-1]
    interpolation_steps = [1, 1, 1, 1]
    for idx in (1, 3):
        interpolation_steps[idx] = gl.mul(
            interpolation_steps[idx], precomputed_powers_inversed[2]
        )
    for idx in (2, 3):
        interpolation_steps[idx] = gl.mul(
            interpolation_steps[idx], precomputed_powers_inversed[3]
        )

    if num_queries != len(proof.queries_per_fri_repetition):
        return False

    base_oracle_depth = (
        lde_domain_size.bit_length() - 1 - (vk.cap_size.bit_length() - 1)
    )
    witness_leaf_size = (
        num_variable_polys + num_witness_polys + num_multiplicities_polys
    )
    stage_2_leaf_size = (
        1
        + num_intermediate
        + num_lookup_subarguments
        + num_multiplicities_polys
    ) * 2
    quotient_leaf_size = quotient_degree * 2
    setup_leaf_size = (
        num_copy_permutation_polys
        + num_constant_polys
        + ((lp.width + 1) if lp.is_lookup else 0)
    )

    z_polys_offset = 0
    intermediate_polys_offset = 2
    lookup_witness_encoding_polys_offset = (
        intermediate_polys_offset + num_intermediate * 2
    )
    lookup_multiplicities_encoding_polys_offset = (
        lookup_witness_encoding_polys_offset + num_lookup_subarguments * 2
    )
    constants_offset = num_copy_permutation_polys
    lookup_tables_values_offset = (
        num_copy_permutation_polys + num_constant_polys
    )
    lookup_multiplicities_offset = num_variable_polys + num_witness_polys
    base_coset_inverse = gl.inv(gl.MULTIPLICATIVE_GENERATOR)

    def cast_base(els):
        return [(int(e) % gl.P, 0) for e in els]

    def cast_ext(els):
        assert len(els) % 2 == 0
        return [
            (int(els[i]) % gl.P, int(els[i + 1]) % gl.P)
            for i in range(0, len(els), 2)
        ]

    z_omega = e_mul_base(z, omega)

    for q in proof.queries_per_fri_repetition:
        bits = bools_buffer.get_bits(t, max_needed_bits)
        inner_idx = u64_from_lsb_first_bits(
            bits[:num_bits_for_in_coset_index]
        )
        coset_idx = u64_from_lsb_first_bits(
            bits[num_bits_for_in_coset_index:]
        )
        base_tree_idx = (coset_idx << base_tree_index_shift) + inner_idx

        if len(q.witness.leaf_elements) != witness_leaf_size:
            return False
        if len(q.witness.proof) != base_oracle_depth:
            return False
        if not _verify_merkle_path(
            q.witness.leaf_elements,
            q.witness.proof,
            proof.witness_oracle_cap,
            base_tree_idx,
        ):
            return False
        if len(q.stage_2.leaf_elements) != stage_2_leaf_size:
            return False
        if len(q.stage_2.proof) != base_oracle_depth:
            return False
        if not _verify_merkle_path(
            q.stage_2.leaf_elements,
            q.stage_2.proof,
            proof.stage_2_oracle_cap,
            base_tree_idx,
        ):
            return False
        if len(q.quotient.leaf_elements) != quotient_leaf_size:
            return False
        if len(q.quotient.proof) != base_oracle_depth:
            return False
        if not _verify_merkle_path(
            q.quotient.leaf_elements,
            q.quotient.proof,
            proof.quotient_oracle_cap,
            base_tree_idx,
        ):
            return False
        if len(q.setup.leaf_elements) != setup_leaf_size:
            return False
        if len(q.setup.proof) != base_oracle_depth:
            return False
        if not _verify_merkle_path(
            q.setup.leaf_elements,
            q.setup.proof,
            vk.setup_merkle_tree_cap,
            base_tree_idx,
        ):
            return False

        # domain element from LSB-first bits
        domain_element = 1
        for a, b in zip(bits, precomputed_powers[1:]):
            if a:
                domain_element = gl.mul(domain_element, b)

        power_chunks = []
        skip_highest_powers = 0
        for deg_log2 in schedule:
            el = 1
            pairs = list(
                zip(
                    bits[skip_highest_powers:],
                    precomputed_powers_inversed[1:],
                )
            )[deg_log2:]
            for a, b in pairs:
                if a:
                    el = gl.mul(el, b)
            skip_highest_powers += deg_log2
            power_chunks.append(el)

        domain_element_for_quotiening = gl.mul(
            domain_element, gl.MULTIPLICATIVE_GENERATOR
        )
        domain_element_for_interpolation = domain_element_for_quotiening

        simulated = ZERO
        challenge_offset = 0
        sources = []
        sources += cast_base(
            q.witness.leaf_elements[:num_variable_polys]
        )
        sources += cast_base(
            q.witness.leaf_elements[
                num_variable_polys : num_variable_polys + num_witness_polys
            ]
        )
        sources += cast_base(
            q.setup.leaf_elements[
                constants_offset : constants_offset + num_constant_polys
            ]
        )
        sources += cast_base(
            q.setup.leaf_elements[:num_copy_permutation_polys]
        )
        sources += cast_ext(
            q.stage_2.leaf_elements[
                z_polys_offset:lookup_witness_encoding_polys_offset
            ]
        )
        if lp.is_lookup:
            sources += cast_base(
                q.witness.leaf_elements[
                    lookup_multiplicities_offset : lookup_multiplicities_offset
                    + num_multiplicities_polys
                ]
            )
            sources += cast_ext(
                q.stage_2.leaf_elements[
                    lookup_witness_encoding_polys_offset:
                ]
            )
            sources += cast_base(
                q.setup.leaf_elements[
                    lookup_tables_values_offset : lookup_tables_values_offset
                    + lp.width
                    + 1
                ]
            )
        sources += cast_ext(q.quotient.leaf_elements)
        assert len(sources) == len(proof.values_at_z)
        simulated = _quotening(
            simulated,
            sources,
            proof.values_at_z,
            domain_element_for_quotiening,
            z,
            deep_challenges[
                challenge_offset : challenge_offset + len(sources)
            ],
        )
        challenge_offset += len(sources)

        sources_zw = cast_ext(
            q.stage_2.leaf_elements[z_polys_offset:intermediate_polys_offset]
        )
        simulated = _quotening(
            simulated,
            sources_zw,
            proof.values_at_z_omega,
            domain_element_for_quotiening,
            z_omega,
            deep_challenges[
                challenge_offset : challenge_offset + len(sources_zw)
            ],
        )
        challenge_offset += len(sources_zw)

        if lp.is_lookup:
            sources_0 = cast_ext(
                q.stage_2.leaf_elements[
                    lookup_witness_encoding_polys_offset:
                ]
            )
            simulated = _quotening(
                simulated,
                sources_0,
                proof.values_at_0,
                domain_element_for_quotiening,
                ZERO,
                deep_challenges[
                    challenge_offset : challenge_offset + len(sources_0)
                ],
            )
            challenge_offset += len(sources_0)

        for open_at, subset in public_input_opening_tuples:
            srcs = []
            vals = []
            for column, expected in subset:
                srcs.append(
                    (int(q.witness.leaf_elements[column]) % gl.P, 0)
                )
                vals.append((int(expected) % gl.P, 0))
            simulated = _quotening(
                simulated,
                srcs,
                vals,
                domain_element_for_quotiening,
                (open_at, 0),
                deep_challenges[
                    challenge_offset : challenge_offset + len(srcs)
                ],
            )
            challenge_offset += len(srcs)
        assert challenge_offset == len(deep_challenges)

        current_folded_value = simulated
        subidx = base_tree_idx
        coset_inverse = base_coset_inverse
        if len(schedule) != len(q.fri):
            return False
        expected_fri_query_len = base_oracle_depth
        for idx, (deg_log2, fri_query) in enumerate(zip(schedule, q.fri)):
            expected_fri_query_len -= deg_log2
            interpolation_degree = 1 << deg_log2
            subidx_in_leaf = subidx % interpolation_degree
            tree_idx = subidx >> deg_log2
            if (
                current_folded_value[0]
                != int(fri_query.leaf_elements[subidx_in_leaf]) % gl.P
                or current_folded_value[1]
                != int(
                    fri_query.leaf_elements[
                        interpolation_degree + subidx_in_leaf
                    ]
                )
                % gl.P
            ):
                return False
            cap = (
                proof.fri_base_oracle_cap
                if idx == 0
                else proof.fri_intermediate_oracles_caps[idx - 1]
            )
            if len(fri_query.leaf_elements) != interpolation_degree * 2:
                return False
            if len(fri_query.proof) != expected_fri_query_len:
                return False
            if not _verify_merkle_path(
                fri_query.leaf_elements, fri_query.proof, cap, tree_idx
            ):
                return False

            # leaf layout: interpolation_degree c0s then as many c1s
            elements = [
                (
                    int(fri_query.leaf_elements[i]) % gl.P,
                    int(fri_query.leaf_elements[interpolation_degree + i])
                    % gl.P,
                )
                for i in range(interpolation_degree)
            ]
            challenges = fri_intermediate_challenges[idx]
            assert len(challenges) == deg_log2
            base_pow = power_chunks[idx]
            for ch in challenges:
                nxt = []
                for i in range(len(elements) // 2):
                    a = elements[2 * i]
                    b = elements[2 * i + 1]
                    result = e_add(a, b)
                    diff = e_mul(e_sub(a, b), ch)
                    powv = gl.mul(
                        gl.mul(base_pow, interpolation_steps[i]),
                        coset_inverse,
                    )
                    diff = e_mul_base(diff, powv)
                    nxt.append(e_add(result, diff))
                elements = nxt
                base_pow = gl.mul(base_pow, base_pow)
                coset_inverse = gl.mul(coset_inverse, coset_inverse)
            for _ in range(deg_log2):
                domain_element_for_interpolation = gl.mul(
                    domain_element_for_interpolation,
                    domain_element_for_interpolation,
                )
            subidx = tree_idx
            current_folded_value = elements[0]

        # evaluate final monomials by Horner at the interpolation point
        result_from_monomial = ZERO
        for mc0, mc1 in zip(
            reversed(proof.final_fri_monomials[0]),
            reversed(proof.final_fri_monomials[1]),
        ):
            result_from_monomial = e_mul_base(
                result_from_monomial, domain_element_for_interpolation
            )
            result_from_monomial = e_add(
                result_from_monomial, (int(mc0) % gl.P, int(mc1) % gl.P)
            )
        if result_from_monomial != current_folded_value:
            return False

    return True
