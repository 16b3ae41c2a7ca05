"""Setup / verification-key generation.

Counterpart of `/root/reference/src/cs/implementations/setup.rs`
(`create_permutation_polys` :401, `compute_selectors_and_constants_placement`
:486, `create_constant_setup_polys` :710, `get_full_setup` :1255).

TPU-first differences:
- sigma construction is a single vectorized numpy pass (stable argsort over
  the flattened placement + per-group rotation), not a per-cell cycle walk;
- selector encoding uses a balanced binary tree over the used gate set
  (variable-depth optimization as in the reference's TreeNode comes later);
  the path bits land in the leading constant columns, gate constants follow;
- all setup polynomials are low-degree-extended and Merkle-committed on
  device in one batch.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from ..field import gl
from ..merkle import MerkleTreeWithCap
from ..ntt import lde_from_monomial, monomial_from_values
from .config import require_poseidon2_tree


def build_selector_tree(gates):
    """Degree-aware selector placement (reference setup.rs:486 TreeNode
    optimizer): high-degree / constant-hungry gates get short selector
    paths. Returns (tree, per-gate paths as 0/1 lists)."""
    from ..cs.selector_tree import GateDescription, compute_selector_placement

    descriptions = [
        GateDescription(
            gate_idx=i,
            num_constants=g.num_constants,
            degree=g.max_degree,
            needs_selector=True,
            is_lookup=getattr(g, "is_lookup_marker", False),
        )
        for i, g in enumerate(gates)
    ]
    tree = compute_selector_placement(descriptions)
    paths = []
    for i in range(len(gates)):
        p = tree.output_placement(i)
        assert p is not None, f"gate {i} missing from selector tree"
        paths.append([int(b) for b in p])
    return tree, paths


def non_residues_for_copy_permutation(num_cols: int) -> list[int]:
    """Distinct coset representatives k_col = g^col (g the multiplicative
    generator); k_0 = 1 (reference utils.rs non-residues)."""
    out = [1]
    for _ in range(1, num_cols):
        out.append(gl.mul(out[-1], gl.MULTIPLICATIVE_GENERATOR))
    return out


def non_residues_for_copy_permutation_bb(num_cols: int) -> list[int]:
    """The BabyBear k_col = 31^col family (31 generates the full
    multiplicative group, so the cosets are distinct up to huge widths)."""
    from ..field import babybear as bb

    out = [1]
    for _ in range(1, num_cols):
        out.append(bb.mul_s(out[-1], 31))
    return out


def _sigma_cells(copy_placement: np.ndarray, trace_len: int) -> np.ndarray:
    """Field-independent half of the permutation-poly construction: the
    flat cell -> next-cell-in-cycle map (vacant cells fixed points)."""
    C, n = copy_placement.shape
    assert n == trace_len
    pl = copy_placement.reshape(-1)
    N = C * n
    order = np.argsort(pl, kind="stable")
    sorted_pl = pl[order]
    pos = np.arange(N)
    same_next = np.zeros(N, dtype=bool)
    same_next[:-1] = sorted_pl[1:] == sorted_pl[:-1]
    # group starts
    first = np.ones(N, dtype=bool)
    first[1:] = sorted_pl[1:] != sorted_pl[:-1]
    group_id = np.cumsum(first) - 1
    start_positions = np.nonzero(first)[0]
    starts_per_pos = start_positions[group_id]
    nxt = np.where(same_next, pos + 1, starts_per_pos)
    sigma_cell = np.empty(N, dtype=np.int64)
    sigma_cell[order] = order[nxt]
    # vacant cells: identity
    vacant = pl < 0
    sigma_cell[vacant] = np.nonzero(vacant)[0]
    return sigma_cell


def compute_sigma_values(
    copy_placement: np.ndarray, trace_len: int, non_residues=None
):
    """Vectorized permutation-polynomial construction.

    copy_placement: (C, n) int64 of place ids (-1 vacant). Cells holding the
    same variable form a cycle; sigma maps each cell to the next one in its
    cycle (vacant cells are fixed points). Returns (C, n) uint64 of
    sigma_col(w^row) = k_{col'} * w^{row'}.

    non_residues: per-column coset representatives k_col; defaults to this
    framework's g^col family (the reference-dialect prover passes the
    reference's small-QNR family instead).
    """
    C, n = copy_placement.shape
    sigma_cell = _sigma_cells(copy_placement, trace_len)
    # encode: cell -> k_col * w^row
    omega = gl.omega(n.bit_length() - 1)
    w_pows = np.zeros(n, dtype=np.uint64)
    cur = 1
    for i in range(n):
        w_pows[i] = cur
        cur = gl.mul(cur, omega)
    if non_residues is None:
        non_residues = non_residues_for_copy_permutation(C)
    ks = np.array([int(k) for k in non_residues], dtype=np.uint64)
    tgt_col = (sigma_cell // n).astype(np.int64)
    tgt_row = (sigma_cell % n).astype(np.int64)
    vals = _np_mod_mul(ks[tgt_col], w_pows[tgt_row])
    return vals.reshape(C, n)


def compute_sigma_values_bb(
    copy_placement: np.ndarray, trace_len: int, non_residues=None
):
    """BabyBear twin of compute_sigma_values: same vectorized cycle walk,
    encode over p = 2^31 - 2^27 + 1 with the 31^col non-residue family.
    Returns (C, n) uint32."""
    from ..field import babybear as bb

    C, n = copy_placement.shape
    sigma_cell = _sigma_cells(copy_placement, trace_len)
    w_pows = bb.powers_np(bb.omega(n.bit_length() - 1), n)
    if non_residues is None:
        non_residues = non_residues_for_copy_permutation_bb(C)
    ks = np.array([int(k) for k in non_residues], dtype=np.uint32)
    tgt_col = (sigma_cell // n).astype(np.int64)
    tgt_row = (sigma_cell % n).astype(np.int64)
    vals = bb.mul_np(ks[tgt_col], w_pows[tgt_row])
    return vals.reshape(C, n)


def _np_mod_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized Goldilocks multiply on host uint64 arrays (the same
    EPSILON-reduction as field/goldilocks.py, in numpy — the python-object
    bigint path this replaces cost ~20 minutes for a 92x2^20 sigma)."""
    M32 = np.uint64(0xFFFFFFFF)
    a_lo = a & M32
    a_hi = a >> np.uint64(32)
    b_lo = b & M32
    b_hi = b >> np.uint64(32)
    with np.errstate(over="ignore"):
        ll = a_lo * b_lo
        lh = a_lo * b_hi
        hl = a_hi * b_lo
        hh = a_hi * b_hi
        mid = lh + hl
        mid_c = (mid < lh).astype(np.uint64)
        lo = ll + (mid << np.uint64(32))
        lo_c = (lo < ll).astype(np.uint64)
        hi = hh + (mid >> np.uint64(32)) + (mid_c << np.uint64(32)) + lo_c
        # reduce128: x = lo - hi_hi + hi_lo * EPSILON
        hi_hi = hi >> np.uint64(32)
        hi_lo = hi & M32
        t0 = lo - hi_hi
        t0 = np.where(lo < hi_hi, t0 - M32, t0)
        t1 = hi_lo * M32
        t2 = t0 + t1
        res = np.where(t2 < t0, t2 + M32, t2)
        return np.where(res >= np.uint64(gl.P), res - np.uint64(gl.P), res)


def build_constant_columns(assembly, selector_paths) -> np.ndarray:
    """(K, n) uint64 constant columns with variable-depth selector layout:
    on a row holding gate g, columns [0, len(path_g)) carry g's selector
    path bits and g's own constants start at column len(path_g) (reference
    create_constant_setup_polys, setup.rs:710)."""
    n = assembly.trace_len
    K = assembly.geometry.num_constant_columns
    for gid, g in enumerate(assembly.gates):
        used = len(selector_paths[gid]) + g.num_constants
        assert used <= K, (
            f"gate {g.name}: selector depth {len(selector_paths[gid])} + "
            f"constants {g.num_constants} exceed {K} constant columns"
        )
    cols = np.zeros((K, n), dtype=np.uint64)
    rg = assembly.row_gate
    max_depth = max((len(p) for p in selector_paths), default=0)
    if max_depth:
        # bits[g, d] = path bit (rows of shallower gates keep zeros beyond
        # their own path, which their selector product never reads)
        bits = np.zeros((len(selector_paths), max_depth), dtype=np.uint64)
        for gid, p in enumerate(selector_paths):
            bits[gid, : len(p)] = p
        cols[:max_depth, :] = bits[rg].T
    offsets = np.array(
        [len(p) for p in selector_paths], dtype=np.int64
    )
    for row, consts in assembly.gate_constants.items():
        off = int(offsets[rg[row]])
        for i, c in enumerate(consts):
            cols[off + i, row] = c
    return cols


@dataclass
class VerificationKey:
    """Fixed parameters + setup commitment (reference verifier.rs:31)."""

    geometry: object
    trace_len: int
    fri_lde_factor: int
    cap_size: int
    num_queries: int
    pow_bits: int
    fri_final_degree: int
    gate_names: list
    selector_paths: list
    public_input_locations: list  # [(col, row)]
    setup_merkle_cap: list
    num_copy_cols: int
    num_wit_cols: int
    lookup_params: object = None
    num_lookup_tables: int = 0
    fri_folding_schedule: list | None = None
    # quotient chunk count / sweep rate; None (legacy keys) = fri_lde_factor
    quotient_degree: int | None = None
    # Fiat-Shamir transcript kind the proof/verifier must replay
    transcript: str = "poseidon2"
    # Merkle tree hasher of the setup cap and of every oracle of a proof
    tree_hasher: str = "poseidon2"

    def effective_quotient_degree(self) -> int:
        return self.quotient_degree or self.fri_lde_factor

    def to_dict(self):
        from dataclasses import asdict

        d = {
            "trace_len": self.trace_len,
            "fri_lde_factor": self.fri_lde_factor,
            "quotient_degree": self.quotient_degree,
            "transcript": self.transcript,
            "tree_hasher": self.tree_hasher,
            "cap_size": self.cap_size,
            "num_queries": self.num_queries,
            "pow_bits": self.pow_bits,
            "fri_final_degree": self.fri_final_degree,
            "gate_names": list(self.gate_names),
            "selector_paths": [list(p) for p in self.selector_paths],
            "public_input_locations": list(self.public_input_locations),
            "setup_merkle_cap": [list(c) for c in self.setup_merkle_cap],
            "num_copy_cols": self.num_copy_cols,
            "num_wit_cols": self.num_wit_cols,
            "num_lookup_tables": self.num_lookup_tables,
            "fri_folding_schedule": (
                None
                if self.fri_folding_schedule is None
                else list(self.fri_folding_schedule)
            ),
            "lookup_params": None
            if self.lookup_params is None
            else {
                "width": self.lookup_params.width,
                "num_repetitions": self.lookup_params.num_repetitions,
                "share_table_id": self.lookup_params.share_table_id,
                "use_specialized_columns": self.lookup_params.use_specialized_columns,
            },
            "geometry": {
                "num_columns_under_copy_permutation": self.geometry.num_columns_under_copy_permutation,
                "num_witness_columns": self.geometry.num_witness_columns,
                "num_constant_columns": self.geometry.num_constant_columns,
                "max_allowed_constraint_degree": self.geometry.max_allowed_constraint_degree,
            },
        }
        return d


@dataclass
class SetupData:
    """Everything the prover needs beyond the assembly's witness."""

    vk: VerificationKey
    sigma_cols: np.ndarray  # (C, n) host
    constant_cols: np.ndarray  # (K, n) host
    setup_monomials: object  # (C+K, n) device
    setup_lde: object  # (C+K, lde, n) device, or None in streamed mode
    setup_tree: MerkleTreeWithCap
    selector_paths: list
    non_residues: list


def generate_setup(assembly, config) -> SetupData:
    """Full setup: sigmas + constants -> monomial -> LDE -> Merkle -> VK.

    Setup column order: [sigma (C_total) | constants (K, + table-id col when
    lookups are on) | stacked table columns (width+1, lookups only)].
    """
    n = assembly.trace_len
    assert config.fri_final_degree < n, (
        "fri_final_degree must be below the trace length (at least one fold)"
    )
    tree, selector_paths = build_selector_tree(assembly.gates)
    # masked-constraint degree must fit the QUOTIENT evaluation domain
    # (quotient_degree cosets) — decoupled from the commitment rate
    # fri_lde_factor, reference prover.rs:230-259 quotient_degree_from_
    # gate_terms vs proof_config.fri_lde_factor. The degree-aware tree
    # keeps high-degree gates shallow so the bound is tight.
    tree_degree, tree_constants = tree.compute_stats()
    degree_bound = max(
        tree_degree,
        assembly.geometry.max_allowed_constraint_degree + 1,
        1,
    )
    derived_q = 1 << (degree_bound - 1).bit_length()  # next power of two
    quotient_degree = config.quotient_degree or derived_q
    assert tree_degree <= quotient_degree, (
        f"selector tree degree {tree_degree} exceeds quotient_degree "
        f"{quotient_degree}"
    )
    assert tree_constants <= assembly.geometry.num_constant_columns, (
        f"selector tree needs {tree_constants} constant columns, geometry "
        f"has {assembly.geometry.num_constant_columns}"
    )
    assert (
        assembly.geometry.max_allowed_constraint_degree + 1
        <= quotient_degree
    ), "copy-permutation chunk degree exceeds quotient_degree"
    full_placement = np.concatenate(
        [assembly.copy_placement, assembly.lookup_placement], axis=0
    )
    hasher_name = getattr(config, "tree_hasher", "poseidon2")
    if getattr(assembly, "field", "goldilocks") == "babybear":
        require_poseidon2_tree(hasher_name, "in the BabyBear prover")
        return _generate_setup_babybear(
            assembly, config, full_placement, selector_paths,
            quotient_degree,
        )
    sigma = compute_sigma_values(full_placement, n)
    consts = build_constant_columns(assembly, selector_paths)
    if assembly.lookups_enabled:
        if assembly.lookup_table_id_col is not None:
            # specialized mode: dedicated table-id constant column
            consts = np.concatenate(
                [consts, assembly.lookup_table_id_col[None, :]], axis=0
            )
        table_cols = assembly.stacked_table_columns(assembly.lookup_params.width)
        setup_cols = np.concatenate([sigma, consts, table_cols], axis=0)
    else:
        table_cols = np.zeros((0, n), dtype=np.uint64)
        setup_cols = np.concatenate([sigma, consts], axis=0)
    dev = jnp.asarray(setup_cols)
    monomials = monomial_from_values(dev)
    del dev
    from .streaming import commit_streaming, use_streamed_lde

    if use_streamed_lde(setup_cols.shape[0], n * config.fri_lde_factor):
        require_poseidon2_tree(hasher_name, "on a streamed commit")
        # beyond the footprint threshold the setup LDE is never
        # materialized: the tree commits from streamed column blocks and
        # the prover regenerates blocks from the monomials (streaming.py)
        lde = None
        tree = commit_streaming(
            monomials, config.fri_lde_factor, config.merkle_tree_cap_size
        )
    else:
        lde = lde_from_monomial(monomials, config.fri_lde_factor)
        # same shape-keyed leaf-sponge + node-stack dispatches as the
        # prover's commit pipeline, so the setup commit shares executables
        # (and the precompile warm) with the proof oracles
        from ..merkle import tree_hasher

        tree = MerkleTreeWithCap.from_layers(
            list(
                tree_hasher(hasher_name).commit_layers_device(
                    lde, config.merkle_tree_cap_size
                )
            ),
            config.merkle_tree_cap_size,
        )
    vk = VerificationKey(
        geometry=assembly.geometry,
        trace_len=n,
        fri_lde_factor=config.fri_lde_factor,
        quotient_degree=quotient_degree,
        transcript=getattr(config, "transcript", "poseidon2"),
        tree_hasher=hasher_name,
        cap_size=config.merkle_tree_cap_size,
        num_queries=config.num_queries,
        pow_bits=config.pow_bits,
        fri_final_degree=config.fri_final_degree,
        gate_names=[g.name for g in assembly.gates],
        selector_paths=selector_paths,
        public_input_locations=[(c, r) for (c, r, _v) in assembly.public_inputs],
        setup_merkle_cap=tree.get_cap(),
        num_copy_cols=sigma.shape[0],
        num_wit_cols=assembly.wit_placement.shape[0],
        lookup_params=assembly.lookup_params if assembly.lookups_enabled else None,
        num_lookup_tables=len(assembly.lookup_tables),
        fri_folding_schedule=getattr(config, "fri_folding_schedule", None),
    )
    return SetupData(
        vk=vk,
        sigma_cols=sigma,
        constant_cols=consts,
        setup_monomials=monomials,
        setup_lde=lde,
        setup_tree=tree,
        selector_paths=selector_paths,
        non_residues=non_residues_for_copy_permutation(sigma.shape[0]),
    )


_BB_TRANSCRIPTS = {
    "poseidon2": "poseidon2_babybear",
    "blake2s": "blake2s_babybear",
}


def _generate_setup_babybear(
    assembly, config, full_placement, selector_paths, quotient_degree
):
    """The BabyBear setup leg (ISSUE 20): u32 sigma/constant/table columns,
    HOST numpy monomials + coset-31 LDE, and a paired-leaf Poseidon2-BB
    Merkle commit — the same oracle layout the full prover's witness
    commits use, shared verbatim by the device and numpy prover backends
    (setup-cap parity is by construction)."""
    from ..field import babybear as bb
    from ..hashes import poseidon2_bb as p2bb
    from ..ntt import bb_ntt
    from .bb_kernels import BBMerkleTree

    n = assembly.trace_len
    L = config.fri_lde_factor
    half = (n * L) // 2
    non_residues = non_residues_for_copy_permutation_bb(
        full_placement.shape[0]
    )
    sigma = compute_sigma_values_bb(full_placement, n, non_residues)
    consts = build_constant_columns(assembly, selector_paths).astype(
        np.uint32
    )
    if assembly.lookups_enabled:
        assert assembly.lookup_table_id_col is not None, (
            "babybear backend supports specialized lookup columns only"
        )
        consts = np.concatenate(
            [consts, assembly.lookup_table_id_col[None, :].astype(np.uint32)],
            axis=0,
        )
        table_cols = assembly.stacked_table_columns(
            assembly.lookup_params.width
        ).astype(np.uint32)
        setup_cols = np.concatenate([sigma, consts, table_cols], axis=0)
    else:
        setup_cols = np.concatenate([sigma, consts], axis=0)
    monomials = bb_ntt.ntt_np(setup_cols, inverse=True)
    lde = bb_ntt.lde_np(monomials, L, 31)
    paired = np.concatenate([lde[:, :half], lde[:, half:]], axis=0)
    digests = p2bb.leaf_hash_bb_np(paired.T)
    layers = [digests]
    while layers[-1].shape[0] > config.merkle_tree_cap_size:
        cur = layers[-1]
        layers.append(p2bb.node_hash_bb_np(cur[0::2], cur[1::2]))
    tree = BBMerkleTree(layers, config.merkle_tree_cap_size)
    transcript = getattr(config, "transcript", "poseidon2")
    transcript = _BB_TRANSCRIPTS.get(transcript, transcript)
    assert transcript.endswith("babybear"), (
        f"transcript {transcript} has no babybear instantiation"
    )
    vk = VerificationKey(
        geometry=assembly.geometry,
        trace_len=n,
        fri_lde_factor=L,
        quotient_degree=quotient_degree,
        transcript=transcript,
        cap_size=config.merkle_tree_cap_size,
        num_queries=config.num_queries,
        pow_bits=config.pow_bits,
        fri_final_degree=config.fri_final_degree,
        gate_names=[g.name for g in assembly.gates],
        selector_paths=selector_paths,
        public_input_locations=[
            (c, r) for (c, r, _v) in assembly.public_inputs
        ],
        setup_merkle_cap=tree.get_cap(),
        num_copy_cols=sigma.shape[0],
        num_wit_cols=assembly.wit_placement.shape[0],
        lookup_params=(
            assembly.lookup_params if assembly.lookups_enabled else None
        ),
        num_lookup_tables=len(assembly.lookup_tables),
        fri_folding_schedule=getattr(config, "fri_folding_schedule", None),
    )
    return SetupData(
        vk=vk,
        sigma_cols=sigma,
        constant_cols=consts,
        setup_monomials=monomials,
        setup_lde=lde,
        setup_tree=tree,
        selector_paths=selector_paths,
        non_residues=non_residues,
    )
