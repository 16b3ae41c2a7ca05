"""The geometry/shape-bucket key — ONE definition of "same shape".

Three subsystems bucket work by circuit shape and must never disagree:

- `prover/precompile.py` enumerates the shape-keyed kernel library of a
  (assembly, config) pair — every derived batch width below picks which
  executables a prove dispatches;
- the service admission queue (`service/queue.py`) groups requests into
  shape buckets so same-shape jobs share warmed caches and compiled
  kernels (and the scheduler reads bucket occupancy);
- the compile ledger (`utils/profiling.CompileLedger`) tags per-kernel
  entries with the shape they belong to, so a compile-bill regression is
  attributable to the bucket that paid it.

`shape_bucket(assembly, config)` derives everything from circuit
STRUCTURE only (placements, gates, geometry, lookup params) — witness
values and sigma columns are never read, so it runs before
`generate_setup` and is safe at admission time. The derivation mirrors
`prover._prove_impl` / `setup.generate_setup` exactly; `precompile.
enumerate_kernels` consumes the same `ShapeBucket` instance, which is
what makes divergence impossible rather than merely unlikely.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ShapeBucket:
    """Everything shape-keyed about one (assembly, config) pair.

    The identity fields (everything that feeds `key`) determine every
    derived batch width; two requests with equal keys dispatch the same
    kernel library, share domain/twiddle caches, and can pack into one
    admission bucket."""

    # -- trace / protocol geometry ----------------------------------------
    trace_len: int
    lde_factor: int            # L: FRI commit rate
    cap_size: int              # Merkle tree cap
    quotient_degree: int       # Q: resolved (config override or derived)
    num_queries: int
    fri_final_degree: int
    # explicit per-oracle fold counts, () = the derived greedy schedule
    # (the dispatched fri_fold_k* kernel set depends on it)
    fri_schedule: tuple
    transcript: str
    tree_hasher: str
    # -- column geometry ---------------------------------------------------
    num_copy_cols: int         # Cg
    num_lookup_cols: int       # LC
    num_wit_cols: int          # W
    num_constant_cols: int     # K (incl. the specialized table-id column)
    num_public_inputs: int
    # -- lookup argument ---------------------------------------------------
    lookups: bool
    lookup_mode: str | None
    lookup_subargs: int        # R_args
    lookup_width: int
    # -- gate set fingerprint (the sweep/stack graphs are per-gate-set) ----
    gates_fp: str
    # -- derived batch widths (functions of the fields above; carried so
    #    consumers never re-derive them differently) ------------------------
    num_chunks: int = field(compare=False)
    chunks: tuple = field(compare=False)
    max_degree: int = field(compare=False)

    # ---- derived accessors (shared shorthand of precompile/prover) -------
    @property
    def log_n(self) -> int:
        return self.trace_len.bit_length() - 1

    @property
    def domain_len(self) -> int:
        """N = n * L, the full LDE domain."""
        return self.trace_len * self.lde_factor

    @property
    def Ct(self) -> int:
        return self.num_copy_cols + self.num_lookup_cols

    @property
    def M(self) -> int:
        return 1 if self.lookups else 0

    @property
    def TW(self) -> int:
        return (self.lookup_width + 1) if self.lookups else 0

    @property
    def S(self) -> int:
        """Stage-2 oracle width: z + partials + lookup A_i/B columns."""
        return 2 * self.num_chunks + 2 * self.lookup_subargs + 2 * self.M

    @property
    def B_wit(self) -> int:
        return self.Ct + self.num_wit_cols + self.M

    @property
    def B_setup(self) -> int:
        return self.Ct + self.num_constant_cols + self.TW

    @property
    def B_q(self) -> int:
        return 2 * self.quotient_degree

    @property
    def B_all(self) -> int:
        return self.B_wit + self.B_setup + self.S + self.B_q

    @property
    def key(self) -> str:
        """Canonical compact bucket key, e.g.
        ``n2^10:L2:cap4:q2:Q4:f16:tposeidon2:c8+0+0:k6:pi1:nolk:g1a2f3``.
        Built from identity fields only — equal keys mean equal kernel
        shapes, shared caches, and one admission bucket."""
        lk = (
            f"lk{self.lookup_mode},{self.lookup_subargs}x{self.lookup_width}"
            if self.lookups
            else "nolk"
        )
        sched = (
            "s" + ",".join(str(k) for k in self.fri_schedule)
            if self.fri_schedule
            else "sderived"
        )
        # non-default field backends (ISSUE 19) suffix the key — their
        # kernel shapes/dtypes are disjoint, so they must never share a
        # cache or admission bucket with the Goldilocks set. Goldilocks
        # keys stay BYTE-IDENTICAL to every key minted before the field
        # seam existed (cached bundles/ledgers keep matching).
        from ..field.spec import active_field

        fld = active_field()
        field_sfx = f":F{fld}" if fld != "goldilocks" else ""
        # likewise a non-default tree hasher: its commit programs are its own
        if self.tree_hasher != "poseidon2":
            field_sfx += f":H{self.tree_hasher}"
        return (
            f"n2^{self.log_n}:L{self.lde_factor}:cap{self.cap_size}"
            f":q{self.quotient_degree}:Q{self.num_queries}"
            f":f{self.fri_final_degree}:{sched}:t{self.transcript}"
            f":c{self.num_copy_cols}+{self.num_lookup_cols}"
            f"+{self.num_wit_cols}:k{self.num_constant_cols}"
            f":pi{self.num_public_inputs}:{lk}:g{self.gates_fp}"
            f"{field_sfx}"
        )

    @property
    def fingerprint(self) -> str:
        """Short stable digest of `key` for filesystem-safe naming
        (the AOT bundle store prefixes every bundle directory with it,
        so an operator can grep a bundle back to its shape bucket)."""
        return key_fingerprint(self.key)

    def __str__(self) -> str:
        return self.key


def key_fingerprint(key: str) -> str:
    """12-hex blake2s of a bucket key — the ONE fs-safe short form of
    "same shape" (prover/aot.py bundle dirs; anything else that needs a
    compact per-bucket name should use this, not its own hash)."""
    return hashlib.blake2s(key.encode(), digest_size=6).hexdigest()


def _gates_fingerprint(gates) -> str:
    """Short stable digest of the gate set IN PLACEMENT ORDER — the
    stage-2 stack and coset-sweep graphs are generated from the selector
    tree over exactly this sequence, so two circuits only share those
    executables when the sequence matches."""
    h = hashlib.blake2s(digest_size=6)
    for g in gates:
        h.update(type(g).__name__.encode())
        h.update(b"\x00")
    return h.hexdigest()


def derived_quotient_degree(assembly, config) -> int:
    """Q exactly as `setup.generate_setup` resolves it: the config
    override, else the next power of two covering the circuit's
    constraint-degree bound."""
    if config.quotient_degree is not None:
        return config.quotient_degree
    from .setup import build_selector_tree

    tree, _paths = build_selector_tree(assembly.gates)
    tree_degree, _consts = tree.compute_stats()
    degree_bound = max(
        tree_degree, assembly.geometry.max_allowed_constraint_degree + 1, 1
    )
    return 1 << (degree_bound - 1).bit_length()


def shape_bucket(assembly, config) -> ShapeBucket:
    """Derive the ShapeBucket of one (assembly, config) pair. Cached on
    the assembly (keyed by the config's field tuple): admission-time
    bucketing and a later precompile of the same pair must not re-pay the
    selector-tree walk."""
    from .stages import chunk_columns

    cfg_key = (
        config.fri_lde_factor, config.merkle_tree_cap_size,
        config.num_queries, config.pow_bits, config.fri_final_degree,
        tuple(config.fri_folding_schedule or ()), config.quotient_degree,
        config.transcript, getattr(config, "tree_hasher", "poseidon2"),
    )
    cache = getattr(assembly, "_shape_bucket_cache", None)
    if cache is None:
        cache = {}
        try:
            assembly._shape_bucket_cache = cache
        except Exception:
            cache = None
    if cache is not None and cfg_key in cache:
        return cache[cfg_key]

    geometry = assembly.geometry
    lookups = assembly.lookups_enabled
    lk_mode = assembly.lookup_mode if lookups else None
    lp = assembly.lookup_params
    Cg = assembly.copy_placement.shape[0]
    LC = assembly.num_lookup_cols
    chunks = chunk_columns(Cg + LC, geometry.max_allowed_constraint_degree)
    bucket = ShapeBucket(
        trace_len=int(assembly.trace_len),
        lde_factor=int(config.fri_lde_factor),
        cap_size=int(config.merkle_tree_cap_size),
        quotient_degree=derived_quotient_degree(assembly, config),
        num_queries=int(config.num_queries),
        fri_final_degree=int(config.fri_final_degree),
        fri_schedule=tuple(
            int(k) for k in (config.fri_folding_schedule or ())
        ),
        transcript=config.transcript,
        tree_hasher=getattr(config, "tree_hasher", "poseidon2"),
        num_copy_cols=int(Cg),
        num_lookup_cols=int(LC),
        num_wit_cols=int(assembly.wit_placement.shape[0]),
        num_constant_cols=int(
            geometry.num_constant_columns
            + (1 if (lookups and lk_mode == "specialized") else 0)
        ),
        num_public_inputs=len(assembly.public_inputs),
        lookups=bool(lookups),
        lookup_mode=lk_mode,
        lookup_subargs=int(assembly.num_lookup_subargs if lookups else 0),
        lookup_width=int(lp.width if lookups else 0),
        gates_fp=_gates_fingerprint(assembly.gates),
        num_chunks=len(chunks),
        chunks=tuple(tuple(c) for c in chunks),
        max_degree=int(geometry.max_allowed_constraint_degree),
    )
    if cache is not None:
        cache[cfg_key] = bucket
    return bucket


def bucket_key(assembly, config) -> str:
    """The canonical shape-bucket key string (the admission-queue and
    compile-ledger tag)."""
    return shape_bucket(assembly, config).key
