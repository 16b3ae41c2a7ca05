"""Proof configuration (reference ProofConfig, prover.rs:55)."""

from dataclasses import dataclass

# Merkle tree hashers a key can name (merkle.tree_hasher resolves them)
TREE_HASHERS = ("poseidon2", "blake2s")


class TreeHasherNotSupported(NotImplementedError):
    """A Blake2s tree asked of a path no deployment runs it on: a streamed
    commit, a mesh, the BabyBear prover, the in-circuit verifier."""


def require_poseidon2_tree(tree_hasher: str, where: str):
    """Raise by name where only Poseidon2 trees run (`where` ends the
    sentence: "under a mesh")."""
    if tree_hasher != "poseidon2":
        raise TreeHasherNotSupported(
            f"tree_hasher={tree_hasher!r} is not supported {where}: "
            "only Poseidon2 trees run there"
        )


@dataclass
class ProofConfig:
    fri_lde_factor: int = 8
    merkle_tree_cap_size: int = 16
    num_queries: int = 50
    pow_bits: int = 0
    fri_final_degree: int = 64  # stop folding when poly degree <= this
    # optional explicit FRI folding schedule: list of per-oracle fold counts
    # (2^k-to-1 per oracle, reference fri/mod.rs interpolation schedule);
    # None derives the reference-style greedy [3,3,...,rem] schedule
    fri_folding_schedule: list | None = None
    # quotient evaluation rate (number of size-n cosets the quotient sweep
    # runs over = number of degree-<n quotient chunks). None derives it from
    # the circuit's constraint degrees at setup time — DECOUPLED from
    # fri_lde_factor, as in the reference (prover.rs:259 quotient_degree vs
    # :313 used_lde_degree): oracles commit at fri_lde_factor while the
    # sweep streams per-coset at this rate, so e.g. the Era main-VM config
    # (LDE 2, degree-8 quotient) neither inflates proofs nor HBM.
    quotient_degree: int | None = None
    # Fiat-Shamir transcript kind: poseidon2 (default, recursion-compatible)
    # | poseidon (legacy round function) | blake2s | keccak256 (reference
    # transcript.rs:48,155,264), chosen apart from the tree hasher
    transcript: str = "poseidon2"
    # Merkle tree hasher of every oracle and of the setup: poseidon2
    # (default; what a circuit can verify) | blake2s (upstream's
    # non-recursive benches: leaf = Blake2s-256 of the elements' LE bytes,
    # node = Blake2s-256 of left || right). Kept in the key.
    tree_hasher: str = "poseidon2"

    def __post_init__(self):
        assert self.fri_lde_factor & (self.fri_lde_factor - 1) == 0
        assert self.merkle_tree_cap_size & (self.merkle_tree_cap_size - 1) == 0
        if self.fri_folding_schedule is not None:
            assert all(int(k) >= 1 for k in self.fri_folding_schedule)
        from ..transcript import TRANSCRIPTS

        assert self.transcript in TRANSCRIPTS, self.transcript
        assert self.tree_hasher in TREE_HASHERS, self.tree_hasher
        if self.quotient_degree is not None:
            assert self.quotient_degree >= 1
            assert self.quotient_degree & (self.quotient_degree - 1) == 0
