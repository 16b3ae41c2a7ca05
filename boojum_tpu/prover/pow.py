"""Proof-of-work grinding (reference `PoWRunner`, pow.rs:7,51,140).

Algebraic Poseidon2 PoW (recursion-friendly: the recursive verifier replays
it with one flattened-gate sponge call): seed = 4 transcript challenges; find
a u64 nonce such that hash(seed ‖ nonce)[0] has `pow_bits` low zero bits. The
nonce is absorbed back into the transcript before query-index sampling so
queries are grinding-bound.

Byte-oriented Blake2s / Keccak256 runners mirror the reference's alternative
backends: seed = 4 challenges as LE bytes, digest's first LE u64 must have
`pow_bits` low zero bits.
"""

from ..hashes.poseidon2 import Poseidon2SpongeHost
from ..utils.spans import span as _span


def pow_grind(transcript, pow_bits: int) -> int:
    if pow_bits == 0:
        return 0
    assert pow_bits <= 32, "unreasonable pow difficulty"
    # the seed's draw, the grinding and the nonce's absorb are the host's
    # own time, like every other block of transcript work in a prove
    with _span("host.transcript", pow_bits=pow_bits):
        seed = transcript.get_multiple_challenges(4)
        mask = (1 << pow_bits) - 1
        nonce = 0
        while True:
            h = Poseidon2SpongeHost.hash_leaf(seed + [nonce])
            if h[0] & mask == 0:
                break
            nonce += 1
        transcript.witness_field_elements([nonce])
    return nonce


def pow_verify(transcript, pow_bits: int, nonce: int) -> bool:
    if pow_bits == 0:
        return True
    seed = transcript.get_multiple_challenges(4)
    h = Poseidon2SpongeHost.hash_leaf(seed + [int(nonce)])
    if h[0] & ((1 << pow_bits) - 1) != 0:
        return False
    transcript.witness_field_elements([nonce])
    return True


def _byte_pow_grind(transcript, pow_bits: int, hasher) -> int:
    if pow_bits == 0:
        return 0
    assert pow_bits <= 32, "unreasonable pow difficulty"
    seed = b"".join(
        c.to_bytes(8, "little")
        for c in transcript.get_multiple_challenges(4)
    )
    mask = (1 << pow_bits) - 1
    nonce = 0
    while True:
        h = hasher(seed + nonce.to_bytes(8, "little"))
        if int.from_bytes(h[:8], "little") & mask == 0:
            break
        nonce += 1
    transcript.witness_field_elements([nonce])
    return nonce


def _byte_pow_verify(transcript, pow_bits: int, nonce: int, hasher) -> bool:
    if pow_bits == 0:
        return True
    seed = b"".join(
        c.to_bytes(8, "little")
        for c in transcript.get_multiple_challenges(4)
    )
    mask = (1 << pow_bits) - 1
    h = hasher(seed + int(nonce).to_bytes(8, "little"))
    if int.from_bytes(h[:8], "little") & mask != 0:
        return False
    transcript.witness_field_elements([nonce])
    return True


def blake2s_pow_grind(transcript, pow_bits: int) -> int:
    """Blake2s nonce search (reference pow.rs:51)."""
    import hashlib

    return _byte_pow_grind(
        transcript, pow_bits, lambda d: hashlib.blake2s(d).digest()
    )


def blake2s_pow_verify(transcript, pow_bits: int, nonce: int) -> bool:
    import hashlib

    return _byte_pow_verify(
        transcript, pow_bits, nonce, lambda d: hashlib.blake2s(d).digest()
    )


def keccak256_pow_grind(transcript, pow_bits: int) -> int:
    """Keccak-256 nonce search (reference pow.rs:140)."""
    from ..hashes.keccak_host import keccak256

    return _byte_pow_grind(transcript, pow_bits, keccak256)


def keccak256_pow_verify(transcript, pow_bits: int, nonce: int) -> bool:
    from ..hashes.keccak_host import keccak256

    return _byte_pow_verify(transcript, pow_bits, nonce, keccak256)
