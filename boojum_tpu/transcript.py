"""Fiat–Shamir transcript over the Poseidon2 sponge (host-side).

Semantics follow the reference's algebraic sponge transcript
(`/root/reference/src/cs/implementations/transcript.rs:48`
AlgebraicSpongeBasedTranscript, overwrite absorption, rescue-prime padding
with a trailing 1) and its query-index bit buffer (`:369` BoolsBuffer). The
transcript is inherently sequential and tiny, so it runs on the host:
everything it absorbs (caps, evaluations) is read back from device once per
round, and the device has nothing queued while it runs. The PROVER's
Poseidon2 transcript therefore permutes in the native library where one
loaded (`make_prover_transcript`, PR 38); the verifier and every reference
permute in Python (`hashes/poseidon2.py::poseidon2_permutation_host`), so a
natively drawn transcript is replayed by code the prover did not run.

Field genericity (ISSUE 19): every p-specific constant — the reduction
modulus, the sponge width/rate, the absorb word width, the extension degree
one `get_ext_challenge` spans — reads from a `field.spec.FieldSpec` class
attribute. The Goldilocks defaults are BIT-IDENTICAL to the hardcoded
originals; `Poseidon2BabyBearTranscript` is the same machine instantiated
at the BabyBear record (width-16 permutation, 31-bit elements, degree-4
ext challenges).
"""

import ctypes

from .field.spec import BABYBEAR as _BB_SPEC
from .field.spec import GOLDILOCKS as _GL_SPEC
from .hashes.poseidon2 import poseidon2_permutation_host
from .utils import metrics as _metrics


class Poseidon2Transcript:
    """Algebraic sponge transcript over a width-12 permutation; subclasses
    swap the permutation (the reference is generic over the round function
    the same way, transcript.rs:48) and/or the FieldSpec."""

    _SPEC = _GL_SPEC
    _PERMUTATION = staticmethod(poseidon2_permutation_host)

    def __init__(self):
        self.state = [0] * self._SPEC.sponge_width
        self.buffer = []
        self.available = []

    def _permute(self):
        # the work behind the prover's `host.transcript` spans, counted
        _metrics.count("transcript.permutations")
        self.state = self._PERMUTATION(self.state)

    def _absorb(self, padded):
        """Overwrite-mode absorb of whole rate-blocks, a permutation each."""
        rate = self._SPEC.sponge_rate
        for i in range(0, len(padded), rate):
            self.state[:rate] = padded[i : i + rate]
            self._permute()

    def witness_field_elements(self, els):
        p = self._SPEC.p
        self.buffer.extend(int(e) % p for e in els)

    def witness_merkle_tree_cap(self, cap):
        for digest in cap:
            self.witness_field_elements(digest)

    def get_challenge(self) -> int:
        rate = self._SPEC.sponge_rate
        if not self.buffer:
            if self.available:
                return self.available.pop(0)
            self._permute()
            self.available = list(self.state[:rate])
            return self.available.pop(0)
        # rescue-prime padding: trailing 1, then zeros to a multiple of rate
        to_absorb = self.buffer + [1]
        self.buffer = []
        while len(to_absorb) % rate != 0:
            to_absorb.append(0)
        self._absorb(to_absorb)
        self.available = list(self.state[:rate])
        return self.available.pop(0)

    def get_multiple_challenges(self, n: int):
        return [self.get_challenge() for _ in range(n)]

    def get_ext_challenge(self):
        """One challenge per extension coordinate — a 2-tuple over
        Goldilocks, a 4-tuple over BabyBear (where 31-bit base draws are
        unsound and all protocol challenges live in GF(p^4))."""
        return tuple(
            self.get_challenge() for _ in range(self._SPEC.ext_degree)
        )


class NativePoseidon2Transcript(Poseidon2Transcript):
    """The same machine on the permutation of `native/resolver.cpp` (about
    0.01 ms each against 0.15 in Python), a buffer's whole absorb in one
    call. The state stays a list of canonical ints between calls;
    `transcript.native_permutations` says it engaged."""

    def __init__(self, lib):
        super().__init__()
        self._lib = lib

    def _steps(self, k, entry, *args):
        _metrics.count("transcript.permutations", k)
        _metrics.count("transcript.native_permutations", k)
        words = (ctypes.c_uint64 * 12)(*self.state)
        if entry(words, *args):
            raise RuntimeError("native Poseidon2 constants not registered")
        self.state = list(words)

    def _permute(self):
        self._steps(1, self._lib.poseidon2_permute)

    def _absorb(self, padded):
        k = len(padded) // self._SPEC.sponge_rate
        blocks = (ctypes.c_uint64 * len(padded))(*padded)
        self._steps(k, self._lib.poseidon2_absorb, blocks, k)


class _ByteTranscript:
    """Byte-oriented transcript base (reference Blake2sTranscript /
    Keccak256Transcript, transcript.rs:155,264): field elements are absorbed
    as `elem_bytes`-wide LE words (8 for Goldilocks); on each challenge
    request the pending buffer is folded into a running 32-byte seed, then
    challenges are squeezed as `hash(seed ‖ counter_le4)` blocks, each LE
    word reduced mod p."""

    _SPEC = _GL_SPEC

    def __init__(self):
        self.seed = b"\x00" * 32
        self.buffer = bytearray()
        self.counter = 0
        self.available = []

    def _hash(self, data: bytes) -> bytes:
        raise NotImplementedError

    def witness_field_elements(self, els):
        p = self._SPEC.p
        width = self._SPEC.elem_bytes
        for e in els:
            self.buffer += (int(e) % p).to_bytes(width, "little")

    def witness_merkle_tree_cap(self, cap):
        """A cap digest goes in as its bytes, each word little-endian and
        unreduced: a Blake2s digest's words are not below p (a Poseidon2
        digest's are, so for it this is what absorbing elements was)."""
        width = self._SPEC.elem_bytes
        for digest in cap:
            for w in digest:
                self.buffer += int(w).to_bytes(width, "little")

    def get_challenge(self) -> int:
        p = self._SPEC.p
        width = self._SPEC.elem_bytes
        if self.buffer:
            self.seed = self._hash(self.seed + bytes(self.buffer))
            self.buffer = bytearray()
            self.counter = 0
            self.available = []
        if not self.available:
            block = self._hash(
                self.seed + self.counter.to_bytes(4, "little")
            )
            self.counter += 1
            self.available = [
                int.from_bytes(block[i : i + width], "little") % p
                for i in range(0, 32, width)
            ]
        return self.available.pop(0)

    def get_multiple_challenges(self, n: int):
        return [self.get_challenge() for _ in range(n)]

    def get_ext_challenge(self):
        return tuple(
            self.get_challenge() for _ in range(self._SPEC.ext_degree)
        )


class Blake2sTranscript(_ByteTranscript):
    def _hash(self, data: bytes) -> bytes:
        import hashlib

        return hashlib.blake2s(data).digest()


class Keccak256Transcript(_ByteTranscript):
    def _hash(self, data: bytes) -> bytes:
        from .hashes.keccak_host import keccak256

        return keccak256(data)


from .hashes.poseidon import poseidon_permutation_host as _poseidon_perm


class PoseidonTranscript(Poseidon2Transcript):
    """Same sponge semantics over the LEGACY Poseidon permutation
    (reference GoldilocksPoisedonTranscript, transcript.rs:48 with the
    original round function)."""

    _PERMUTATION = staticmethod(_poseidon_perm)


def _bb_permutation_host(state):
    # lazy: hashes/poseidon2_bb drags in jax; the Goldilocks transcripts
    # must stay importable without paying for the BabyBear backend
    from .hashes.poseidon2_bb import poseidon2_permutation_bb_host

    return poseidon2_permutation_bb_host(state)


class Poseidon2BabyBearTranscript(Poseidon2Transcript):
    """The BabyBear instantiation: width-16 permutation over p = 2^31 -
    2^27 + 1, rate 8, degree-4 ext challenges (field/spec.py BABYBEAR)."""

    _SPEC = _BB_SPEC
    _PERMUTATION = staticmethod(_bb_permutation_host)


class Blake2sBabyBearTranscript(Blake2sTranscript):
    """Byte transcript at the BabyBear record: 4-byte LE absorb words,
    8 challenge words per squeezed 32-byte block."""

    _SPEC = _BB_SPEC


TRANSCRIPTS = {
    "poseidon2": Poseidon2Transcript,
    "poseidon": PoseidonTranscript,
    "blake2s": Blake2sTranscript,
    "keccak256": Keccak256Transcript,
    "poseidon2_babybear": Poseidon2BabyBearTranscript,
    "blake2s_babybear": Blake2sBabyBearTranscript,
}


def make_transcript(kind: str = "poseidon2"):
    return TRANSCRIPTS[kind]()


def make_prover_transcript(kind: str = "poseidon2"):
    """The transcript as the prover draws it: the native engine for
    "poseidon2" where the library loaded (no compiler, or
    BOOJUM_TPU_NO_NATIVE: the Python one), `make_transcript` for every
    other kind. The verifier never calls this."""
    if kind != "poseidon2":
        return make_transcript(kind)
    from .native import get_lib

    # the counter exists, at 0, where the run fell back
    _metrics.count("transcript.native_permutations", 0)
    lib = get_lib()
    if lib is None:
        return Poseidon2Transcript()
    return NativePoseidon2Transcript(lib)


class BitSource:
    """Uniform query-index bits drawn from transcript challenges.

    Takes only the low (challenge_bits - max_needed) bits of each
    challenge for uniformity, as the reference does (`transcript.rs:388`).
    `challenge_bits` is the field's challenge word width — 64 for
    Goldilocks (the historical hardcode), 31 for BabyBear
    (FieldSpec.challenge_bits).
    """

    def __init__(self, max_needed_bits: int, challenge_bits: int = 64):
        assert 0 < max_needed_bits < challenge_bits
        self.bits = []
        self.max_needed = max_needed_bits
        self.challenge_bits = challenge_bits

    def get_bits(self, transcript: Poseidon2Transcript, num_bits: int):
        while len(self.bits) < num_bits:
            c = transcript.get_challenge()
            usable = self.challenge_bits - self.max_needed
            self.bits.extend((c >> i) & 1 for i in range(usable))
        out, self.bits = self.bits[:num_bits], self.bits[num_bits:]
        return out

    def get_index(self, transcript: Poseidon2Transcript, num_bits: int) -> int:
        bits = self.get_bits(transcript, num_bits)
        idx = 0
        for i, b in enumerate(bits):
            idx |= b << i
        return idx
