"""The prover's transcript on the native permutation (ISSUE 38).

Two engines, one permutation: `hashes/poseidon2.py::
poseidon2_permutation_host` (Python ints, the reference, what `verify()`
and `Poseidon2SpongeHost` run) and `native/resolver.cpp`'s, which
`transcript.make_prover_transcript` takes where the library loaded. Held
equal here on single states, on whole absorbs, on a scripted transcript
and on the bytes of a 2^10 proof; the Python one is also held to a digest
recorded from the permutation as it was before it lost its per-operation
calls. The native cases skip where no compiler built the library.
"""

import ctypes
import hashlib
import random

import pytest

from boojum_tpu import native
from boojum_tpu import transcript as T
from boojum_tpu.field import gl
from boojum_tpu.hashes.poseidon2 import (
    Poseidon2SpongeHost,
    poseidon2_permutation_host,
)
from boojum_tpu.prover import verify
from boojum_tpu.utils import metrics
from proving import baseline, prove_recorded, small_parts

P = gl.P
LOW32 = 0xFFFFFFFF
HIGH32 = 0xFFFFFFFF00000000


@pytest.fixture
def lib():
    found = native.get_lib()
    if found is None:
        pytest.skip("no native library (no compiler, or BOOJUM_TPU_NO_NATIVE)")
    return found


@pytest.fixture
def no_native(monkeypatch):
    """The process as it is where the switch was set before the first use."""
    monkeypatch.setenv("BOOJUM_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.get_lib() is None


def _random_state(seed):
    rnd = random.Random(seed)
    return [rnd.randrange(P) for _ in range(12)]


STATES = [
    pytest.param([0] * 12, id="zero"),
    pytest.param([P - 1] * 12, id="p-1"),
    pytest.param([LOW32] * 12, id="low32"),
    pytest.param([HIGH32] * 12, id="high32"),
] + [pytest.param(_random_state(s), id=f"seed{s}") for s in range(64)]


def _native_permutation(lib, state):
    words = (ctypes.c_uint64 * 12)(*state)
    assert lib.poseidon2_permute(words) == 0
    return list(words)


@pytest.mark.parametrize("state", STATES)
def test_native_permutation_is_the_python_permutation(lib, state):
    py, nat = list(state), list(state)
    for _ in range(3):  # chained: each output is the next input
        py = poseidon2_permutation_host(py)
        nat = _native_permutation(lib, nat)
        assert nat == py
        assert all(type(v) is int and 0 <= v < P for v in py)


def test_python_permutation_is_what_it_was():
    """sha256 over the chained outputs of every state above, recorded from
    the permutation that made a `gl.*` call an operation (PR 37's tree)."""
    h = hashlib.sha256()
    for case in STATES:
        s = list(case.values[0])
        for _ in range(3):
            s = poseidon2_permutation_host(s)
            h.update(b"".join(v.to_bytes(8, "little") for v in s))
    assert h.hexdigest() == (
        "77efbefcd76e6d0f6038cd2f459a6384e1af2f1146d176e2932f1b37777a0a4a"
    )


def test_python_permutation_reduces_what_it_is_given():
    """The contract has no `canonical in`: the sponge's callers hand it
    whatever ints they hold."""
    s = _random_state(99)
    shifted = [v + P * (i % 3) for i, v in enumerate(s)]
    assert poseidon2_permutation_host(shifted) == poseidon2_permutation_host(s)


@pytest.mark.parametrize("blocks", [1, 2, 9, 68])
def test_native_absorb_is_block_by_block_python(lib, blocks):
    rnd = random.Random(blocks)
    values = [rnd.randrange(P) for _ in range(8 * blocks)]
    start = _random_state(1000 + blocks)
    sponge = Poseidon2SpongeHost()
    sponge.state = list(start)
    sponge.absorb(values)
    t = T.NativePoseidon2Transcript(lib)
    t.state = list(start)
    reg = metrics.start_metrics()
    try:
        t._absorb(values)
    finally:
        metrics.stop_metrics()
    assert t.state == sponge.state
    assert reg.counters == {
        "transcript.permutations": blocks,
        "transcript.native_permutations": blocks,
    }


def _scripted(t):
    """Caps, field elements (some unreduced), single and extension
    challenges past the rate, a query index draw: what a prove sends."""
    rnd = random.Random(38)
    out = []
    t.witness_merkle_tree_cap(
        [[rnd.randrange(P) for _ in range(4)] for _ in range(16)]
    )
    t.witness_field_elements([rnd.randrange(2 * P) for _ in range(3)])
    out.append(t.get_ext_challenge())
    out.append(t.get_ext_challenge())
    t.witness_field_elements([rnd.randrange(P) for _ in range(543)])
    out.append(t.get_multiple_challenges(11))
    out.append(t.get_challenge())
    t.witness_merkle_tree_cap([[rnd.randrange(P) for _ in range(4)]])
    bits = T.BitSource(20)
    out.append([bits.get_index(t, 17) for _ in range(50)])
    return out


def _scripted_digest(t):
    return hashlib.sha256(repr(_scripted(t)).encode()).hexdigest()


def test_scripted_transcript_python_engine():
    assert type(T.make_transcript("poseidon2")) is T.Poseidon2Transcript
    # recorded from PR 37's tree (the permutation through gl.* calls)
    assert _scripted_digest(T.make_transcript("poseidon2")) == (
        "8ca4d874018d3d4ce96e64a74c56b2ab339819f4430b9d92c7cd4afdb050dc6e"
    )


def test_scripted_transcript_native_engine(lib):
    t = T.make_prover_transcript("poseidon2")
    assert type(t) is T.NativePoseidon2Transcript
    assert _scripted(t) == _scripted(T.make_transcript("poseidon2"))


def test_scripted_transcript_fallback(no_native):
    t = T.make_prover_transcript("poseidon2")
    assert type(t) is T.Poseidon2Transcript
    assert _scripted(t) == _scripted(T.make_transcript("poseidon2"))


@pytest.mark.parametrize("kind", sorted(set(T.TRANSCRIPTS) - {"poseidon2"}))
def test_every_other_transcript_keeps_its_engine(kind):
    assert type(T.make_prover_transcript(kind)) is T.TRANSCRIPTS[kind]


def test_the_verifier_and_the_host_sponge_permute_in_python():
    assert type(T.make_transcript("poseidon2")) is T.Poseidon2Transcript
    assert T.Poseidon2Transcript._PERMUTATION is poseidon2_permutation_host
    assert Poseidon2SpongeHost._PERMUTATION is poseidon2_permutation_host


# ---------------------------------------------------------------------------
# The shared 2^10 prove: the library loaded, and the switch set
# ---------------------------------------------------------------------------


def _counters(rep):
    return rep["metrics"]["counters"]


def _verifies(proof):
    asm, setup, _config = small_parts()
    return verify(setup.vk, proof, asm.gates)


def test_the_baseline_prove_drew_its_transcript_natively(lib):
    proof, rep = baseline()
    c = _counters(rep)
    assert (
        c["transcript.native_permutations"]
        == c["transcript.permutations"]
        > 0
    )
    assert _verifies(proof)


def test_the_fallback_prove_is_the_same_proof(no_native):
    proof, rep = prove_recorded("no_native")
    c = _counters(rep)
    assert c["transcript.native_permutations"] == 0
    base_proof, base_rep = baseline()
    assert c["transcript.permutations"] == (
        _counters(base_rep)["transcript.permutations"]
    )
    assert proof.to_json() == base_proof.to_json()
    assert _verifies(proof)
