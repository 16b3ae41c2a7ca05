"""Byte transcripts, PoW runners, serialization, convenience drivers
(reference test model: transcript.rs / pow.rs / fast_serialization.rs)."""

import os

from boojum_tpu.field import gl
from boojum_tpu.prover.pow import (
    blake2s_pow_grind,
    blake2s_pow_verify,
    keccak256_pow_grind,
    keccak256_pow_verify,
)
from boojum_tpu.serialization import (
    load_setup,
    save_setup,
    vk_from_json,
    vk_to_json,
)
from boojum_tpu.transcript import (
    Blake2sTranscript,
    Keccak256Transcript,
    make_transcript,
)


def test_byte_transcripts_deterministic_and_sensitive():
    for kind in ("blake2s", "keccak256"):
        t1 = make_transcript(kind)
        t2 = make_transcript(kind)
        t1.witness_field_elements([1, 2, 3])
        t2.witness_field_elements([1, 2, 3])
        c1 = t1.get_multiple_challenges(5)
        c2 = t2.get_multiple_challenges(5)
        assert c1 == c2
        assert all(0 <= c < gl.P for c in c1)
        t3 = make_transcript(kind)
        t3.witness_field_elements([1, 2, 4])
        assert t3.get_challenge() != c1[0]
        # absorbing after squeezing reseeds
        t1.witness_field_elements([9])
        more = t1.get_challenge()
        assert more != c1[0]


def test_byte_transcript_absorbs_a_cap_digest_as_its_32_bytes():
    """A Blake2s tree's digest words are any 64-bit values: the cap goes in
    unreduced, word by word little-endian (for a Poseidon2 digest, whose
    words are below p, that is what absorbing them as elements was)."""
    import hashlib

    big = (gl.P, gl.P + 5, (1 << 64) - 1, 7)
    small = (1, gl.P - 1, 3, 0)
    for kind in ("blake2s", "keccak256"):
        t = make_transcript(kind)
        t.witness_merkle_tree_cap([big, small])
        raw = b"".join(w.to_bytes(8, "little") for w in big + small)
        assert bytes(t.buffer) == raw
        as_elements = make_transcript(kind)
        as_elements.witness_field_elements(big + small)
        assert bytes(as_elements.buffer) != raw  # those are reduced mod p
        same = make_transcript(kind)
        same.witness_field_elements(small)
        cap_only = make_transcript(kind)
        cap_only.witness_merkle_tree_cap([small])
        assert bytes(same.buffer) == bytes(cap_only.buffer)
    t = Blake2sTranscript()
    t.witness_merkle_tree_cap([big])
    first = t.get_challenge()
    seed = hashlib.blake2s(
        b"\x00" * 32 + b"".join(w.to_bytes(8, "little") for w in big)
    ).digest()
    block = hashlib.blake2s(seed + (0).to_bytes(4, "little")).digest()
    assert first == int.from_bytes(block[:8], "little") % gl.P


def test_transcript_kinds_differ():
    b = Blake2sTranscript()
    k = Keccak256Transcript()
    b.witness_field_elements([7])
    k.witness_field_elements([7])
    assert b.get_challenge() != k.get_challenge()


def test_byte_pow_runners():
    for grind, check in (
        (blake2s_pow_grind, blake2s_pow_verify),
        (keccak256_pow_grind, keccak256_pow_verify),
    ):
        t = Blake2sTranscript()
        t.witness_field_elements([42])
        nonce = grind(t, 8)
        after_grind = t.get_challenge()
        tv = Blake2sTranscript()
        tv.witness_field_elements([42])
        assert check(tv, 8, nonce)
        assert tv.get_challenge() == after_grind
        tb = Blake2sTranscript()
        tb.witness_field_elements([42])
        assert not check(tb, 8, nonce + 1)


def test_vk_json_roundtrip_and_setup_serde(tmp_path):
    from test_e2e import CONFIG, build_fibonacci_circuit
    from boojum_tpu.prover import (
        generate_setup,
        prove,
        prove_from_precomputations,
        verify,
    )

    cs, _ = build_fibonacci_circuit(steps=5)
    asm = cs.into_assembly()
    setup = generate_setup(asm, CONFIG)
    # vk json roundtrip
    vk2 = vk_from_json(vk_to_json(setup.vk))
    assert vk2.to_dict() == setup.vk.to_dict()
    # setup fast-serialization roundtrip; prove with the LOADED setup and
    # verify against the ORIGINAL vk
    path = os.path.join(tmp_path, "setup.npz")
    save_setup(path, setup)
    setup2 = load_setup(path)
    assert setup2.vk.to_dict() == setup.vk.to_dict()
    proof = prove_from_precomputations(asm, setup2, CONFIG)
    assert verify(setup.vk, proof, asm.gates)


def test_prove_one_shot_driver():
    from test_e2e import CONFIG, build_fibonacci_circuit
    from boojum_tpu.prover import prove_one_shot, verify_circuit

    cs, _ = build_fibonacci_circuit(steps=5)
    asm, setup, proof = prove_one_shot(cs, CONFIG)
    assert verify_circuit(setup.vk, proof, asm.gates)


def test_legacy_poseidon_permutation_device_host_parity():
    import numpy as np
    import jax.numpy as jnp

    from boojum_tpu.field import gl
    from boojum_tpu.hashes.poseidon import (
        PoseidonSpongeHost,
        leaf_hash as p_leaf_hash,
        poseidon_permutation,
        poseidon_permutation_host,
    )

    rng = np.random.default_rng(50)
    st = rng.integers(0, gl.P, size=(4, 12), dtype=np.uint64)
    dev = np.asarray(poseidon_permutation(jnp.asarray(st)))
    for i in range(4):
        assert [int(x) for x in dev[i]] == poseidon_permutation_host(
            list(st[i])
        )
    vals = rng.integers(0, gl.P, size=(3, 11), dtype=np.uint64)
    dev = np.asarray(p_leaf_hash(jnp.asarray(vals)))
    for i in range(3):
        assert [int(x) for x in dev[i]] == PoseidonSpongeHost.hash_leaf(
            list(vals[i])
        )
    # distinct from Poseidon2 (different round functions, shared constants)
    from boojum_tpu.hashes.poseidon2 import poseidon2_permutation_host

    assert poseidon_permutation_host([1] * 12) != poseidon2_permutation_host(
        [1] * 12
    )


def test_pluggable_transcript_prove_verify():
    from boojum_tpu.examples import build_fma_chain_circuit
    from boojum_tpu.prover import (
        ProofConfig,
        prove_one_shot,
        verify_circuit,
    )

    def build():
        return build_fma_chain_circuit(num_rows=150)[0]

    for kind in ("poseidon", "blake2s"):
        cfg = ProofConfig(
            num_queries=10, fri_final_degree=8, transcript=kind
        )
        asm, setup, proof = prove_one_shot(build(), cfg)
        assert setup.vk.transcript == kind
        assert verify_circuit(setup.vk, proof, asm.gates), kind
        # transcript must be load-bearing: verifying with the wrong kind
        # (a fresh vk clone) must fail
        import dataclasses

        wrong = dataclasses.replace(setup.vk, transcript="poseidon2")
        assert not verify_circuit(wrong, proof, asm.gates), kind
