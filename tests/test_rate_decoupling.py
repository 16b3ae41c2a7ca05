"""Quotient-rate decoupling: sweep at Q cosets, commit at fri_lde_factor.

Mirrors the reference's used_lde_degree (prover.rs:313) vs
subset_for_degree(fri_lde_factor) (setup.rs:1187) split — the Era main-VM
golden proof commits at LDE 2 while its quotient has 8 chunks. These tests
pin: Q derivation from constraint degrees, prove/verify at L < Q, proof
layout (2Q quotient leaf values), tamper rejection, and VK serde roundtrip.
"""

import numpy as np
import pytest

from boojum_tpu.examples import build_fma_chain_circuit
from boojum_tpu.prover import (
    ProofConfig,
    generate_setup,
    prove,
    prove_one_shot,
    verify,
    verify_circuit,
)


def _fma_circuit():
    # 300 fma gates: a 2^8-row trace
    return build_fma_chain_circuit(num_rows=150)[0]


def test_decoupled_commit_rate_below_quotient_degree():
    cfg = ProofConfig(fri_lde_factor=2, num_queries=20, fri_final_degree=8)
    asm, setup, proof = prove_one_shot(_fma_circuit(), cfg)
    # degree bound: max_allowed 4 + 1 -> next pow2 = 8
    assert setup.vk.quotient_degree == 8
    assert setup.vk.fri_lde_factor == 2
    assert len(proof.queries[0].quotient.leaf_values) == 2 * 8
    assert proof.config["quotient_degree"] == 8
    assert verify_circuit(setup.vk, proof, asm.gates)


def test_decoupled_tamper_rejected():
    cfg = ProofConfig(fri_lde_factor=2, num_queries=12, fri_final_degree=8)
    asm, setup, proof = prove_one_shot(_fma_circuit(), cfg)
    q = proof.queries[0].quotient
    q.leaf_values[0] = (q.leaf_values[0] + 1) % ((1 << 64) - (1 << 32) + 1)
    assert not verify_circuit(setup.vk, proof, asm.gates)


def test_explicit_quotient_degree_override():
    # force Q=16 > derived 8; still proves and verifies
    cfg = ProofConfig(
        fri_lde_factor=2,
        num_queries=12,
        fri_final_degree=8,
        quotient_degree=16,
    )
    asm, setup, proof = prove_one_shot(_fma_circuit(), cfg)
    assert setup.vk.quotient_degree == 16
    assert len(proof.queries[0].quotient.leaf_values) == 32
    assert verify_circuit(setup.vk, proof, asm.gates)


def test_vk_serde_roundtrip_quotient_degree():
    from boojum_tpu.serialization import vk_from_json, vk_to_json

    cfg = ProofConfig(fri_lde_factor=2, num_queries=8, fri_final_degree=8)
    cs = _fma_circuit()
    asm = cs.into_assembly()
    setup = generate_setup(asm, cfg)
    vk2 = vk_from_json(vk_to_json(setup.vk))
    assert vk2.quotient_degree == setup.vk.quotient_degree
    assert vk2.effective_quotient_degree() == 8


def test_decoupled_with_lookups():
    # the streamed per-coset sweep's lookup branches at L < Q (specialized
    # columns; the xor example circuit)
    from boojum_tpu.examples import build_xor_lookup_circuit

    cs, _, _ = build_xor_lookup_circuit(num_lookups=16)
    asm = cs.into_assembly()
    cfg = ProofConfig(fri_lde_factor=2, num_queries=16, fri_final_degree=8)
    setup = generate_setup(asm, cfg)
    assert setup.vk.quotient_degree > setup.vk.fri_lde_factor
    proof = prove(asm, setup, cfg)
    assert verify(setup.vk, proof, asm.gates)
    # lookup tamper: bump a multiplicity-ish stage-2 leaf -> reject
    q = proof.queries[0].stage2
    q.leaf_values[-1] = (q.leaf_values[-1] + 1) % ((1 << 64) - (1 << 32) + 1)
    assert not verify(setup.vk, proof, asm.gates)


def test_streamed_lde_proof_byte_identical(monkeypatch):
    """BOOJUM_TPU_STREAM_LDE=1 forces the streamed commit/DEEP/query path
    (load-bearing for the 2^20 result); its proof must be BYTE-identical to
    the materialized path's — block ordering, trailing-chunk sponge padding
    and the per-column query regeneration are all pinned by this."""
    cfg = ProofConfig(fri_lde_factor=2, num_queries=10, fri_final_degree=8)
    cs = _fma_circuit()
    asm = cs.into_assembly()
    setup = generate_setup(asm, cfg)
    baseline = prove(asm, setup, cfg)
    monkeypatch.setenv("BOOJUM_TPU_STREAM_LDE", "1")
    streamed = prove(asm, setup, cfg)
    assert streamed.to_json() == baseline.to_json()
    assert verify(setup.vk, streamed, asm.gates)


@pytest.mark.parametrize("L", [2, 8])
def test_committed_cosets_are_read_and_the_bytes_keep(L, monkeypatch):
    """ISSUE 27: on the first min(L, Q) cosets round 3 reads the witness,
    setup and stage-2 evaluations from the commitments (L < Q: the Era
    split; L = Q: every coset). Same proof bytes and checkpoint stream as
    with every evaluation transformed from the monomials, and the flight
    recording counts which was which."""
    from boojum_tpu.prover import prover as P
    from proving import checkpoint_stream, prove_recorded

    cfg = ProofConfig(fri_lde_factor=L, num_queries=10, fri_final_degree=8)
    asm = _fma_circuit().into_assembly()
    parts = (asm, generate_setup(asm, cfg), cfg)
    Q = parts[1].vk.effective_quotient_degree()
    assert Q == 8
    proof, rep = prove_recorded("reuse", parts=parts)
    counters = rep["metrics"]["counters"]
    assert counters["quotient.coset_evals_reused"] == 3 * min(L, Q)
    assert counters["ntt.coset_evals"] == 4 * Q - 3 * min(L, Q)
    monkeypatch.setattr(P, "coset_is_committed", lambda *a: False)
    plain, plain_rep = prove_recorded("transform", parts=parts)
    assert plain_rep["metrics"]["counters"]["ntt.coset_evals"] == 4 * Q
    assert plain.to_json() == proof.to_json()
    assert checkpoint_stream(plain_rep) == checkpoint_stream(rep)
    assert verify(parts[1].vk, proof, asm.gates)
