"""BabyBear under the full PLONKish prover (ISSUE 20).

The tentpole makes the REAL prove() pipeline field-generic: under
BOOJUM_TPU_FIELD=babybear the same rounds, Fiat-Shamir checkpoints and
clock stages run on the plane-free u32 kernel set (prover/prover_bb.py)
— witness ingestion as bare u32 lanes, stage-2 copy-permutation/lookup
via BabyBear batch inversion, the fused coset quotient sweep, Poseidon2-
BB Merkle commits, DEEP at a GF(p^4) z, the FRI chain. These tests pin
the acceptance criteria:

- full prove() at 2^10 on the fma AND xor4-lookup circuits: proof bytes
  and checkpoint streams bit-identical between the device backend and
  the NumPy reference twin, deterministic across runs;
- ZERO limb.splits / limb.joins during a BabyBear full prove while the
  `_bb` kernel counters move (the plane-free guard is not vacuous);
- the quotient identity at z re-checked from the proof's own openings
  via BBExtScalarOps (prover_bb.quotient_identity_at_z);
- the poseidon-rf e2e leg through the REAL prove() entry: dispatch,
  cost record stamped field=babybear, report validator accepts it;
- the analytic cost sheet: per-stage HBM bytes under babybear exactly
  HALF the Goldilocks sheet for the same geometry, flops identical;
- goldilocks untouched when the env var is unset (the GL path still
  proves and verifies, no babybear stamp anywhere);
- Poseidon2-BB: the Pallas kernel (forced interpret=True on CPU)
  matches the XLA twin permutation;
- sha256-over-babybear REJECTED at synthesis by the field-capacity
  guard with a clear error (satellite: cs.require_field_bits);
- trend/SLO plumbing (satellites): _trend_identity splits series by
  field, slo_summary counts lines per field, warm_geometry warms the
  bb_ntt table set under its field-aware key.
"""

import functools
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from boojum_tpu.examples import (
    build_poseidon_rf_circuit,
    build_xor_lookup_circuit,
)
from proving import environ, fma_assembly


def _bb_field():
    return environ({"BOOJUM_TPU_FIELD": "babybear"})


def _cfg():
    from boojum_tpu.prover import ProofConfig

    return ProofConfig(fri_lde_factor=2, num_queries=8, fri_final_degree=8)


@functools.lru_cache(maxsize=None)
def _circuit(kind):
    """(assembly, setup) synthesized UNDER the babybear env var — the CS
    stamps its field at synthesis, generate_setup dispatches on it."""
    with _bb_field():
        if kind == "fma":  # tier-1's shared circuit, over this field
            asm = fma_assembly()
        elif kind == "xor4":
            cs, _, _ = build_xor_lookup_circuit(
                num_lookups=600, capacity=1 << 11
            )
            asm = cs.into_assembly()
        else:  # poseidon-rf
            cs, _ = build_poseidon_rf_circuit(num_rounds=48)
            asm = cs.into_assembly()
        assert asm.field == "babybear"
        from boojum_tpu.prover import generate_setup

        return asm, generate_setup(asm, _cfg())


def _checkpointed(fn, *args):
    from boojum_tpu.utils.report import CheckpointLog, install_checkpoint_log

    log = CheckpointLog()
    prev = install_checkpoint_log(log)
    try:
        proof = fn(*args)
    finally:
        install_checkpoint_log(prev)
    return proof, log.entries


@functools.lru_cache(maxsize=None)
def _device_run(kind):
    """ONE device-backend full prove per circuit, shared by the parity /
    determinism / zero-conversion tests, recorded under metrics."""
    from boojum_tpu.prover.prover_bb import prove_full_babybear
    from boojum_tpu.utils import metrics

    asm, setup = _circuit(kind)
    with _bb_field():
        reg = metrics.start_metrics()
        try:
            proof, entries = _checkpointed(
                prove_full_babybear, asm, setup, _cfg()
            )
        finally:
            metrics.stop_metrics()
    return proof, entries, reg.to_dict()["counters"]


@functools.lru_cache(maxsize=None)
def _reference_run(kind):
    from boojum_tpu.compat.prove_reference_bb import (
        prove_full_babybear_reference,
    )

    asm, setup = _circuit(kind)
    with _bb_field():
        return _checkpointed(prove_full_babybear_reference, asm, setup, _cfg())


# ---------------------------------------------------------------------------
# Device / numpy parity at 2^10 (the acceptance bar)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["fma", "xor4"])
def test_full_prover_proof_bytes_parity(kind):
    asm, _ = _circuit(kind)
    assert asm.trace_len == 1 << 10
    dev, _, _ = _device_run(kind)
    ref, _ = _reference_run(kind)
    assert dev.to_json() == ref.to_json()
    assert dev.config.get("field") == "babybear"


@pytest.mark.parametrize("kind", ["fma", "xor4"])
def test_full_prover_checkpoint_stream_parity(kind):
    _, dev_entries, _ = _device_run(kind)
    _, ref_entries = _reference_run(kind)
    assert dev_entries == ref_entries
    labels = [e["label"] for e in dev_entries]
    # the GL round protocol, replayed verbatim: commits, challenges,
    # FRI chain, grinding, query sampling
    for must in (
        "setup_cap", "witness_cap", "stage2_cap", "quotient_cap",
        "evaluations", "deep_challenge", "fri_final_monomials",
        "pow_nonce", "query_indices",
    ):
        assert must in labels, (must, labels)


def test_full_prover_deterministic_across_runs():
    from boojum_tpu.prover.prover_bb import prove_full_babybear

    asm, setup = _circuit("fma")
    dev, entries, _ = _device_run("fma")
    with _bb_field():
        again, entries2 = _checkpointed(
            prove_full_babybear, asm, setup, _cfg()
        )
    assert again.to_json() == dev.to_json()
    assert entries2 == entries


def test_zero_limb_conversions_during_full_prove():
    """THE plane-free guard at full-prover scope: no (lo, hi) planes
    exist anywhere on the babybear prove() path — and the `_bb` twins
    all dispatched, so the zero is not vacuous."""
    for kind in ("fma", "xor4"):
        _, _, c = _device_run(kind)
        for k in ("limb.splits", "limb.joins", "limb.host_splits",
                  "limb.host_joins"):
            assert c.get(k, 0) == 0, (kind, k, c)
        for k in ("ntt.bb_dispatches", "lde.bb_dispatches",
                  "merkle.bb_commits", "stage2.bb_scans",
                  "gate_sweep.bb_builds", "quotient.bb_full_sweeps",
                  "deep.bb_accumulates", "fri.bb_folds"):
            assert c.get(k, 0) >= 1, (kind, k, c)
    _, _, c = _device_run("xor4")
    assert c.get("lookup.bb_polys", 0) >= 1, c


@pytest.mark.parametrize("kind", ["fma", "xor4"])
def test_quotient_identity_at_z(kind):
    """Self-check straight from the proof's openings: the gate + copy +
    lookup terms recombined over GF(p^4) scalar ops must equal
    T(z)·(z^n − 1) — any mis-wired column ordering or challenge replay
    lands here, not in a downstream consumer."""
    from boojum_tpu.prover.prover_bb import quotient_identity_at_z

    asm, setup = _circuit(kind)
    proof, _, _ = _device_run(kind)
    with _bb_field():
        assert quotient_identity_at_z(asm, setup, proof)


# ---------------------------------------------------------------------------
# The REAL prove() entry: dispatch, clock, cost record (poseidon-rf leg)
# ---------------------------------------------------------------------------


def test_prove_entry_poseidon_rf_dispatches_and_stamps_cost():
    from boojum_tpu.prover import prove
    from boojum_tpu.prover.prover_bb import quotient_identity_at_z
    from boojum_tpu.utils.report import (
        build_report,
        flight_recording,
        validate_report,
    )

    asm, setup = _circuit("poseidon")
    with _bb_field():
        with flight_recording(label="bb-full-e2e") as rec:
            proof = prove(asm, setup, _cfg())
        report = build_report(rec)
        assert quotient_identity_at_z(asm, setup, proof)
    assert proof.config.get("field") == "babybear"
    cost = report.get("cost")
    assert cost is not None and cost.get("field") == "babybear"
    # the artifact passes the same validator prove_report.py --check runs
    assert validate_report(report) == []


def test_cost_sheet_hbm_bytes_exactly_half_of_goldilocks():
    """The >= 2x byte-reduction claim at full-prover scope: the same
    geometry costed under babybear moves exactly HALF the HBM/ICI bytes
    of the Goldilocks sheet in EVERY stage — flops unchanged (the op
    count does not depend on the element width)."""
    from boojum_tpu.prover.shape_key import shape_bucket
    from boojum_tpu.utils.costmodel import stage_costs

    asm, _ = _circuit("fma")
    sb = shape_bucket(asm, _cfg())
    prev = os.environ.pop("BOOJUM_TPU_FIELD", None)
    try:
        gl = stage_costs(sb, _cfg())
    finally:
        if prev is not None:
            os.environ["BOOJUM_TPU_FIELD"] = prev
    with _bb_field():
        bbc = stage_costs(sb, _cfg())
    assert set(gl) == set(bbc) and gl
    for st, g in gl.items():
        b = bbc[st]
        assert b["hbm_bytes"] == pytest.approx(g["hbm_bytes"] * 0.5), st
        assert b["ici_bytes"] == pytest.approx(g["ici_bytes"] * 0.5), st
        assert b["flops"] == pytest.approx(g["flops"]), st


# ---------------------------------------------------------------------------
# Goldilocks untouched with the env unset
# ---------------------------------------------------------------------------


def test_goldilocks_path_unaffected_when_env_unset(monkeypatch):
    from proving import baseline, small_parts

    from boojum_tpu.field.spec import active_field
    from boojum_tpu.prover import verify

    monkeypatch.delenv("BOOJUM_TPU_FIELD", raising=False)
    assert active_field() == "goldilocks"
    asm, setup, _cfg = small_parts()
    assert asm.field == "goldilocks"
    assert setup.vk.transcript == "poseidon2"  # not the _babybear twin
    proof, _report = baseline()
    assert proof.config.get("field") != "babybear"
    assert verify(setup.vk, proof, asm.gates)


# ---------------------------------------------------------------------------
# Poseidon2-BB: Pallas (interpret) vs XLA parity on CPU
# ---------------------------------------------------------------------------


def test_poseidon2_bb_pallas_interpret_matches_xla():
    import jax.numpy as jnp

    from boojum_tpu.field import babybear as bb
    from boojum_tpu.hashes.poseidon2_bb import (
        poseidon2_permutation_bb_pallas,
        poseidon2_permutation_bb_xla,
    )

    rng = np.random.default_rng(20)
    states = rng.integers(0, bb.P, (64, 16), dtype=np.uint64).astype(
        np.uint32
    )
    # boundary rows: all zeros, all p-1
    states[0] = 0
    states[1] = bb.P - 1
    x = jnp.asarray(states)
    got = np.asarray(poseidon2_permutation_bb_pallas(x, interpret=True))
    want = np.asarray(poseidon2_permutation_bb_xla(x))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Field-capacity guard: sha256 over babybear is a synthesis error
# ---------------------------------------------------------------------------


def test_sha256_over_babybear_rejected_at_synthesis():
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.implementations.reference_cs import (
        FieldCapacityError,
    )
    from boojum_tpu.cs.types import CSGeometry, LookupParameters
    from boojum_tpu.gadgets import allocate_u8_input, sha256

    geom = CSGeometry(60, 0, 8, 7)
    with _bb_field():
        cs = ConstraintSystem(
            geom, 1 << 15,
            lookup_params=LookupParameters(width=4, num_repetitions=8),
        )
        with pytest.raises(FieldCapacityError) as exc:
            sha256(cs, allocate_u8_input(cs, b"abc"))
    msg = str(exc.value)
    assert "babybear" in msg and "goldilocks" in msg


# ---------------------------------------------------------------------------
# Satellites: trend identity, SLO field axis, field-aware geometry warm
# ---------------------------------------------------------------------------


def test_trend_identity_splits_series_by_field():
    from boojum_tpu.utils.report import _trend_identity

    host = {"host_fp": "fp0", "device_kind": "cpu", "backend": "cpu",
            "jax": "1", "jaxlib": "1"}
    gl = _trend_identity({"host": host})
    bb_line = _trend_identity({"host": host, "field": "babybear"})
    bb_cost = _trend_identity(
        {"host": host, "cost": {"field": "babybear"}}
    )
    assert gl != bb_line
    assert bb_line == bb_cost
    assert bb_line.endswith("field=babybear")
    # goldilocks stays UNSUFFIXED: the repo's pre-field history (and the
    # ""-identity legacy-adoption path) keeps gating new GL lines
    assert gl == _trend_identity({"host": host, "field": "goldilocks"})
    assert "field=" not in gl
    assert _trend_identity({}) == ""


def test_trend_series_do_not_cross_gate_between_fields():
    """A synthetic mixed history: GL rounds at one wall, a babybear
    round 2x slower — with the field folded into the identity the BB
    point opens its OWN series instead of regressing the GL one."""
    from boojum_tpu.utils.report import trend_gate, trend_series

    host = {"host_fp": "fp0", "device_kind": "cpu", "backend": "cpu",
            "jax": "1", "jaxlib": "1"}

    def pt(label, wall, field=None):
        d = {"label": label, "identity": None,
             "values": {"total_wall": {"value": wall, "unit": "s"}}}
        src = {"host": host}
        if field:
            src["field"] = field
        from boojum_tpu.utils.report import _trend_identity

        d["identity"] = _trend_identity(src)
        return d

    points = [pt("r1", 1.0), pt("r2", 1.02), pt("r3", 2.2, "babybear")]
    series = trend_series(points)
    assert len(series) == 2  # one GL series, one BB series
    assert trend_gate(series) == []  # the BB point gates nothing


def test_slo_summary_counts_lines_per_field():
    from boojum_tpu.utils.report import render_slo, slo_summary

    reports = [
        {"field": "babybear"},
        {"cost": {"field": "babybear", "stages": {}}},
        {"cost": {"field": "goldilocks", "stages": {}}},
    ]
    summary = slo_summary(reports)
    assert summary["fields"] == {"babybear": 2, "goldilocks": 1}
    assert "field backend babybear=2, goldilocks=1" in render_slo(summary)


def test_warm_geometry_is_field_aware():
    """The same shape bucket warmed under goldilocks must warm AGAIN
    under babybear (different table set), and the babybear leg must
    actually populate the bb_ntt twiddle / scale caches the full prover
    reads."""
    from boojum_tpu.ntt import bb_ntt
    from boojum_tpu.prover import bb_kernels as BK
    from boojum_tpu.service.cache import DeviceCacheManager

    bucket = types.SimpleNamespace(
        log_n=8, trace_len=1 << 8, lde_factor=2, quotient_degree=8,
        fri_final_degree=8, fri_schedule=(), lookups=False,
    )
    mgr = DeviceCacheManager()
    with _bb_field():
        before = bb_ntt._twiddles.cache_info().hits + \
            bb_ntt._twiddles.cache_info().misses
        assert mgr.warm_geometry(bucket) is True
        after = bb_ntt._twiddles.cache_info().hits + \
            bb_ntt._twiddles.cache_info().misses
        assert after > before  # the bb table set was touched
        assert BK.domain_xs_bb.cache_info().currsize >= 1
        assert mgr.warm_geometry(bucket) is False  # idempotent
    # goldilocks: SAME geometry, DIFFERENT key — warms its own set
    assert mgr.warm_geometry(bucket) is True
    assert mgr.warm_geometry(bucket) is False
