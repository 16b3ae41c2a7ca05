"""End-to-end limb residency (ISSUE 10).

The tentpole makes (lo, hi) u32 limb planes the canonical on-device
representation for the whole prove (BOOJUM_TPU_LIMB_RESIDENT): witness
columns enter as planes at H2D, stay planes through iNTT/LDE, Poseidon2
sponges, the fused quotient sweep, DEEP and FRI, and `limbs.join`
survives only at the API edge. These tests pin the acceptance criteria:

- 2^10 e2e proof bytes AND the Fiat–Shamir checkpoint stream are
  bit-identical under `=1` vs `=0`, on no-mesh AND the 8-device CPU
  shard_map mesh;
- metrics guards that the resident kernels actually dispatched
  (quotient.resident_coset_sweeps / fri.resident_folds /
  merkle.resident_commits / ntt.resident_transforms nonzero);
- ZERO interior `limb.splits`/`limb.joins` during a resident prove —
  the device-op counters charged inside field/limbs.py split/join; the
  allowlisted edges are host conversions (limb.host_*) plus the
  per-setup `limbs.edge("ingest:*")` splits;
- `prove_report.py --check` (report.validate_report) FAILS a line
  claiming resident dispatch while counting interior splits/joins;
- the resident flag surfaces as a span attribute and in --slo.
"""

import jax
import pytest

from boojum_tpu.utils import report
from proving import (
    baseline,
    checkpoint_stream,
    interpret_e2e,
    mesh_2x4,
    recorded_prove,
    small_parts,
)


# Every test that reads the resident prove is slow (proving.interpret_e2e
# says why). Tier-1 keeps the limb cores (test_limb_sweep's kernel
# parities), the resident kernel set's enumeration and lowering
# (test_precompile) and the dispatch decision (test_variant).
def _both_runs():
    # the shared baseline is the u64 prove (residency is off on CPU unless
    # asked for); it is proved first, so its caches never benefit from
    # resident-run state
    u64 = baseline()
    res = recorded_prove("res", {"BOOJUM_TPU_LIMB_RESIDENT": "1"})
    return {"u64": u64, "res": res}


# ---------------------------------------------------------------------------
# No-mesh acceptance: bit parity + dispatch guards + zero interior
# ---------------------------------------------------------------------------


@interpret_e2e
def test_bit_parity_resident_vs_u64_2pow10():
    """Acceptance: proof bytes AND the checkpoint stream are bit-identical
    with BOOJUM_TPU_LIMB_RESIDENT=1 vs =0 — residency changes WHERE the
    representation converts (nowhere interior), never a value that
    crosses the transcript."""
    from boojum_tpu.prover import verify

    runs = _both_runs()
    p_u, r_u = runs["u64"]
    p_r, r_r = runs["res"]
    base = checkpoint_stream(r_u)
    assert base, "no checkpoints recorded"
    assert checkpoint_stream(r_r) == base
    assert p_r.to_json() == p_u.to_json()
    asm, setup, _config = small_parts()
    assert verify(setup.vk, p_r, asm.gates)
    for rep in (r_u, r_r):
        assert report.validate_report(rep) == []


@interpret_e2e
def test_resident_kernels_actually_dispatched():
    """Metrics guard: the =1 run must have gone through the resident
    coset sweeps, FRI folds, plane commits and plane transforms — a
    silent fallback to the converting path would make the parity test
    (and the zero-conversion guard) vacuous."""
    runs = _both_runs()
    c_u = runs["u64"][1]["metrics"]["counters"]
    c_r = runs["res"][1]["metrics"]["counters"]
    assert c_u.get("quotient.resident_coset_sweeps", 0) == 0
    assert c_u.get("fri.resident_folds", 0) == 0
    assert c_u.get("merkle.resident_commits", 0) == 0
    assert (
        c_r["quotient.resident_coset_sweeps"] == c_r["quotient.coset_sweeps"]
    )
    assert c_r["quotient.resident_coset_sweeps"] > 0
    assert c_r["fri.resident_folds"] == c_r["fri.folds"] > 0
    assert c_r["merkle.resident_commits"] > 0
    assert c_r["ntt.resident_transforms"] > 0
    assert c_r["deep.resident_codewords"] >= 1


@interpret_e2e
def test_zero_interior_conversions_guard():
    """THE residency guard: a resident prove records ZERO interior
    limb.splits / limb.joins (the device-op counters charged inside
    field/limbs.py). Only allowlisted edges may convert: host-side
    splits/joins (H2D witness, host tables, transcript/query joins) and
    the per-setup `ingest:*` edge splits."""
    runs = _both_runs()
    c_r = runs["res"][1]["metrics"]["counters"]
    assert c_r.get("limb.splits", 0) == 0, c_r
    assert c_r.get("limb.joins", 0) == 0, c_r
    # the edges actually ran: host joins happen at every transcript pull
    # and query opening of a resident prove
    assert c_r.get("limb.host_joins", 0) > 0
    assert c_r.get("limb.host_splits", 0) > 0
    # the u64 run (limb kernels off on CPU) never converts at all — and
    # never claims residency
    c_u = runs["u64"][1]["metrics"]["counters"]
    assert c_u.get("quotient.resident_coset_sweeps", 0) == 0


@interpret_e2e
def test_check_gate_rejects_lying_resident_line():
    """report.validate_report (the prove_report.py --check gate) FAILS a
    line claiming resident dispatch while counting interior conversions,
    and accepts the honest resident line."""
    import copy

    runs = _both_runs()
    rep = runs["res"][1]
    assert report.validate_report(rep) == []
    bad = copy.deepcopy(rep)
    bad["metrics"]["counters"]["limb.splits"] = 3
    problems = report.validate_report(bad)
    assert any("interior limb.splits" in p for p in problems), problems
    bad2 = copy.deepcopy(rep)
    bad2["metrics"]["counters"]["limb.joins"] = 1
    assert any(
        "interior limb.joins" in p for p in report.validate_report(bad2)
    )
    # malformed limb counter values fail too
    bad3 = copy.deepcopy(rep)
    bad3["metrics"]["counters"]["limb.host_joins"] = -2
    assert any("limb metric" in p for p in report.validate_report(bad3))


@interpret_e2e
def test_resident_flag_surfaces_in_spans_and_slo():
    """The resident flag rides the round-3/FRI spans as an attribute
    (rendered in the span tree) and --slo counts resident lines."""
    runs = _both_runs()
    rep = runs["res"][1]
    found = []
    for _path, sp in report.flatten_spans(rep):
        a = sp.get("attrs") or {}
        if a.get("resident"):
            found.append(sp.get("name"))
    assert any("round3_coset_sweeps" in (n or "") for n in found), found
    assert any((n or "").startswith("fri_oracle") for n in found), found
    rendered = report.render_report(rep)
    assert " resident" in rendered
    slo = report.slo_summary([rep, runs["u64"][1]])
    assert slo["limb_resident_lines"] == 1


# ---------------------------------------------------------------------------
# shard_map mesh acceptance (8 virtual CPU devices)
# ---------------------------------------------------------------------------


def _mesh_run():
    return recorded_prove(
        "res_sm",
        {
            "BOOJUM_TPU_MESH_MODE": "shard_map",
            "BOOJUM_TPU_LIMB_RESIDENT": "1",
        },
        mesh=mesh_2x4(),
    )


@pytest.mark.slow  # a fresh streamed plane-kernel compile sweep: beyond
# the tier-1 watchdog on the 1-core CPU box; full/standalone runs run it
def test_streamed_resident_bit_parity_2pow10():
    """The resident STREAMED commit path (BOOJUM_TPU_STREAM_LDE=1:
    plane double-buffered blocks, MonomialPlanesSource regens in DEEP and
    queries, the de-meshed FRI entry) routes different graphs than the
    materialized path the main parity tests pin — its proof bytes and
    checkpoints must still be bit-identical, streamed blocks dispatched,
    zero interior conversions."""
    runs = _both_runs()
    p0, r0 = runs["u64"]
    p, r = recorded_prove(
        "res_stream",
        {"BOOJUM_TPU_LIMB_RESIDENT": "1", "BOOJUM_TPU_STREAM_LDE": "1"},
    )
    assert checkpoint_stream(r) == checkpoint_stream(r0)
    assert p.to_json() == p0.to_json()
    c = r["metrics"]["counters"]
    assert c["stream.double_buffered_blocks"] > 0
    assert c["merkle.streamed_commits"] > 0
    assert c["quotient.resident_coset_sweeps"] > 0
    assert c.get("limb.splits", 0) == 0
    assert c.get("limb.joins", 0) == 0
    assert report.validate_report(r) == []


@pytest.mark.slow  # a fresh sm plane-kernel compile sweep: far beyond the
# tier-1 watchdog on the 1-core CPU box; full/standalone runs execute it
@pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)
def test_resident_mesh_bit_parity_2pow10():
    """Acceptance: the resident prove on the 2x4 shard_map mesh —
    per-chip plane kernels, collectives moving lo/hi u32 planes — is
    bit-identical to the meshless u64 prove, with the resident per-chip
    kernels actually dispatched and the ici gauges charged."""
    runs = _both_runs()
    p0, r0 = runs["u64"]
    p, r = _mesh_run()
    assert checkpoint_stream(r) == checkpoint_stream(r0)
    assert p.to_json() == p0.to_json()
    c = r["metrics"]["counters"]
    g = r["metrics"]["gauges"]
    assert c["quotient.resident_coset_sweeps"] > 0
    assert c["fri.resident_folds"] > 0
    assert c["merkle.resident_commits"] > 0
    assert c["merkle.sm_commits"] > 0
    assert c["deep.sm_codewords"] == 1
    assert c["deep.resident_codewords"] == 1
    assert c["ici.all_to_alls"] > 0
    assert g["ici.all_to_all_bytes"] > 0
    assert g["ici.all_gather_bytes"] > 0
    assert c.get("limb.splits", 0) == 0
    assert c.get("limb.joins", 0) == 0
    assert report.validate_report(r) == []
