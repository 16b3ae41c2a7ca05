"""Mesh-native prover parity (ISSUE 5).

The tentpole replaced the GSPMD-only mesh path with a shard_map-based one
(`parallel/shard_sweep.py`): every chip runs the native limb Pallas
kernels on its local shard and the collectives are explicit (one
all_to_all per col->row Merkle pivot, one all_gather per cap), charged to
`ici.*` gauges. These tests pin the acceptance criteria on the virtual
8-device CPU mesh (conftest forces xla_force_host_platform_device_count):

- a 2^10 e2e prove produces bit-identical proof bytes AND digest
  checkpoint streams across {no mesh, 2x4 GSPMD mesh, 2x4 shard_map mesh
  with the limb kernels in interpret mode};
- metrics guards that the shard_map plane kernels actually dispatched
  (quotient.resident_coset_sweeps / fri.resident_folds /
  merkle.resident_commits nonzero) — without them the parity assertions
  would be vacuous;
- the new ici.* byte/time gauges appear in the ProveReport line and
  report.validate_report (the `prove_report.py --check` gate) validates
  them;
- shard_cols' divisibility fallback warns once through the
  boojum_tpu logger and records the chosen axis as a span attribute.
"""

import logging

import jax
import pytest

from boojum_tpu.utils import report
from proving import (
    baseline,
    checkpoint_stream,
    interpret_e2e,
    mesh_2x4 as _mesh,
    recorded_prove,
    small_parts,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


# Every test that reads the shard_map run is slow: its per-chip kernels
# are the interpret-mode limb ones (proving.interpret_e2e says why).
# Tier-1 keeps shard_map over the whole mesh against a direct prove
# (test_service's shard-parallel request, ici.all_to_alls > 0), gspmd
# against one device (test_sharding), the sm kernel set's enumeration and
# lowering (test_precompile) and the limb cores (test_limb_sweep).
def _three_mode_runs():
    # the shared meshless baseline is proved first, so its caches never
    # benefit from mesh-run state; the shard_map run asks for the plane
    # representation (interpret mode on CPU) so the parity covers the
    # per-chip Pallas path the TPU runs, not the u64 XLA kernels
    nomesh = baseline()
    gspmd = recorded_prove(
        "gspmd", {"BOOJUM_TPU_MESH_MODE": "gspmd"}, mesh=_mesh()
    )
    sm = recorded_prove(
        "sm",
        {
            "BOOJUM_TPU_MESH_MODE": "shard_map",
            "BOOJUM_TPU_LIMB_RESIDENT": "1",
        },
        mesh=_mesh(),
    )
    return {"nomesh": nomesh, "gspmd": gspmd, "sm": sm}


@interpret_e2e
def test_three_mode_bit_parity_2pow10():
    """Acceptance: proof bytes AND the digest-checkpoint stream are
    bit-identical across no-mesh / GSPMD-mesh / shard_map-mesh."""
    from boojum_tpu.prover import verify

    runs = _three_mode_runs()
    p0, r0 = runs["nomesh"]
    base_ck = checkpoint_stream(r0)
    assert base_ck, "no checkpoints recorded"
    for mode in ("gspmd", "sm"):
        p, r = runs[mode]
        assert checkpoint_stream(r) == base_ck, mode
        assert p.to_json() == p0.to_json(), mode
    asm, setup, _config = small_parts()
    assert verify(setup.vk, runs["sm"][0], asm.gates)


@interpret_e2e
def test_sm_limb_kernels_actually_dispatched():
    """Metrics guard: the shard_map run must have gone through the
    per-chip plane coset sweep, the plane FRI folds AND the plane
    commits — a silent fallback to u64/XLA or to GSPMD would make the
    parity test vacuous."""
    runs = _three_mode_runs()
    c_sm = runs["sm"][1]["metrics"]["counters"]
    c_g = runs["gspmd"][1]["metrics"]["counters"]
    assert (
        c_sm["quotient.resident_coset_sweeps"]
        == c_sm["quotient.coset_sweeps"]
    )
    assert c_sm["quotient.resident_coset_sweeps"] > 0
    assert c_sm["fri.resident_folds"] == c_sm["fri.folds"] > 0
    assert c_sm["merkle.resident_commits"] > 0
    assert c_sm["merkle.sm_commits"] > 0
    assert c_sm["fri.sm_commits"] > 0
    assert c_sm["fri.sm_folds"] > 0
    assert c_sm["deep.sm_codewords"] == 1
    # GSPMD cannot partition a pallas_call: the legacy mode must NOT have
    # dispatched any plane or shard_map kernel
    for k in (
        "quotient.resident_coset_sweeps", "fri.resident_folds",
        "merkle.resident_commits", "merkle.sm_commits", "fri.sm_commits",
    ):
        assert c_g.get(k, 0) == 0, k


@interpret_e2e
def test_ici_gauges_present_and_checked():
    """Acceptance: ici.all_to_all_bytes / ici.pivot_s appear in the
    shard_map ProveReport line, validate_report (the prove_report.py
    --check gate) passes it, and a report whose collective counters lack
    their gauges FAILS the gate."""
    runs = _three_mode_runs()
    rep = runs["sm"][1]
    gauges = rep["metrics"]["gauges"]
    counters = rep["metrics"]["counters"]
    assert gauges["ici.all_to_all_bytes"] > 0
    assert gauges["ici.pivot_s"] > 0
    assert gauges["ici.all_gather_bytes"] > 0
    assert counters["ici.all_to_alls"] > 0
    assert counters["ici.all_gathers"] > 0
    assert report.validate_report(rep) == []
    # the meshless / gspmd runs never touch the explicit-collective seam
    for mode in ("nomesh", "gspmd"):
        c = runs[mode][1]["metrics"]["counters"]
        assert c.get("ici.all_to_alls", 0) == 0, mode
        assert report.validate_report(runs[mode][1]) == []
    # mutilated report: counter without gauge must be flagged
    import copy

    bad = copy.deepcopy(rep)
    del bad["metrics"]["gauges"]["ici.all_to_all_bytes"]
    problems = report.validate_report(bad)
    assert any("ici.all_to_all_bytes" in p for p in problems)
    bad2 = copy.deepcopy(rep)
    bad2["metrics"]["gauges"]["ici.pivot_s"] = -1.0
    assert any("ici.pivot_s" in p for p in report.validate_report(bad2))


@interpret_e2e
def test_streamed_sm_bit_parity_2pow10():
    """The streamed commit path under a shard_map mesh (BOOJUM_TPU_
    STREAM_LDE=1: shard_sweep.streamed_leaf_digests_sm per-chip absorbs
    + the de-meshed round-5/FRI fallback for the streamed regens) routes
    DIFFERENT graphs than the materialized path the three-mode tests pin
    — its proof bytes and checkpoints must still be bit-identical, with
    the per-chip streamed blocks actually dispatched."""
    runs = _three_mode_runs()
    p0, r0 = runs["nomesh"]
    p, r = recorded_prove(
        "sm_stream",
        {
            "BOOJUM_TPU_MESH_MODE": "shard_map",
            "BOOJUM_TPU_LIMB_RESIDENT": "1",
            "BOOJUM_TPU_STREAM_LDE": "1",
        },
        mesh=_mesh(),
    )
    assert checkpoint_stream(r) == checkpoint_stream(r0)
    assert p.to_json() == p0.to_json()
    c = r["metrics"]["counters"]
    assert c["stream.sm_blocks"] > 0
    assert c["merkle.streamed_commits"] > 0
    assert report.validate_report(r) == []


def test_shard_cols_fallback_warning(caplog):
    """shard_cols must log ONE warning per (shape, mesh) when 'col' does
    not divide the batch axis, and record the chosen axis as an attribute
    on the current span."""
    import jax.numpy as jnp

    from boojum_tpu.parallel import sharding as sh
    from boojum_tpu.utils.spans import SpanRecorder, install_recorder, span

    m = _mesh()
    sh._SHARD_COLS_WARNED.clear()
    rec = SpanRecorder()
    prev = install_recorder(rec)
    # the boojum_tpu logger does not propagate (profiling.py owns its
    # handler pipeline) — attach caplog's handler directly
    lg = logging.getLogger("boojum_tpu")
    lg.addHandler(caplog.handler)
    try:
        with sh.prover_mesh(m):
            with caplog.at_level(logging.WARNING, logger="boojum_tpu"):
                with span("fallback_probe"):
                    # 15 columns over the 2-way 'col' axis: falls back to
                    # the (power-of-two) domain axis
                    sh.shard_cols(jnp.zeros((15, 256), jnp.uint64))
                    # repeat: the warning must NOT repeat
                    sh.shard_cols(jnp.zeros((15, 256), jnp.uint64))
                with span("clean_probe"):
                    sh.shard_cols(jnp.zeros((16, 256), jnp.uint64))
    finally:
        install_recorder(prev)
        lg.removeHandler(caplog.handler)
    warnings = [
        r for r in caplog.records if "shard_cols" in r.getMessage()
    ]
    assert len(warnings) == 1
    spans = {s["name"]: s for s in rec.roots}
    assert (
        spans["fallback_probe"]["attrs"]["shard_cols_axis"]
        == "domain(col,row)"
    )
    assert spans["clean_probe"]["attrs"]["shard_cols_axis"] == "col"


def test_fold_shards_ok():
    from boojum_tpu.parallel.shard_sweep import fold_shards_ok

    m = _mesh()  # 8 devices
    assert fold_shards_ok(2048, 3, m)  # 2048 % 64 == 0
    assert fold_shards_ok(256, 3, m)
    assert not fold_shards_ok(32, 3, m)  # 32 % 64 != 0
    assert not fold_shards_ok(2048 + 8, 1, m)
