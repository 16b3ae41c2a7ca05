"""Overlapped prove pipeline (ISSUE 3): async transfer helper, chunked
H2D upload, double-buffered streamed commits, challenge-independent
prefetch — all on the CPU backend with the 2^10 acceptance circuit.

Pins the acceptance criteria:
- proof bytes AND the Fiat–Shamir digest checkpoint stream are
  bit-identical across the overlapped / sequenced / streamed paths;
- the overlapped prove issues STRICTLY FEWER blocking host syncs than
  the sequenced baseline (metrics guard — the win can't silently
  regress);
- a raise inside a streamed commit block still yields a partial
  ProveReport (error-annotated span tree + the checkpoints up to the
  failure).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.utils import metrics, report, transfer
from proving import (
    baseline,
    checkpoint_stream,
    recorded_prove,
    small_parts,
)


# ---------------------------------------------------------------------------
# Async transfer helper units
# ---------------------------------------------------------------------------


def test_overlap_enabled_parsing(monkeypatch):
    monkeypatch.delenv("BOOJUM_TPU_OVERLAP", raising=False)
    assert transfer.overlap_enabled() is True  # default on
    for v in ("1", "true", "on", "yes"):
        monkeypatch.setenv("BOOJUM_TPU_OVERLAP", v)
        assert transfer.overlap_enabled() is True
    for v in ("0", "false", "off", "no"):
        monkeypatch.setenv("BOOJUM_TPU_OVERLAP", v)
        assert transfer.overlap_enabled() is False
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "maybe")
    with pytest.raises(ValueError, match="BOOJUM_TPU_OVERLAP"):
        transfer.overlap_enabled()


def test_to_host_passthrough_and_device_counting():
    host = np.arange(7, dtype=np.uint64)
    reg = metrics.start_metrics()
    try:
        out = transfer.to_host(host)
        np.testing.assert_array_equal(out, host)
        assert reg.counters.get("host.blocking_syncs", 0) == 0  # host value
        dev = jnp.asarray(host)
        out = transfer.to_host(dev)
        np.testing.assert_array_equal(out, host)
        assert reg.counters["host.blocking_syncs"] == 1
        assert reg.counters["transfer.d2h_bytes"] == host.nbytes
    finally:
        metrics.stop_metrics()


def test_fetch_batches_one_blocking_sync(monkeypatch):
    arrays = [
        jnp.asarray(np.arange(16, dtype=np.uint64)),
        jnp.asarray(np.arange(16, 48, dtype=np.uint64)),
        jnp.asarray(np.arange(3, dtype=np.uint64)),
    ]
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "1")
    reg = metrics.start_metrics()
    try:
        got = transfer.fetch_np(*arrays, label="unit")
        assert reg.counters["host.blocking_syncs"] == 1  # ONE for the batch
        assert reg.counters["transfer.d2h_batches"] == 1
        assert reg.counters["transfer.d2h_bytes"] == sum(
            a.size * 8 for a in arrays
        )
        assert "transfer.overlap_s" in reg.gauges
    finally:
        metrics.stop_metrics()
    for a, h in zip(arrays, got):
        np.testing.assert_array_equal(np.asarray(a), h)

    # sequenced twin: one blocking sync PER array
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "0")
    reg = metrics.start_metrics()
    try:
        got2 = transfer.fetch_np(*arrays)
        assert reg.counters["host.blocking_syncs"] == len(arrays)
    finally:
        metrics.stop_metrics()
    for a, b in zip(got, got2):
        np.testing.assert_array_equal(a, b)

    # wait() is idempotent
    f = transfer.start_fetch(arrays)
    assert f.wait() is f.wait()


def test_chunked_upload_parity(monkeypatch):
    rng = np.random.default_rng(5)
    groups = [
        rng.integers(0, 1 << 63, (5, 64), dtype=np.uint64),
        rng.integers(0, 1 << 63, (3, 64), dtype=np.uint64),
        rng.integers(0, 1 << 63, (1, 64), dtype=np.uint64),
    ]
    ref = np.concatenate(groups, axis=0)
    # force multi-chunk uploads (2 rows per chunk at n=64)
    monkeypatch.setattr(transfer, "H2D_CHUNK_BYTES", 2 * 64 * 8)
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "1")
    got = transfer.chunked_upload(groups)
    np.testing.assert_array_equal(np.asarray(got), ref)
    # the chunk plan helper mirrors the dispatch exactly
    shapes = transfer.upload_chunk_shapes([g.shape[0] for g in groups], 64)
    assert sum(shapes) == ref.shape[0]
    assert shapes == [2, 2, 1, 2, 1, 1]
    # overlap off: the legacy single synchronous upload, same bytes
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "0")
    got_seq = transfer.chunked_upload(groups)
    np.testing.assert_array_equal(np.asarray(got_seq), ref)


def test_render_report_shows_occupancy():
    rep = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "label": "occ",
        "wall_s": 2.0,
        "spans": [
            {
                "name": "prove",
                "start_s": 0.0,
                "wall_s": 2.0,
                "children": [
                    {
                        "name": "round4",
                        "start_s": 0.1,
                        "wall_s": 1.0,
                        "sync_s": 0.25,
                        "overlap_s": 0.5,
                        "children": [],
                    }
                ],
            }
        ],
        "metrics": {"counters": {}, "gauges": {}, "boundaries": []},
        "checkpoints": [],
    }
    text = report.render_report(rep)
    assert "occ=25%" in text  # sync_s/wall in the tree
    assert "ovl=0.500s" in text
    # top-N leaf table carries the sync/occ column too
    assert "sync=0.250s" in text


# ---------------------------------------------------------------------------
# End-to-end: overlapped vs sequenced vs streamed 2^10 proves
# ---------------------------------------------------------------------------


def _three_path_runs():
    # sequenced FIRST so its counters never benefit from state the
    # overlapped run warmed; the overlapped run is the shared baseline
    # (overlap is on unless BOOJUM_TPU_OVERLAP=0)
    seq = recorded_prove("sequenced", {"BOOJUM_TPU_OVERLAP": "0"})
    ovl = baseline()
    streamed = recorded_prove("streamed", {"BOOJUM_TPU_STREAM_LDE": "1"})
    return {"sequenced": seq, "overlapped": ovl, "streamed": streamed}


def test_bit_parity_overlapped_sequenced_streamed():
    """Acceptance: proof bytes and the PR-2 checkpoint stream are
    bit-identical across all three dispatch orders — the overlap layer
    changes WHEN work is enqueued, never what is absorbed."""
    from boojum_tpu.prover import verify

    runs = _three_path_runs()
    p_seq, r_seq = runs["sequenced"]
    p_ovl, r_ovl = runs["overlapped"]
    p_str, r_str = runs["streamed"]

    base = checkpoint_stream(r_seq)
    assert base, "no checkpoints recorded"
    assert checkpoint_stream(r_ovl) == base
    assert checkpoint_stream(r_str) == base
    assert p_ovl.to_json() == p_seq.to_json()
    assert p_str.to_json() == p_seq.to_json()

    asm, setup, _config = small_parts()
    assert verify(setup.vk, p_ovl, asm.gates)
    for _label, (_p, rep) in runs.items():
        assert report.validate_report(rep) == []


def test_overlapped_prove_strictly_fewer_blocking_syncs():
    """CI guard (acceptance): the overlapped path must issue strictly
    fewer blocking host syncs than the sequenced path — counted at the
    single d2h seam (utils/transfer.py), so a regression that quietly
    re-serializes a pull flips this test."""
    runs = _three_path_runs()
    seq = runs["sequenced"][1]["metrics"]["counters"]
    ovl = runs["overlapped"][1]["metrics"]["counters"]
    assert seq.get("host.blocking_syncs", 0) > 0
    assert ovl.get("host.blocking_syncs", 0) > 0
    assert ovl["host.blocking_syncs"] < seq["host.blocking_syncs"]
    # the saving must come from batching, not from skipped transfers:
    # both paths move the same d2h bytes
    assert ovl["transfer.d2h_bytes"] == seq["transfer.d2h_bytes"]
    assert ovl.get("transfer.d2h_batches", 0) >= 2  # round 4 + FRI final


def test_overlapped_report_carries_overlap_metrics():
    runs = _three_path_runs()
    r_ovl = runs["overlapped"][1]
    gauges = r_ovl["metrics"]["gauges"]
    assert gauges.get("transfer.overlap_s", 0) > 0
    # the streamed run exercised the double-buffered commit path
    r_str = runs["streamed"][1]
    assert (
        r_str["metrics"]["counters"].get("stream.double_buffered_blocks", 0)
        >= 2
    )


def test_error_in_streamed_block_yields_partial_report(monkeypatch):
    """A raise inside a streamed commit block must still produce a
    ProveReport: error-annotated spans for the failing stage and every
    checkpoint recorded before the failure."""
    from boojum_tpu.prover import prove
    from boojum_tpu.prover import streaming

    asm, setup, config = small_parts()
    monkeypatch.setenv("BOOJUM_TPU_OVERLAP", "1")
    monkeypatch.setenv("BOOJUM_TPU_STREAM_LDE", "1")

    real_absorb = streaming._absorb_cols
    calls = {"n": 0}

    def exploding_absorb(state, cols):
        calls["n"] += 1
        if calls["n"] >= 2:  # witness block passes, stage-2 block raises
            raise RuntimeError("injected block failure")
        return real_absorb(state, cols)

    monkeypatch.setattr(streaming, "_absorb_cols", exploding_absorb)
    with report.flight_recording(label="injected") as rec:
        with pytest.raises(RuntimeError, match="injected block failure"):
            prove(asm, setup, config)
    rep = report.build_report(rec)

    # round 0 + round 1 checkpoints made it; the failing round did not
    labels = [e["label"] for e in rep["checkpoints"]]
    assert "setup_cap" in labels and "witness_cap" in labels
    assert "stage2_cap" not in labels
    # the span tree records the failure instead of dropping the stage
    errors = [
        (path, sp["error"])
        for path, sp in report.flatten_spans(rep)
        if sp.get("error")
    ]
    assert errors, "no error-annotated span recorded"
    assert any("injected block failure" in e for _p, e in errors)
    assert any("round2" in p for p, _e in errors)
