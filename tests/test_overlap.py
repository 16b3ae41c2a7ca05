"""Overlapped prove pipeline (ISSUE 3): async transfer helper, chunked
H2D upload, double-buffered streamed commits, challenge-independent
prefetch — all on the CPU backend with the 2^10 acceptance circuit.

Pins the acceptance criteria:
- proof bytes AND the Fiat–Shamir digest checkpoint stream are
  bit-identical across the overlapped (materialized) and streamed paths;
- the overlapped prove issues the number of blocking host syncs it was
  measured to issue (metrics guard — the win over the deleted sequenced
  order can't silently regress);
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


def test_fetch_batches_one_blocking_sync():
    arrays = [
        jnp.asarray(np.arange(16, dtype=np.uint64)),
        jnp.asarray(np.arange(16, 48, dtype=np.uint64)),
        jnp.asarray(np.arange(3, dtype=np.uint64)),
    ]
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
    got = transfer.chunked_upload(groups)
    np.testing.assert_array_equal(np.asarray(got), ref)
    # the chunk plan helper mirrors the dispatch exactly
    shapes = transfer.upload_chunk_shapes([g.shape[0] for g in groups], 64)
    assert sum(shapes) == ref.shape[0]
    assert shapes == [2, 2, 1, 2, 1, 1]


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
# End-to-end: overlapped (materialized) vs streamed 2^10 proves
# ---------------------------------------------------------------------------


def _two_path_runs():
    # the shared baseline is the overlapped prove (the only order there is)
    ovl = baseline()
    streamed = recorded_prove("streamed", {"BOOJUM_TPU_STREAM_LDE": "1"})
    return {"overlapped": ovl, "streamed": streamed}


def test_bit_parity_overlapped_streamed():
    """Acceptance: proof bytes and the PR-2 checkpoint stream are
    bit-identical across the materialized and the streamed (double-
    buffered) commits — the overlap layer changes WHEN work is enqueued,
    never what is absorbed."""
    from boojum_tpu.prover import verify

    runs = _two_path_runs()
    p_ovl, r_ovl = runs["overlapped"]
    p_str, r_str = runs["streamed"]

    base = checkpoint_stream(r_ovl)
    assert base, "no checkpoints recorded"
    assert checkpoint_stream(r_str) == base
    assert p_str.to_json() == p_ovl.to_json()

    asm, setup, _config = small_parts()
    assert verify(setup.vk, p_ovl, asm.gates)
    for _label, (_p, rep) in runs.items():
        assert report.validate_report(rep) == []


def test_overlapped_prove_blocking_syncs_pinned():
    """CI guard (acceptance): the prove issues exactly the blocking host
    syncs and d2h batches the overlapped order was measured to issue at
    2^10 rows (8 and 2, read off PR 27's tree before the sequenced order
    went; that order paid one sync per pulled array) — counted at the
    single d2h seam (utils/transfer.py), so a regression that quietly
    re-serializes a pull flips this test."""
    ovl = _two_path_runs()["overlapped"][1]["metrics"]["counters"]
    assert ovl["host.blocking_syncs"] == 8
    assert ovl["transfer.d2h_batches"] == 2  # round 4 + FRI final


def test_overlapped_report_carries_overlap_metrics():
    runs = _two_path_runs()
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
