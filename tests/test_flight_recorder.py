"""Flight recorder tests (ISSUE 2): hierarchical spans, metrics registry,
Fiat–Shamir digest checkpoints, ProveReport artifact + CLI — all on the
CPU backend with a 2^10 circuit (tier-1 safe)."""

import io
import json
import logging
import os
import subprocess
import sys

import pytest

from boojum_tpu.utils import metrics, profiling, report, spans
from proving import baseline, prove_recorded, small_parts

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_tree_nesting_and_ordering():
    rec = spans.start_recording()
    try:
        with spans.span("outer"):
            with spans.span("child_a"):
                pass
            with spans.span("child_b"):
                with spans.span("grandchild"):
                    pass
        with spans.span("second_root"):
            pass
    finally:
        spans.stop_recording()
    tree = rec.tree()
    assert [sp["name"] for sp in tree] == ["outer", "second_root"]
    outer = tree[0]
    assert [c["name"] for c in outer["children"]] == ["child_a", "child_b"]
    assert outer["children"][1]["children"][0]["name"] == "grandchild"
    # ordering: siblings start in sequence, children inside the parent
    a, b = outer["children"]
    assert outer["start_s"] <= a["start_s"] <= b["start_s"]
    assert all(sp["wall_s"] >= 0 for sp, _ in _walk(tree))
    # parent covers its children
    assert outer["wall_s"] >= a["wall_s"] + b["wall_s"] - 1e-6


def _walk(tree):
    for sp in tree:
        yield sp, None
        yield from _walk(sp["children"])


def test_error_span_recorded_partially():
    rec = spans.start_recording()
    try:
        with pytest.raises(ValueError, match="boom"):
            with spans.span("outer"):
                with spans.span("failing"):
                    raise ValueError("boom")
    finally:
        spans.stop_recording()
    outer = rec.tree()[0]
    assert outer["error"].startswith("ValueError")
    failing = outer["children"][0]
    assert failing["name"] == "failing"
    assert failing["error"].startswith("ValueError: boom")
    assert failing["wall_s"] is not None and failing["wall_s"] >= 0


def test_stage_timer_records_sink_entry_on_exception():
    """Satellite: a raising stage must not lose its timing line or its
    sink entry (the old stage_timer body was not try/finally-wrapped)."""
    sink = profiling.collect_stages()
    try:
        with pytest.raises(RuntimeError):
            with profiling.stage_timer("exploding_stage"):
                raise RuntimeError("mid-stage failure")
    finally:
        profiling.stop_collecting_stages()
    assert len(sink) == 1
    name, dt = sink[0]
    assert name == "exploding_stage" and dt >= 0


def test_span_disabled_is_noop():
    assert spans.current_recorder() is None
    with spans.span("nothing") as sp:
        assert sp is None


# ---------------------------------------------------------------------------
# Logging (satellite: profiling.log -> logging.getLogger("boojum_tpu"))
# ---------------------------------------------------------------------------


def test_log_composes_with_user_handlers():
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("boojum_tpu")
    h = _Capture()
    logger.addHandler(h)
    try:
        profiling.log("user handler sees this")
    finally:
        logger.removeHandler(h)
    assert "user handler sees this" in records


def test_stderr_handler_install_is_idempotent():
    """Satellite (ISSUE 9): toggling BOOJUM_TPU_PROFILE twice in one
    process (set_profiling on/off/on), or re-running the module-level
    install, must never stack a second stderr handler — each stage line
    would then print once per toggle. The handler is keyed by NAME, not
    class identity, so even a re-executed module (stale class object)
    cannot defeat the guard."""
    logger = logging.getLogger("boojum_tpu")

    def gated_handlers():
        return [
            h for h in logger.handlers
            if getattr(h, "name", None) == profiling._STDERR_HANDLER_NAME
        ]

    assert len(gated_handlers()) == 1  # module import installed exactly one
    try:
        for _ in range(3):  # "toggled twice" and then some
            profiling.set_profiling(True)
            profiling.set_profiling(False)
        profiling.ensure_stderr_handler()
        profiling.ensure_stderr_handler()
        assert len(gated_handlers()) == 1
        # the line really prints ONCE, not once per toggle
        err = io.StringIO()
        old = sys.stderr
        sys.stderr = err
        try:
            profiling.set_profiling(True)
            profiling.log("single emission")
        finally:
            sys.stderr = old
            profiling.set_profiling(None)
        assert err.getvalue().count("single emission") == 1
    finally:
        profiling.set_profiling(None)


def test_log_stderr_gated_on_profiling_env():
    err = io.StringIO()
    old = sys.stderr
    sys.stderr = err
    try:
        profiling.set_profiling(False)
        profiling.log("hidden line")
        profiling.set_profiling(True)
        profiling.log("visible line")
    finally:
        sys.stderr = old
        profiling.set_profiling(None)
    out = err.getvalue()
    assert "hidden line" not in out
    assert "[boojum_tpu] visible line" in out


# ---------------------------------------------------------------------------
# Contextvars scoping (ISSUE 9): the packed-service concurrency contract
# ---------------------------------------------------------------------------


def test_scoped_collectors_isolate_concurrent_contexts():
    """Two 'requests' recording concurrently on pool threads — each
    scoped flight recorder must collect ONLY its own spans, counters
    (canary check) and checkpoint stream, with zero cross-bleed. This is
    the unit-level contract behind packed proof-parallel recording."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    gate = threading.Barrier(2, timeout=30)

    def request(i):
        with report.flight_recording(label=f"req-{i}", scoped=True) as rec:
            gate.wait()  # both contexts genuinely record AT THE SAME TIME
            metrics.count(f"canary.{i}")
            metrics.count("shared.counter")
            report.checkpoint(0, "setup_cap", [i])
            report.checkpoint(1, "witness_cap", [i, i])
            with spans.span("service_request", request=f"req-{i}"):
                with spans.span("inner"):
                    gate.wait()
        return report.build_report(
            rec,
            extra={
                "request": {
                    "id": f"req-{i}", "bucket": "n2^10",
                    "placement": "proof_parallel",
                    "queue_latency_s": 0.0, "prove_wall_s": 0.01,
                }
            },
        )

    with ThreadPoolExecutor(max_workers=2) as pool:
        reps = list(pool.map(request, range(2)))

    for i, rep in enumerate(reps):
        other = 1 - i
        counters = rep["metrics"]["counters"]
        assert counters[f"canary.{i}"] == 1
        assert f"canary.{other}" not in counters, "counter bled"
        assert counters["shared.counter"] == 1, "shared counter double-counted"
        digests = [e["digest"] for e in rep["checkpoints"]]
        assert len(digests) == 2
        assert digests != [
            e["digest"] for e in reps[other]["checkpoints"]
        ], "checkpoint stream bled"
        names = [sp["name"] for sp in rep["spans"]]
        assert names == ["service_request"], names
        assert rep["spans"][0]["attrs"]["request"] == f"req-{i}"
        # --check level: the line is well-formed and single-request
        assert report.validate_report(rep) == []


def test_scoped_collectors_override_global_default_and_restore():
    """The process-global default context (bench/CLI posture) keeps
    working: a scoped context overrides it locally, and recording falls
    back to the global collectors the moment the scope exits."""
    rec_global = spans.start_recording()
    reg_global = metrics.start_metrics()
    log_global = report.CheckpointLog()
    prev_log = report.install_checkpoint_log(log_global)
    try:
        with spans.span("before_scope"):
            pass
        metrics.count("global.counter")
        report.checkpoint(0, "setup_cap", [1])
        with report.flight_recording(label="scoped", scoped=True) as rec:
            with spans.span("scoped_span"):
                pass
            metrics.count("scoped.counter")
            report.checkpoint(0, "setup_cap", [2])
        with spans.span("after_scope"):
            pass
        metrics.count("global.counter")
    finally:
        report.install_checkpoint_log(prev_log)
        metrics.stop_metrics()
        spans.stop_recording()
    assert [sp["name"] for sp in rec_global.tree()] == [
        "before_scope", "after_scope"
    ]
    assert reg_global.counters == {"global.counter": 2}
    assert len(log_global.entries) == 1
    assert [sp["name"] for sp in rec.spans.tree()] == ["scoped_span"]
    assert rec.metrics.counters == {"scoped.counter": 1}
    assert len(rec.checkpoints.entries) == 1
    # and a thread spawned OUTSIDE any scope sees the global default
    # (threads start with an empty context -> fallback)
    import threading

    seen = {}

    def probe():
        seen["rec"] = spans.current_recorder()

    rec2 = spans.start_recording()
    try:
        t = threading.Thread(target=probe)
        t.start()
        t.join()
    finally:
        spans.stop_recording()
    assert seen["rec"] is rec2


def test_validate_report_rejects_mixed_request_ids():
    """--check satellite (ISSUE 9): one line carrying spans of TWO
    request ids means scoped collectors bled across packed requests —
    the exact corruption the contextvar scoping prevents — and must
    fail the gate."""
    base = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "wall_s": 0.5,
        "spans": [
            {"name": "service_request", "start_s": 0.0, "wall_s": 0.1,
             "span_id": "11" * 8, "children": [],
             "attrs": {"request": "req-1"}},
        ],
        "metrics": {"counters": {}},
        "checkpoints": [],
        "request": {
            "id": "req-1", "bucket": "n2^10", "placement": "proof_parallel",
            "queue_latency_s": 0.0, "prove_wall_s": 0.1,
        },
    }
    assert report.validate_report(base) == []
    bad = dict(base)
    bad["spans"] = base["spans"] + [
        {"name": "service_request", "start_s": 0.2, "wall_s": 0.1,
         "span_id": "22" * 8, "children": [],
         "attrs": {"request": "req-2"}},
    ]
    probs = report.validate_report(bad)
    assert any("mixes request ids" in p for p in probs), probs


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_metrics_registry_counters_and_gauges():
    # disabled: module-level hooks are no-ops
    assert metrics.current_registry() is None
    metrics.count("never.recorded", 5)

    reg = metrics.start_metrics()
    try:
        metrics.count("ntt.calls")
        metrics.count("ntt.calls", 2)
        metrics.count_bytes_h2d(1024)
        metrics.gauge_max("mem.peak", 10)
        metrics.gauge_max("mem.peak", 7)  # lower: must not regress the max
        metrics.stage_boundary("round1")
    finally:
        metrics.stop_metrics()
    d = reg.to_dict()
    assert d["counters"]["ntt.calls"] == 3
    assert d["counters"]["transfer.h2d_bytes"] == 1024
    assert d["counters"]["transfer.h2d_ops"] == 1
    assert d["gauges"]["mem.peak"] == 10
    assert d["boundaries"][0]["label"] == "round1"
    assert "live_arrays" in d["boundaries"][0]
    assert metrics.count("after.stop") is None  # no raise after stop


# ---------------------------------------------------------------------------
# Checkpoint digests
# ---------------------------------------------------------------------------


def test_digest_of_nested_values_stable():
    a = report.digest_of([(1, 2), [3, [4]]])
    b = report.digest_of([1, 2, 3, 4])
    assert a == b  # flattening is structural, digest is over the sequence
    assert a != report.digest_of([1, 2, 3, 5])
    assert len(a) == 64


# ---------------------------------------------------------------------------
# End-to-end: recorded 2^10 proves
# ---------------------------------------------------------------------------


def test_checkpoints_identical_across_reruns_and_diverge_on_flip():
    asm, setup, config = small_parts()
    _p1, rep1 = baseline()
    _p2, rep2 = prove_recorded("rerun")  # a second, fresh prove

    assert report.validate_report(rep1) == []
    # every Fiat–Shamir round is checkpointed
    rounds = {e["round"] for e in rep1["checkpoints"]}
    assert rounds == {0, 1, 2, 3, 4, 5}
    labels = [e["label"] for e in rep1["checkpoints"]]
    for want in (
        "setup_cap", "witness_cap", "challenges", "stage2_cap", "alpha",
        "quotient_cap", "z", "evaluations", "deep_challenge",
        "fri_cap_0", "fri_challenge_0", "fri_final_monomials",
        "query_indices",
    ):
        assert want in labels, want

    d = report.diff_reports(rep1, rep2)
    assert d["first_checkpoint_divergence"] is None
    assert d["num_checkpoints"][0] == d["num_checkpoints"][1] > 0

    # flip one witness word: the diff must name round 1's witness commit
    # as the first diverging stage
    import numpy as np

    from boojum_tpu.field import gl

    wv = list(asm.witness_vec())
    placed = np.asarray(asm.copy_placement)
    place = int(placed[placed >= 0].min())  # a place wired into copy cols
    wv[place] = (int(wv[place]) + 1) % gl.P
    asm_flipped = asm.with_external_witness(wv)
    _p3, rep3 = prove_recorded(
        "flipped", parts=(asm_flipped, setup, config)
    )
    d2 = report.diff_reports(rep1, rep3)
    fd = d2["first_checkpoint_divergence"]
    assert fd is not None
    assert fd["label"] == "witness_cap" and fd["round"] == 1
    assert fd["a_digest"] != fd["b_digest"]


def test_report_env_emission_schema_and_cli(tmp_path, monkeypatch):
    """BOOJUM_TPU_REPORT=<path> makes a plain prove() emit a ProveReport
    line; the artifact passes --check, covers >= 90% of the prove wall in
    spans, and self-diffs clean (the post-bench smoke gate)."""
    asm, setup, config = small_parts()
    path = str(tmp_path / "prove_report.jsonl")
    monkeypatch.setenv("BOOJUM_TPU_REPORT", path)
    from boojum_tpu.prover import prove, verify

    proof = prove(asm, setup, config)
    assert verify(setup.vk, proof, asm.gates)
    monkeypatch.delenv("BOOJUM_TPU_REPORT")

    reports = report.load_reports(path)
    assert len(reports) == 1
    rep = reports[0]
    assert rep["kind"] == report.REPORT_KIND
    assert rep["schema"] == report.REPORT_SCHEMA
    assert report.validate_report(rep) == []
    assert report.span_coverage(rep) >= 0.90
    assert {e["round"] for e in rep["checkpoints"]} == {0, 1, 2, 3, 4, 5}
    counters = rep["metrics"]["counters"]
    assert counters.get("prover.proves") == 1
    assert counters.get("merkle.tree_builds", 0) >= 3
    assert counters.get("transfer.d2h_bytes", 0) > 0

    # CLI: render + check + self-diff, in-process (no jax import needed)
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import prove_report as cli
    finally:
        sys.path.pop(0)
    assert cli.main([path]) == 0
    assert cli.main(["--check", path]) == 0
    assert cli.main(["--diff", path, path]) == 0


def test_prove_report_cli_subprocess_is_light():
    """The CLI must work standalone (no boojum_tpu/jax import): --check an
    artifact written by hand."""
    rep = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "label": "hand",
        "wall_s": 1.0,
        "spans": [
            {
                "name": "prove",
                "start_s": 0.0,
                "wall_s": 1.0,
                "span_id": "aa" * 8,
                "trace_id": "ab" * 16,
                "children": [
                    {
                        "name": "round1",
                        "start_s": 0.0,
                        "wall_s": 0.95,
                        "span_id": "bb" * 8,
                        "parent_span_id": "aa" * 8,
                        "children": [],
                    }
                ],
            }
        ],
        "metrics": {"counters": {}, "gauges": {}, "boundaries": []},
        "checkpoints": [
            {
                "seq": 0,
                "round": 0,
                "label": "setup_cap",
                "digest": "0" * 64,
            },
            {
                "seq": 1,
                "round": 1,
                "label": "witness_cap",
                "digest": "1" * 64,
            },
        ],
    }
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "r.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps(rep) + "\n")
        env = {
            k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"
        }
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO_ROOT, "scripts", "prove_report.py"),
                "--check",
                path,
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=60,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "ok" in out.stdout

        # monotonicity violations must fail the gate
        bad = dict(rep)
        bad["checkpoints"] = [
            dict(rep["checkpoints"][1], seq=0, round=1),
            dict(rep["checkpoints"][0], seq=1, round=0),
        ]
        with open(path, "w") as f:
            f.write(json.dumps(bad) + "\n")
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO_ROOT, "scripts", "prove_report.py"),
                "--check",
                path,
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=60,
        )
        assert out.returncode == 1
        assert "round" in out.stdout


def test_validate_report_flags_malformed():
    assert report.validate_report({}) != []
    ok = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "wall_s": 0.5,
        "spans": [],
        "metrics": {"counters": {}},
        "checkpoints": [],
    }
    assert report.validate_report(ok) == []
    bad_digest = dict(
        ok,
        checkpoints=[
            {"seq": 0, "round": 0, "label": "x", "digest": "nothex"}
        ],
    )
    assert any("digest" in p for p in report.validate_report(bad_digest))
