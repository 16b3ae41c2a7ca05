"""Live telemetry plane tests (ISSUE 9): background sampler, stdlib
HTTP endpoints (/metrics /healthz /slo), on-demand jax.profiler capture
(BOOJUM_TPU_XPROF), report schema 2 `telemetry` records, the module-
level-state guard over utils/, and the service e2e with the plane up.

Everything here runs on the virtual 8-device CPU mesh; the only tests
paying a real prove are the service e2e ones (2^10, cache-warm)."""

import io
import json
import os
import re
import subprocess
import sys
import time
import tokenize
import urllib.request

import pytest

from boojum_tpu.utils import metrics, profiling, report, telemetry
from proving import small_parts

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Sampler
# ---------------------------------------------------------------------------


def test_sampler_time_series_gauges_and_providers():
    s = telemetry.TelemetrySampler(interval_s=0.05)
    s.add_provider("service.queue.depth", lambda: 7)
    s.add_provider(
        "service.queue.lane", lambda: {"interactive": 1, "batch": 2}
    )
    s.add_provider("broken", lambda: 1 / 0)
    s.add_provider("junk", lambda: {"state": None})  # unconvertible value
    first = s.sample_once()
    s.add_provider("service.queue.depth", lambda: 3)  # re-register wins
    second = s.sample_once()
    # built-in census + provider values, flat and numeric
    assert first["live_arrays"] >= 0 and first["live_bytes"] >= 0
    assert first["service.queue.depth"] == 7
    assert second["service.queue.depth"] == 3
    assert first["service.queue.lane.interactive"] == 1
    assert "broken" not in first
    assert "junk.state" not in first  # junk VALUES are skipped too, not
    #                                   just raising providers
    assert s.provider_errors == 4  # 2 samples x (broken + junk)
    # current-value + high-water gauges on the sampler's registry
    g = s.registry.to_dict()["gauges"]
    assert g["telemetry.service.queue.depth"] == 3
    assert g["telemetry.service.queue.depth_high_water"] == 7
    assert s.registry.to_dict()["counters"]["telemetry.provider_errors"] == 4
    # snapshot = the report-line `telemetry` record, and it validates
    snap = s.snapshot()
    assert snap["interval_s"] == 0.05 and snap["ticks"] == 2
    assert [x["t_s"] for x in snap["samples"]] == sorted(
        x["t_s"] for x in snap["samples"]
    )
    line = {
        "kind": report.REPORT_KIND, "schema": report.REPORT_SCHEMA,
        "wall_s": 0.1, "spans": [], "metrics": {"counters": {}},
        "checkpoints": [], "telemetry": snap,
    }
    assert report.validate_report(line) == []
    # series view for one key
    assert [v for _t, v in s.series("service.queue.depth")] == [7, 3]


def test_sampler_background_thread_ticks_and_stops():
    s = telemetry.TelemetrySampler(interval_s=0.02)
    s.start()
    try:
        deadline = time.time() + 5.0
        while s.ticks < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert s.ticks >= 3
        assert s.running()
    finally:
        s.stop()
    assert not s.running()
    ticks = s.ticks
    time.sleep(0.08)
    assert s.ticks == ticks  # really stopped


def test_sampler_interval_env_and_validation(monkeypatch):
    monkeypatch.setenv("BOOJUM_TPU_TELEMETRY_INTERVAL", "0.25")
    assert telemetry.telemetry_interval_s() == 0.25
    assert telemetry.TelemetrySampler().interval_s == 0.25
    monkeypatch.setenv("BOOJUM_TPU_TELEMETRY_INTERVAL", "0")
    with pytest.raises(ValueError, match="must be > 0"):
        telemetry.telemetry_interval_s()
    monkeypatch.delenv("BOOJUM_TPU_TELEMETRY_INTERVAL")
    assert telemetry.telemetry_interval_s() == telemetry.DEFAULT_INTERVAL_S


def test_installed_sampler_rides_report_lines():
    s = telemetry.TelemetrySampler(interval_s=0.05)
    s.sample_once()
    prev = telemetry.install_sampler(s)
    try:
        with report.flight_recording(label="with_telemetry") as rec:
            metrics.count("x")
        line = report.build_report(rec)
    finally:
        telemetry.install_sampler(prev)
    assert line["schema"] == report.REPORT_SCHEMA
    assert line["telemetry"]["ticks"] == 1
    assert report.validate_report(line) == []
    # without a sampler, no record (and schema-1 lines stay valid)
    with report.flight_recording(label="bare") as rec:
        pass
    assert "telemetry" not in report.build_report(rec)


# ---------------------------------------------------------------------------
# HTTP plane
# ---------------------------------------------------------------------------


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


def test_prometheus_text_rendering():
    from boojum_tpu.service.http_metrics import prometheus_text

    text = prometheus_text(
        {
            "counters": {"service.queue.rejects": 2},
            "gauges": {
                "telemetry.service.queue.depth": 5.0,
                "bad value": float("nan"),
            },
        }
    )
    assert "# TYPE boojum_tpu_service_queue_rejects counter" in text
    assert "boojum_tpu_service_queue_rejects 2" in text
    assert "boojum_tpu_telemetry_service_queue_depth 5.0" in text
    assert "nan" not in text  # NaN readings are dropped, not exported
    assert prometheus_text({}) == "\n"


def test_metrics_plane_endpoints():
    from boojum_tpu.service.http_metrics import MetricsPlane

    s = telemetry.TelemetrySampler(interval_s=0.05)
    s.add_provider("service.queue.depth", lambda: 4)
    s.sample_once()
    plane = MetricsPlane(
        s,
        health_fn=lambda: {"served": 9},
        slo_fn=lambda: {"requests": 1, "proofs_per_sec": 2.5},
        port=0,
    )
    port = plane.start()
    try:
        assert port > 0
        status, ctype, body = _get(plane.url("/metrics"))
        assert status == 200 and "text/plain" in ctype
        assert "boojum_tpu_telemetry_service_queue_depth 4.0" in body
        assert "boojum_tpu_telemetry_live_bytes" in body
        status, ctype, body = _get(plane.url("/healthz"))
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert health["served"] == 9 and health["telemetry_ticks"] == 1
        status, _ctype, body = _get(plane.url("/slo"))
        assert status == 200 and json.loads(body)["proofs_per_sec"] == 2.5
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(plane.url("/nonsense"))
        assert exc.value.code == 404
    finally:
        plane.stop()
    # stopped: the port no longer accepts
    with pytest.raises(Exception):
        _get(plane.url("/healthz"), timeout=2)


def test_metrics_plane_survives_callback_failure():
    from boojum_tpu.service.http_metrics import MetricsPlane

    s = telemetry.TelemetrySampler(interval_s=0.05)
    plane = MetricsPlane(
        s, health_fn=lambda: 1 / 0, slo_fn=lambda: 1 / 0, port=0
    )
    plane.start()
    try:
        status, _c, body = _get(plane.url("/healthz"))
        assert status == 200
        assert "health_fn_error" in json.loads(body)
        status, _c, body = _get(plane.url("/slo"))
        assert status == 200 and "error" in json.loads(body)
    finally:
        plane.stop()


# ---------------------------------------------------------------------------
# On-demand jax.profiler capture (BOOJUM_TPU_XPROF)
# ---------------------------------------------------------------------------


def test_xprof_spec_parsing():
    assert profiling._parse_xprof("/tmp/x") == ("/tmp/x", 1)
    assert profiling._parse_xprof("/tmp/x:3") == ("/tmp/x", 3)
    assert profiling._parse_xprof("/tmp/x:0") == ("/tmp/x", 0)
    # a non-numeric tail is part of the path, not a budget
    assert profiling._parse_xprof("rel:dir") == ("rel:dir", 1)


def test_xprof_budget_captures_next_n_proves(tmp_path, monkeypatch):
    import jax.numpy as jnp

    xdir = str(tmp_path / "traces")
    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{xdir}:2")
    assert profiling.xprof_remaining() == 2
    dirs = []
    for i in range(3):
        with profiling.maybe_trace_capture(f"unit_{i}") as td:
            if td is not None:
                jnp.zeros(8).block_until_ready()
            dirs.append(td)
    # exactly N=2 captures, each into its own labeled subdirectory
    assert dirs[2] is None
    assert dirs[0] != dirs[1]
    for td in dirs[:2]:
        assert td is not None and td.startswith(xdir)
        assert os.path.isdir(td)
    assert profiling.xprof_remaining() == 0
    # forced capture (the service's per-request flag) ignores the spent
    # budget and still lands under the armed dir
    with profiling.maybe_trace_capture("forced", force=True) as td:
        assert td is not None and td.startswith(xdir)
        jnp.zeros(8).block_until_ready()
    # ...and a forced capture never BURNS an armed budget: the budget
    # is for the next N un-flagged proves
    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{xdir}-rearm:1")
    assert profiling.xprof_remaining() == 1
    with profiling.maybe_trace_capture("forced2", force=True) as td:
        assert td is not None
    assert profiling.xprof_remaining() == 1
    # CHANGING the env re-arms; re-exporting the same value does not
    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{xdir}:1")
    assert profiling.xprof_remaining() == 1
    monkeypatch.delenv("BOOJUM_TPU_XPROF")
    assert profiling.xprof_remaining() == 0


def test_xprof_failed_start_refunds_budget(tmp_path, monkeypatch):
    """A transient start_trace failure must not eat the armed budget —
    the operator asked for N captures and should still get them."""
    import jax

    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{tmp_path / 'refund'}:1")
    assert profiling.xprof_remaining() == 1

    def boom(*a, **k):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with profiling.maybe_trace_capture("failing") as td:
        assert td is None
    assert profiling.xprof_remaining() == 1  # refunded
    monkeypatch.undo()
    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{tmp_path / 'refund'}:1")
    with profiling.maybe_trace_capture("retry") as td:
        assert td is not None
    assert profiling.xprof_remaining() == 0
    monkeypatch.delenv("BOOJUM_TPU_XPROF")
    profiling.xprof_remaining()


def test_xprof_no_nested_capture(tmp_path, monkeypatch):
    monkeypatch.setenv("BOOJUM_TPU_XPROF", f"{tmp_path / 't'}:5")
    profiling.xprof_remaining()  # refresh budget from env
    with profiling.maybe_trace_capture("outer") as outer:
        assert outer is not None
        # a packed sibling / inner prove() must not double-capture
        with profiling.maybe_trace_capture("inner") as inner:
            assert inner is None
        with profiling.maybe_trace_capture("inner_forced", force=True) as f:
            assert f is None
    monkeypatch.delenv("BOOJUM_TPU_XPROF")
    profiling.xprof_remaining()


# ---------------------------------------------------------------------------
# Service report lines carry the SERVICE's time series
# ---------------------------------------------------------------------------


def test_request_lines_use_service_sampler_not_foreign_global(
    eight_devices, tmp_path, monkeypatch
):
    """bench.py --service installs its own (provider-less) sampler in
    the process-global slot BEFORE the service exists; the per-request
    lines must still carry the service sampler's queue/lane/in-flight
    axes, not the foreign sampler's bare census."""
    from boojum_tpu.service import ProvingService, ServiceConfig
    from boojum_tpu.service.scheduler import Placement

    foreign = telemetry.TelemetrySampler(interval_s=9.0)
    foreign.sample_once()
    prev = telemetry.install_sampler(foreign)
    try:
        rpt = str(tmp_path / "svc.jsonl")
        svc = ProvingService(
            ServiceConfig(precompile="off", report_path=rpt,
                          telemetry_interval_s=7.0)
        )
        svc.sampler.sample_once()

        def fake_run(req, placement, packed=1, device=None):
            req.slo = {
                "id": req.id, "bucket": req.bucket_key,
                "placement": placement.kind,
                "queue_latency_s": 0.0, "prove_wall_s": 0.01,
            }
            req._done.set()
            return 1

        monkeypatch.setattr(svc, "_run_request", fake_run)
        req = svc.submit(*small_parts())
        svc.queue.pop_batch()
        placement = Placement("proof_parallel", None, total_devices=8)
        assert svc._serve_one(req, placement) == 1
    finally:
        telemetry.install_sampler(prev)
    (line,) = report.load_reports(rpt)
    sample_keys = {
        k for s in line["telemetry"]["samples"] for k in s
    }
    assert "service.queue.depth" in sample_keys
    assert line["telemetry"]["interval_s"] == 7.0  # the service's, not 9.0
    assert report.validate_report(line) == []


# ---------------------------------------------------------------------------
# Guard: no new module-level mutable collector state in utils/
# ---------------------------------------------------------------------------


def test_no_module_level_mutable_collector_state_in_utils():
    """CI satellite (ISSUE 9): the scoping refactor holds only while
    utils/ keeps ALL mutable collector state inside instances resolved
    through the contextvar-first accessors. A new module-level mutable
    collector (list/dict/set/deque/registry at import scope) reopens
    the packed-recording corruption — fail it at review time."""
    utils_dir = os.path.join(REPO_ROOT, "boojum_tpu", "utils")
    assign = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*(?:\s*:[^=]+)?\s*=\s*(.+)$")
    mutable = re.compile(
        r"(\[\s*\]|\{\s*\}|\bset\(\s*\)|\bdeque\(|\blist\(\s*\)"
        r"|\bdict\(\s*\)|\bOrderedDict\(|Registry\(\s*\)"
        r"|SpanRecorder\(|CheckpointLog\(|FlightRecorder\("
        r"|TelemetrySampler\()"
    )
    offenders = []
    for fname in sorted(os.listdir(utils_dir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(utils_dir, fname)
        with open(path) as f:
            src = f.read()
        # strings/comments (docstring examples) must not false-positive
        code_starts = set()
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type not in (
                tokenize.STRING, tokenize.COMMENT, tokenize.NL,
                tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT,
            ):
                code_starts.add(tok.start[0])
        for lineno, line in enumerate(src.splitlines(), 1):
            if lineno not in code_starts or line[:1] in (" ", "\t"):
                continue
            m = assign.match(line)
            if not m:
                continue
            rhs = m.group(1)
            if "ContextVar(" in rhs:  # the sanctioned scoping mechanism
                continue
            if mutable.search(rhs):
                offenders.append(f"{fname}:{lineno}: {line.strip()}")
    assert not offenders, (
        "module-level mutable collector state in utils/ (must live in "
        "instances behind the contextvar-first accessors):\n"
        + "\n".join(offenders)
    )


# ---------------------------------------------------------------------------
# prove_report CLI: --slo with zero request records, telemetry --check
# ---------------------------------------------------------------------------


def _cli():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import prove_report
    finally:
        sys.path.pop(0)
    return prove_report


def _plain_line():
    return {
        "kind": report.REPORT_KIND, "schema": report.REPORT_SCHEMA,
        "label": "bench_rep0", "wall_s": 1.0, "spans": [],
        "metrics": {"counters": {}}, "checkpoints": [],
    }


def test_slo_with_zero_request_records_exits_zero(tmp_path, capsys):
    """Satellite (ISSUE 9): --slo on an artifact of plain proves (no
    `request` records) has no serving span to divide over — that is an
    explicit message and exit 0, not a crash or a failure."""
    path = str(tmp_path / "plain.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(_plain_line()) + "\n")
        f.write(json.dumps(_plain_line()) + "\n")
    rc = _cli().main(["--slo", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no serving span" in out
    assert "0 request records in 2 line(s)" in out
    # the library-level aggregation is also total on empty input
    summary = report.slo_summary([_plain_line()])
    assert summary["requests"] == 0 and summary["proofs_per_sec"] is None


def test_check_validates_telemetry_record(tmp_path, capsys):
    good = dict(_plain_line())
    s = telemetry.TelemetrySampler(interval_s=0.05)
    s.sample_once()
    good["telemetry"] = s.snapshot()
    bad = dict(_plain_line())
    bad["telemetry"] = {
        "interval_s": -1,
        "ticks": 1,
        "samples": [{"t_s": 2.0}, {"t_s": 1.0, "live_bytes": -5}],
    }
    path = str(tmp_path / "mixed.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps(bad) + "\n")
    rc = _cli().main(["--check", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "line 0" in out and "ok" in out
    assert "interval_s" in out and "decreases" in out and "live_bytes" in out


# ---------------------------------------------------------------------------
# Service e2e: the live plane around real proves (cache-warm 2^10)
# ---------------------------------------------------------------------------


@pytest.fixture
def eight_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")


def test_service_worker_loop_serves_live_plane(eight_devices, tmp_path):
    """E2E acceptance slice: a service with the telemetry plane up
    serves real requests; /metrics during the queued phase shows
    service.queue depth + device-memory/census gauges, the report lines
    carry `telemetry` records and pass --check IN A SUBPROCESS, and
    /slo reflects the drained batch."""
    from boojum_tpu.service import ProvingService, ServiceConfig

    asm, setup, cfg = small_parts()
    rpt = str(tmp_path / "svc.jsonl")
    svc = ProvingService(
        ServiceConfig(
            precompile="off", report_path=rpt,
            telemetry_interval_s=0.1, metrics_port=0,
        )
    )
    port = svc.start_telemetry(0)
    try:
        reqs = [svc.submit(asm, setup, cfg) for _ in range(2)]
        svc.sampler.sample_once()  # deterministic queued-phase sample
        _status, _ctype, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert "boojum_tpu_telemetry_service_queue_depth 2.0" in body
        assert "boojum_tpu_telemetry_live_bytes" in body
        assert "boojum_tpu_telemetry_service_inflight" in body
        summary = svc.run_worker()
        assert summary["served"] == 2
        # run_worker leaves the caller-started plane running
        _status, _c, body = _get(f"http://127.0.0.1:{port}/healthz")
        health = json.loads(body)
        assert health["served"] == 2 and health["queue_depth"] == 0
        _status, _c, body = _get(f"http://127.0.0.1:{port}/slo")
        slo = json.loads(body)
        assert slo["requests"] == 2 and slo["served"] == 2
        for r in reqs:
            r.result()
    finally:
        svc.stop_telemetry()
    assert not svc.sampler.running()

    lines = report.load_reports(rpt)
    req_lines = [ln for ln in lines if "request" in ln]
    assert len(req_lines) == 2
    for ln in req_lines:
        assert ln["schema"] == report.REPORT_SCHEMA
        assert ln["telemetry"]["ticks"] >= 1
        assert report.validate_report(ln) == [], ln["request"]["id"]
    # the satellite's tier-1 gate: --check the freshly generated
    # artifact in a SUBPROCESS (stdlib-only CLI, no jax import)
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "prove_report.py"),
            "--check", rpt,
        ],
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    slo_out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "prove_report.py"),
            "--slo", rpt,
        ],
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"},
    )
    assert slo_out.returncode == 0
    assert "proofs/sec" in slo_out.stdout


def test_service_capture_trace_per_request(eight_devices, tmp_path):
    """The per-request capture_trace flag records a jax.profiler trace
    attributable to exactly that request (trace record in ITS line)."""
    from boojum_tpu.service import ProvingService, ServiceConfig

    asm, setup, cfg = small_parts()
    rpt = str(tmp_path / "trace.jsonl")
    os.environ["BOOJUM_TPU_XPROF"] = str(tmp_path / "xprof")
    try:
        profiling.xprof_remaining()  # refresh: arms budget=1
        os.environ.pop("BOOJUM_TPU_XPROF")
        profiling.xprof_remaining()  # disarm again: force flag only
        svc = ProvingService(
            ServiceConfig(precompile="off", report_path=rpt,
                          telemetry_interval_s=5.0)
        )
        r_traced = svc.submit(asm, setup, cfg, capture_trace=True)
        r_plain = svc.submit(asm, setup, cfg)
        summary = svc.run_worker()
        assert summary["served"] == 2
    finally:
        os.environ.pop("BOOJUM_TPU_XPROF", None)
    assert "trace_dir" in r_traced.slo
    assert os.path.isdir(r_traced.slo["trace_dir"])
    assert "trace_dir" not in r_plain.slo
    by_id = {
        ln["request"]["id"]: ln
        for ln in report.load_reports(rpt) if "request" in ln
    }
    traced_line = by_id[r_traced.id]
    assert traced_line["trace"]["dir"] == r_traced.slo["trace_dir"]
    assert "trace" not in by_id[r_plain.id]
    assert report.validate_report(traced_line) == []
