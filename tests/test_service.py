"""Proving-as-a-service (ISSUE 6).

The tentpole added `boojum_tpu/service/`: a shape-bucketed admission
queue with priority lanes and bounded-queue backpressure, a
device-resident cache manager with byte-capped LRU eviction, a
scheduler picking shard-parallel vs proof-parallel placement per
request, and a worker loop emitting per-request SLO records through the
flight recorder. These tests pin the acceptance criteria on the virtual
8-device CPU mesh (conftest forces xla_force_host_platform_device_count):

- a MIXED batch — two geometries, both placements, a priority-lane job —
  drained through the service produces proof bytes AND digest-checkpoint
  streams bit-identical to sequential direct `prove()` per request;
- cache-manager hit/eviction accounting fires (service.cache.* in the
  request lines, LRU eviction at the byte cap);
- backpressure: admission above the queue bound raises QueueFullError
  and counts service.queue.rejects;
- `prove_report.py --check` validates the per-request SLO records
  (rejecting records missing queue-latency/placement) and `--slo`
  summarizes p50/p95 queue latency + proofs/sec;
- the shape-bucket key is ONE shared helper: admission queue, precompile
  enumeration and compile-ledger tags can never disagree.
"""

import functools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from boojum_tpu.utils import report
from proving import (
    baseline,
    checkpoint_stream,
    fma_assembly,
    prove_recorded,
    small_parts,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _parts_b():
    """Geometry B: same gate set at 2^11 — a DIFFERENT shape bucket, which
    is the point: two buckets, and a trace at the forced shard threshold."""
    return small_parts(11)


@functools.lru_cache(maxsize=1)
def _e2e_runs(tmp_dir=None):
    """The acceptance run: direct sequential proves of both geometries,
    then the SAME requests as one mixed service batch — two shape
    buckets, both placements (B's 2^11 trace is at the forced shard
    threshold, A stays proof-parallel), a priority-lane job, a repeated
    same-setup job (the cache-hit path)."""
    import tempfile

    from boojum_tpu.service import ProvingService, ServiceConfig

    direct_a = baseline()
    direct_b = prove_recorded("direct", parts=_parts_b())

    rpt = tempfile.mktemp(suffix=".service.jsonl")
    # precompile="off": the tier-1 persistent cache already holds every
    # kernel these proves dispatch; the warm-variant seam has its own
    # stubbed test (test_variant_warmer_warms_dispatched_set)
    svc = ProvingService(
        ServiceConfig(
            precompile="off",
            report_path=rpt,
            shard_threshold_rows=1 << 11,
            cache_bytes=2 << 30,
        )
    )
    asm_a, setup_a, cfg_a = small_parts()
    asm_b, setup_b, cfg_b = _parts_b()
    reqs = {
        # two same-bucket batch jobs (second is the device-cache HIT)...
        "a1": svc.submit(asm_a, setup_a, cfg_a, tenant="t0"),
        "a2": svc.submit(asm_a, setup_a, cfg_a, tenant="t1"),
        # ...a heavy job placed shard-parallel across the mesh...
        "b1": svc.submit(asm_b, setup_b, cfg_b, priority="bulk"),
        # ...and an interactive-lane job admitted LAST but drained FIRST
        "ai": svc.submit(asm_a, setup_a, cfg_a, priority="interactive"),
    }
    summary = svc.run_worker()
    lines = report.load_reports(rpt)
    return {
        "direct": {"a": direct_a, "b": direct_b},
        "svc": svc,
        "summary": summary,
        "requests": reqs,
        "report_path": rpt,
        "lines": lines,
    }


# ---------------------------------------------------------------------------
# Shared shape-bucket key
# ---------------------------------------------------------------------------


def test_shape_bucket_key_is_shared(monkeypatch):
    """Same circuit STRUCTURE with different witness values -> same key;
    different trace length -> different key; the compile ledger's
    precompile entries carry the exact key the admission queue buckets
    on. (The full lower-sweep of the enumeration is test_precompile's
    job — here it is stubbed to one tiny kernel so only the ledger
    tagging seam is under test.)"""
    import importlib

    import jax.numpy as jnp

    # boojum_tpu.prover re-exports the precompile FUNCTION under the
    # module's name — resolve the module itself
    pc = importlib.import_module("boojum_tpu.prover.precompile")
    from boojum_tpu.prover.shape_key import bucket_key, shape_bucket
    from boojum_tpu.utils.profiling import CompileLedger

    asm_a, _setup, cfg = small_parts()
    asm_same_shape = fma_assembly(10, seed=5)
    assert bucket_key(asm_same_shape, cfg) == bucket_key(asm_a, cfg)
    asm_b, _sb, cfg_b = _parts_b()
    assert bucket_key(asm_b, cfg_b) != bucket_key(asm_a, cfg)

    sb = shape_bucket(asm_a, cfg)
    assert sb.trace_len == 1 << 10 and sb.lde_factor == 2
    assert sb.B_wit > 0 and sb.B_setup > 0 and sb.S > 0 and sb.B_q > 0
    # identity: cached per (assembly, config-fields)
    assert shape_bucket(asm_a, cfg) is sb

    probe = pc.KernelSpec(
        "probe", jax.jit(lambda x: x + 1),
        (jax.ShapeDtypeStruct((4,), jnp.uint64),),
    )
    monkeypatch.setattr(
        pc, "enumerate_kernels", lambda *a, **k: [probe]
    )
    led = CompileLedger()
    pc.precompile(asm_a, cfg, ledger=led, lower_only=True)
    assert [e.get("shape") for e in led.entries] == [sb.key]
    assert led.summary()["shapes"] == [sb.key]


# ---------------------------------------------------------------------------
# Admission queue
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, key, priority="batch"):
        self.bucket_key = key
        self.priority = priority
        self.admit_ts = None


def test_queue_priority_lanes_and_bucket_batching():
    from boojum_tpu.service import AdmissionQueue

    q = AdmissionQueue(capacity=16)
    b1, b2 = _FakeReq("shapeX"), _FakeReq("shapeY")
    b3, b4 = _FakeReq("shapeX"), _FakeReq("shapeX")
    i1 = _FakeReq("shapeY", priority="interactive")
    for r in (b1, b2, b3, i1, b4):
        q.submit(r)
    assert q.depth() == 5
    assert q.occupancy("shapeX") == 3
    assert q.bucket_depths() == {"shapeX": 3, "shapeY": 2}
    # interactive lane drains FIRST even though admitted fourth
    assert q.pop_batch() == [i1]
    # then the batch lane head's bucket gathers ALL its followers...
    assert q.pop_batch() == [b1, b3, b4]
    # ...limit caps a batch; FIFO otherwise
    q2 = AdmissionQueue(capacity=4)
    for r in (_FakeReq("z"), _FakeReq("z"), _FakeReq("z")):
        q2.submit(r)
    assert len(q2.pop_batch(limit=2)) == 2
    assert q.pop_batch() == [b2]
    assert q.pop_batch() == []
    with pytest.raises(ValueError, match="priority lane"):
        q.submit(_FakeReq("w", priority="urgent"))


def test_queue_backpressure_rejects_above_bound():
    from boojum_tpu.service import AdmissionQueue, QueueFullError
    from boojum_tpu.utils import metrics as _metrics

    q = AdmissionQueue(capacity=2)
    reg = _metrics.MetricsRegistry()
    prev = _metrics.install_registry(reg)
    try:
        q.submit(_FakeReq("s"))
        q.submit(_FakeReq("s"))
        with pytest.raises(QueueFullError, match="capacity"):
            q.submit(_FakeReq("s"))
        with pytest.raises(QueueFullError):
            q.submit(_FakeReq("t", priority="interactive"))
    finally:
        _metrics.install_registry(prev)
    assert q.rejects == 2
    assert q.depth() == 2
    assert reg.counters["service.queue.rejects"] == 2
    assert reg.gauges["service.queue.depth"] == 2
    # draining reopens admission
    assert len(q.pop_batch()) == 2
    q.submit(_FakeReq("s"))
    assert q.depth() == 1


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def test_scheduler_placement_decision():
    from jax.sharding import Mesh

    from boojum_tpu.service import (
        PROOF_PARALLEL,
        SHARD_PARALLEL,
        choose_placement,
    )

    mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), axis_names=("col", "row")
    )

    class B:
        trace_len = 1 << 10
        log_n = 10

    class Big:
        trace_len = 1 << 20
        log_n = 20

    # small trace, queued siblings -> proof-parallel, packable
    p = choose_placement(B, 3, mesh, max_inflight=4, threshold_rows=1 << 17)
    assert p.kind == PROOF_PARALLEL and p.mesh is None and p.pack == 3
    assert 0 < p.occupancy < 1
    # lone small trace -> still meshless (collectives cost > win)
    p = choose_placement(B, 1, mesh, threshold_rows=1 << 17)
    assert p.kind == PROOF_PARALLEL and p.pack == 1
    # big trace -> the whole mesh, regardless of occupancy
    p = choose_placement(Big, 5, mesh, threshold_rows=1 << 17)
    assert p.kind == SHARD_PARALLEL and p.mesh is mesh
    assert p.occupancy == 1.0
    # no mesh at all -> everything proof-parallel
    p = choose_placement(Big, 1, None, threshold_rows=1 << 17)
    assert p.kind == PROOF_PARALLEL
    # env-driven threshold (junk raises)
    os.environ["BOOJUM_TPU_SERVICE_SHARD_ROWS"] = "1024"
    try:
        p = choose_placement(B, 1, mesh)
        assert p.kind == SHARD_PARALLEL
    finally:
        del os.environ["BOOJUM_TPU_SERVICE_SHARD_ROWS"]


def test_variant_warmer_warms_dispatched_set(monkeypatch):
    """The scheduler warms EXACTLY the kernel-library variant the chosen
    placement dispatches — mesh_shape=None for proof-parallel, the mesh
    for shard-parallel — and only once per (bucket, placement)."""
    import importlib

    from jax.sharding import Mesh

    pc = importlib.import_module("boojum_tpu.prover.precompile")
    from boojum_tpu.service.scheduler import Placement, VariantWarmer

    calls = []
    monkeypatch.setattr(
        pc, "precompile",
        lambda asm, cfg, max_workers=8, ledger=None, lower_only=False,
        mesh_shape=None: calls.append((mesh_shape, lower_only)),
    )
    mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), axis_names=("col", "row")
    )
    asm, _setup, cfg = small_parts()
    from boojum_tpu.prover.shape_key import shape_bucket

    sb = shape_bucket(asm, cfg)
    w = VariantWarmer(mode="lower")
    pp = Placement("proof_parallel", None, total_devices=8)
    sp = Placement("shard_parallel", mesh, total_devices=8)
    assert w.warm(sb, asm, cfg, pp) is True
    assert w.warm(sb, asm, cfg, pp) is False  # deduped
    assert w.warm(sb, asm, cfg, sp) is True   # other placement: new warm
    assert calls == [(None, True), (mesh, True)]
    assert VariantWarmer(mode="off").warm(sb, asm, cfg, pp) is False
    with pytest.raises(ValueError, match="precompile mode"):
        VariantWarmer(mode="eager")


# ---------------------------------------------------------------------------
# Cache manager
# ---------------------------------------------------------------------------


class _FakeSetup:
    def __init__(self, nbytes):
        self._dev_cache = {
            "sigma": np.zeros(nbytes // 8, dtype=np.uint64)
        }


def test_cache_manager_lru_eviction_at_byte_cap():
    from boojum_tpu.service import DeviceCacheManager
    from boojum_tpu.utils import metrics as _metrics

    reg = _metrics.MetricsRegistry()
    prev = _metrics.install_registry(reg)
    try:
        mgr = DeviceCacheManager(capacity_bytes=1 << 20)  # 1 MiB cap
        s1, s2, s3 = (_FakeSetup(1 << 19) for _ in range(3))  # 512 KiB each
        a = type("A", (), {})()
        assert mgr.pin("k1", a, s1) is False  # miss
        assert mgr.pin("k1", a, s1) is True   # hit
        mgr.after_request()
        assert mgr.pin("k2", a, s2) is False
        mgr.after_request()
        assert mgr.stats()["evictions"] == 0  # 1 MiB exactly: at cap
        assert mgr.pin("k3", a, s3) is False
        mgr.after_request()  # 1.5 MiB > cap: evict LRU (s1)
        st = mgr.stats()
        assert st["evictions"] == 1
        assert st["evicted_bytes"] >= 1 << 19
        assert not s1._dev_cache  # residency actually released
        assert s2._dev_cache and s3._dev_cache
        # re-pinning the evicted setup is a MISS again
        assert mgr.pin("k1", a, s1) is False
    finally:
        _metrics.install_registry(prev)
    assert reg.counters["service.cache.hits"] == 1
    assert reg.counters["service.cache.misses"] == 4
    assert reg.counters["service.cache.evictions"] == 1
    assert reg.gauges["service.cache.evicted_bytes"] >= 1 << 19
    assert "service.cache.pinned_bytes" in reg.gauges


# ---------------------------------------------------------------------------
# E2E: the mixed batch acceptance run
# ---------------------------------------------------------------------------


def test_e2e_mixed_batch_bit_parity():
    """Acceptance: per request, proof bytes AND digest-checkpoint
    streams are bit-identical to sequential direct prove(), across BOTH
    placements."""
    runs = _e2e_runs()
    pa, ra = runs["direct"]["a"]
    pb, rb = runs["direct"]["b"]
    reqs = runs["requests"]
    assert runs["summary"]["failed"] == 0
    for name in ("a1", "a2", "ai"):
        assert reqs[name].result().to_json() == pa.to_json(), name
    assert reqs["b1"].result().to_json() == pb.to_json()

    by_id = {
        ln["request"]["id"]: ln
        for ln in runs["lines"]
        if "request" in ln
    }
    base_a = checkpoint_stream(ra)
    assert base_a
    for name in ("a1", "a2", "ai"):
        ln = by_id[reqs[name].id]
        assert checkpoint_stream(ln) == base_a, name
        assert ln["request"]["placement"] == "proof_parallel"
    ln_b = by_id[reqs["b1"].id]
    assert checkpoint_stream(ln_b) == checkpoint_stream(rb)
    assert ln_b["request"]["placement"] == "shard_parallel"
    # the shard-parallel prove really ran the mesh path: explicit
    # collectives billed to ici.* in ITS request line only
    assert ln_b["metrics"]["counters"].get("ici.all_to_alls", 0) > 0
    assert by_id[reqs["a1"].id]["metrics"]["counters"].get(
        "ici.all_to_alls", 0
    ) == 0
    # placements recorded in the service summary too
    assert runs["summary"]["placements"]["proof_parallel"] == 3
    assert runs["summary"]["placements"]["shard_parallel"] == 1


def test_e2e_priority_lane_drains_first():
    """The interactive job was admitted LAST but must be SERVED first
    (strict-priority lanes) — visible in the report line order."""
    runs = _e2e_runs()
    served_order = [
        ln["request"]["id"] for ln in runs["lines"] if "request" in ln
    ]
    assert served_order[0] == runs["requests"]["ai"].id
    # its queue latency is recorded and sane
    ln = runs["lines"][0]
    assert ln["request"]["queue_latency_s"] >= 0
    assert ln["request"]["priority"] == "interactive"


def test_e2e_cache_hits_fire():
    """Same-setup re-submissions hit the device-resident cache; the hit
    is charged to the request line's service.cache.* counters."""
    runs = _e2e_runs()
    st = runs["svc"].cache.stats()
    assert st["hits"] >= 2  # a2 and ai reuse a1's pinned setup
    assert st["misses"] >= 2  # a1 and b1
    assert st["pinned_bytes"] > 0
    by_id = {
        ln["request"]["id"]: ln for ln in runs["lines"] if "request" in ln
    }
    reqs = runs["requests"]
    a2 = by_id[reqs["a2"].id]
    assert a2["request"]["cache_hit"] is True
    assert a2["metrics"]["counters"]["service.cache.hits"] == 1
    a1_first = by_id[runs["lines"][0]["request"]["id"]]
    assert a1_first["request"]["cache_hit"] is False
    assert a1_first["metrics"]["counters"]["service.cache.misses"] == 1


def test_e2e_backpressure_at_service_bound():
    """Admission above the service queue bound rejects with
    QueueFullError (the backpressure contract) without disturbing
    admitted work."""
    from boojum_tpu.service import (
        ProvingService,
        QueueFullError,
        ServiceConfig,
    )

    asm, setup, cfg = small_parts()
    svc = ProvingService(
        ServiceConfig(precompile="off", queue_capacity=2, report_path=None)
    )
    r1 = svc.submit(asm, setup, cfg)
    r2 = svc.submit(asm, setup, cfg)
    with pytest.raises(QueueFullError):
        svc.submit(asm, setup, cfg)
    assert svc.queue.rejects == 1
    summary = svc.run_worker()
    assert summary["served"] == 2
    assert summary["queue"]["rejects"] == 1
    assert r1.result().to_json() == r2.result().to_json()


def test_e2e_report_check_and_slo():
    """The per-request SLO records pass the prove_report.py --check
    gate, mutilated records FAIL it, and --slo summarizes the batch."""
    runs = _e2e_runs()
    req_lines = [ln for ln in runs["lines"] if "request" in ln]
    assert len(req_lines) == 4
    for ln in req_lines:
        assert report.validate_report(ln) == [], ln["request"]["id"]
        r = ln["request"]
        assert r["prove_wall_s"] > 0
        assert r["proofs_per_sec"] > 0
        assert 0 < r["occupancy"] <= 1.0
        assert r["bucket"].startswith("n2^")

    import copy

    bad = copy.deepcopy(req_lines[0])
    del bad["request"]["queue_latency_s"]
    assert any(
        "queue_latency_s" in p for p in report.validate_report(bad)
    )
    bad2 = copy.deepcopy(req_lines[0])
    bad2["request"]["placement"] = "warp_speed"
    assert any("placement" in p for p in report.validate_report(bad2))
    bad3 = copy.deepcopy(req_lines[0])
    bad3["metrics"]["gauges"]["service.occupancy"] = -2.0
    assert any(
        "service.occupancy" in p for p in report.validate_report(bad3)
    )

    # the stdlib-only CLI agrees, end to end
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cli = os.path.join(root, "scripts", "prove_report.py")
    chk = subprocess.run(
        [sys.executable, cli, "--check", runs["report_path"]],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr
    slo = subprocess.run(
        [sys.executable, cli, "--slo", runs["report_path"]],
        capture_output=True, text=True, timeout=120,
    )
    assert slo.returncode == 0, slo.stdout + slo.stderr
    assert "queue latency p50=" in slo.stdout
    assert "proofs/sec" in slo.stdout

    summary = report.slo_summary(runs["lines"])
    assert summary["requests"] == 4 and summary["served"] == 4
    assert summary["queue_latency_p50_s"] >= 0
    assert summary["queue_latency_p95_s"] >= summary["queue_latency_p50_s"]
    assert summary["prove_wall_p50_s"] > 0
    assert summary["proofs_per_sec"] > 0
    assert summary["placements"] == {
        "proof_parallel": 3, "shard_parallel": 1
    }
    assert summary["priorities"]["interactive"] == 1
    assert summary["cache_hit_rate"] == 0.5


@pytest.mark.slow
def test_packed_proof_parallel_parity_with_recording(monkeypatch):
    """Satellite (ISSUE 9): max_inflight=2 packs same-bucket 2^10
    requests one-per-chip WITH flight recording ON — the combination
    the process-global collectors used to forbid. Proof bytes AND
    digest-checkpoint streams stay bit-identical to the sequential
    direct prove, each packed request writes its own well-formed report
    line, and a canary counter incremented inside request A's scoped
    context never appears on request B's line. Slow-marked: per-device
    placement re-traces the kernel library for the second chip (minutes
    on XLA:CPU), which tier-1's budget cannot absorb."""
    import tempfile

    from boojum_tpu.service import ProvingService, ServiceConfig
    from boojum_tpu.utils import metrics as _metrics

    runs = _e2e_runs()
    pa, ra = runs["direct"]["a"]
    asm, setup, cfg = small_parts()
    rpt = tempfile.mktemp(suffix=".packed.jsonl")
    svc = ProvingService(
        ServiceConfig(precompile="off", max_inflight=2, report_path=rpt)
    )
    # canary: each request counts a counter named after ITSELF inside
    # its (scoped) recording window — any cross-request registry bleed
    # shows up as the other request's canary on this line
    orig = ProvingService._run_request

    def with_canary(self, req, placement, packed=1, device=None):
        _metrics.count(f"canary.{req.id}")
        return orig(self, req, placement, packed=packed, device=device)

    monkeypatch.setattr(ProvingService, "_run_request", with_canary)
    rs = [svc.submit(asm, setup, cfg) for _ in range(2)]
    summary = svc.run_worker()
    assert summary["served"] == 2
    for r in rs:
        assert r.result().to_json() == pa.to_json()
        assert r.slo["packed"] == 2
    assert summary["placements"]["proof_parallel"] == 2

    lines = report.load_reports(rpt)
    req_lines = [ln for ln in lines if "request" in ln]
    assert len(req_lines) == 2
    base = checkpoint_stream(ra)
    assert base
    by_id = {ln["request"]["id"]: ln for ln in req_lines}
    for r in rs:
        other = next(o for o in rs if o is not r)
        ln = by_id[r.id]
        # bit-identical transcript: the packed request recorded the
        # SAME checkpoint stream as the sequential direct prove
        assert checkpoint_stream(ln) == base, r.id
        assert report.validate_report(ln) == [], r.id
        counters = ln["metrics"]["counters"]
        assert counters.get(f"canary.{r.id}") == 1
        assert f"canary.{other.id}" not in counters, "counter bled"
        # exactly ONE prove per line — not its neighbor's too
        assert counters.get("prover.proves") == 1
        assert ln["request"]["packed"] == 2

    # the stdlib CLI gate agrees the artifact is clean
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chk = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "prove_report.py"),
         "--check", rpt],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr


def test_service_metrics_plane_exports_prove_families():
    """/metrics in SERVICE mode must render the prove counter families
    even though each request records into a scoped registry that dies
    with its report line: start_telemetry adopts the process-global
    default slot with the service-lifetime accumulator, _serve_one
    folds each request's registry in, stop_telemetry releases."""
    from boojum_tpu.service import ProvingService, ServiceConfig
    from boojum_tpu.utils import metrics as _metrics

    svc = ProvingService(
        ServiceConfig(precompile="off", report_path=None)
    )
    prev = _metrics.install_registry(None)
    try:
        port = svc.start_telemetry(metrics_port=0)
        assert port
        assert _metrics.current_registry() is svc.prove_registry
        # stand-in for a request's scoped registry (torn down with the
        # line): the fold keeps its families for the plane's merge
        req_reg = _metrics.MetricsRegistry()
        req_reg.count("fri.folds", 4)
        req_reg.count("transfer.h2d_bytes", 123)
        req_reg.gauge_set("cost.total.efficiency", 0.5)
        svc.prove_registry.fold(req_reg)
        text = svc.metrics_plane.render_metrics()
        assert "boojum_tpu_fri_folds 4" in text
        assert "boojum_tpu_transfer_h2d_bytes 123" in text
        assert "boojum_tpu_cost_total_efficiency 0.5" in text
        # a second fold ADDS counters, last-writes gauges
        svc.prove_registry.fold(req_reg)
        text = svc.metrics_plane.render_metrics()
        assert "boojum_tpu_fri_folds 8" in text
        assert "boojum_tpu_cost_total_efficiency 0.5" in text
        svc.stop_telemetry()
        assert _metrics.current_registry() is None
    finally:
        svc.stop_telemetry()
        _metrics.install_registry(prev)
