"""Network admission plane (ISSUE 11).

The tentpole put an HTTP front door (`service/gateway.py`) over the
proving service — tenant bearer-token auth, idempotency-key replay,
429-with-Retry-After quotas charged from the flight-recorder records,
telemetry-driven load-shed, graceful drain and hot AOT reload — and
replaced the admission queue's intra-lane FIFO with deficit-round-robin
weighted fairness across tenants (`service/queue.py` + tenant.py).

Coverage here, cheapest first:

- DRR unit: a 3-tenant unequal-weight drain converges EXACTLY to the
  configured ratios with no proving; lanes stay strict-priority above
  the tenant rings; big batches borrow deficit and are paid back.
- QuotaLedger window math with injected clocks (no sleeping).
- The `tenant` report record's --check rules and the per-tenant --slo.
- Socket-free gateway routing (Gateway.handle): auth, specs, tickets,
  idempotent replay, 429 + reject lines, shed, spool, drain, reload.
- @gateway-marked socket tests (excludable via -m 'not gateway'):
  the http_metrics 500-with-body + service.http.errors satellite, and
  the E2E acceptance run — two tenants over real loopback HTTP, proof
  bytes + Fiat-Shamir checkpoint streams bit-identical to direct
  prove(), replay served from the ledger without a second prove, one
  tenant 429-throttled while the other completes, drain -> artifact
  passes prove_report.py --check.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import pytest

from boojum_tpu.utils import report
from proving import baseline, checkpoint_stream, small_parts

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# DRR fairness (unit, no proving)
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, tenant, key="k", priority="batch"):
        self.tenant = tenant
        self.bucket_key = key
        self.priority = priority
        self.admit_ts = None


def test_queue_drr_fairness_converges_to_weights():
    """Satellite acceptance: 3 backlogged tenants at weights 3:2:1
    drain in EXACTLY those ratios (unit-cost DRR, quantum = weight),
    and nobody starves — every tenant is served within each
    weight-sum-sized window."""
    from boojum_tpu.service import AdmissionQueue

    q = AdmissionQueue(
        capacity=256, weights={"a": 3.0, "b": 2.0, "c": 1.0}
    )
    for i in range(60):
        for t in ("a", "b", "c"):
            # distinct buckets: one request per pop even without limit
            q.submit(_FakeReq(t, key=f"{t}{i}"))
    order = []
    for _ in range(60):
        (r,) = q.pop_batch(limit=1)
        order.append(r.tenant)
    counts = {t: order.count(t) for t in ("a", "b", "c")}
    assert counts == {"a": 30, "b": 20, "c": 10}
    # no starvation: every weight-sum window serves every tenant
    for i in range(0, 60, 6):
        assert set(order[i:i + 6]) == {"a", "b", "c"}
    assert q.served == counts
    # a weight must be positive — a zero-quantum ring would never turn
    with pytest.raises(ValueError, match="weight"):
        q.set_weight("d", 0)


def test_queue_drr_borrowing_and_lane_priority():
    """A tenant draining a big same-bucket batch borrows against its
    deficit and is skipped for proportionally many rounds; strict lane
    priority still trumps every tenant weight."""
    from boojum_tpu.service import AdmissionQueue

    q = AdmissionQueue(capacity=64, weights={"heavy": 1.0, "light": 1.0})
    for _ in range(6):
        q.submit(_FakeReq("heavy", key="same"))
    for i in range(3):
        q.submit(_FakeReq("light", key=f"l{i}"))
    first = q.pop_batch()  # heavy joined first: its whole bucket drains
    assert [r.tenant for r in first] == ["heavy"] * 6
    # heavy borrowed 6 units at weight 1: light's 3 singles all pre-empt
    out = [q.pop_batch(limit=1)[0].tenant for _ in range(3)]
    assert out == ["light"] * 3
    # an INTERACTIVE job from the most indebted tenant still wins: lanes
    # are strict-priority above the per-lane tenant rings
    q.submit(_FakeReq("heavy", key="same"))
    q.submit(_FakeReq("light", key="lx"))
    q.submit(_FakeReq("heavy", key="now", priority="interactive"))
    assert q.pop_batch(limit=1)[0].priority == "interactive"
    # introspection aggregates across tenants
    assert q.depth() == 2
    assert q.tenant_depths() == {"heavy": 1, "light": 1}
    assert q.lane_depths()["batch"] == 2


def test_queue_drr_debt_survives_emptied_backlog():
    """A bursty tenant that drains a big batch and RESUBMITS after its
    backlog emptied still owes its debt while the lane stays contended
    — resubmit-after-drain must not evade the weight ratios. Only when
    the whole lane goes idle does the fairness state reset."""
    from boojum_tpu.service import AdmissionQueue

    q = AdmissionQueue(capacity=256)
    for _ in range(10):
        q.submit(_FakeReq("bursty", key="same"))
    for i in range(12):
        q.submit(_FakeReq("steady", key=f"s{i}"))
    assert len(q.pop_batch()) == 10  # bursty: whole batch, debt -9
    # bursty rejoins immediately; steady (still backlogged) must now be
    # served ~9 ahead before bursty sees service again
    for _ in range(10):
        q.submit(_FakeReq("bursty", key="same"))
    pre = []
    while True:
        (r,) = q.pop_batch(limit=1)
        if r.tenant == "bursty":
            break
        pre.append(r.tenant)
    assert len(pre) >= 9, f"bursty evaded its debt after {len(pre)} pops"
    # lane going fully idle clears the debts: a later epoch starts fair
    while q.pop_batch(limit=None):
        pass
    assert q.depth() == 0
    q.submit(_FakeReq("bursty", key="fresh"))
    q.submit(_FakeReq("steady", key="fresh2"))
    assert q.pop_batch(limit=1)[0].tenant == "bursty"  # no stale debt


# ---------------------------------------------------------------------------
# Quota ledger (unit, injected clock)
# ---------------------------------------------------------------------------


def test_quota_ledger_window_math():
    from boojum_tpu.service import QuotaLedger, TenantSpec

    led = QuotaLedger(
        [
            TenantSpec("metered", "tok-m", quota_bytes=1000,
                       quota_compute_s=10.0),
            TenantSpec("free", "tok-f"),
        ],
        window_s=60.0,
    )
    ok, ra = led.admit("metered", now=0.0)
    assert ok and ra == 0.0
    rec = led.charge("metered", 700, 2.0, now=1.0)
    assert rec["charged_bytes"] == 700
    assert rec["window_used_bytes"] == 700
    ok, _ = led.admit("metered", now=2.0)
    assert ok  # under both axes
    led.charge("metered", 400, 1.0, now=3.0)  # bytes now 1100 >= 1000
    ok, ra = led.admit("metered", now=10.0)
    assert not ok and abs(ra - 50.0) < 1e-9  # window resets at t=60
    assert led.throttled["metered"] == 1
    # the window turning over re-admits
    ok, _ = led.admit("metered", now=61.0)
    assert ok
    # compute axis throttles independently
    led.charge("metered", 0, 11.0, now=62.0)
    ok, _ = led.admit("metered", now=63.0)
    assert not ok
    # spec-less and unlimited tenants never throttle, but are metered
    assert led.admit("free", now=0.0)[0]
    assert led.admit("stranger", now=0.0)[0]
    led.charge("stranger", 5, 0.1, now=1.0)
    snap = led.snapshot()
    assert snap["stranger.used_bytes"] == 5.0
    assert snap["metered.throttled"] == 2.0
    with pytest.raises(ValueError, match="window_s"):
        QuotaLedger([], window_s=0)


def test_parse_tenant_specs_forms(tmp_path):
    from boojum_tpu.service import parse_tenant_specs

    specs = parse_tenant_specs("a:ta:3,b:tb:1:1000:5.5,root:tr:2:admin")
    assert [(s.id, s.weight) for s in specs] == [
        ("a", 3.0), ("b", 1.0), ("root", 2.0)
    ]
    assert specs[1].quota_bytes == 1000
    assert specs[1].quota_compute_s == 5.5
    assert specs[2].admin and not specs[0].admin
    inline = parse_tenant_specs(
        '[{"id": "x", "token": "tx", "weight": 4, "quota_bytes": 9}]'
    )
    assert inline[0].weight == 4.0 and inline[0].quota_bytes == 9
    p = tmp_path / "tenants.json"
    p.write_text('[{"id": "y", "token": "ty", "admin": true}]')
    from_file = parse_tenant_specs(f"@{p}")
    assert from_file[0].id == "y" and from_file[0].admin
    assert parse_tenant_specs("") == []
    with pytest.raises(ValueError, match="id:token"):
        parse_tenant_specs("lonely")
    # a tenant whose shared secret is literally "admin" keeps it: the
    # flag only strips PAST the mandatory id:token prefix
    (ops,) = parse_tenant_specs("ops:admin")
    assert ops.token == "admin" and not ops.admin


# ---------------------------------------------------------------------------
# Report record: --check rules + per-tenant --slo
# ---------------------------------------------------------------------------


def _line(**extra):
    base = {
        "kind": report.REPORT_KIND, "schema": report.REPORT_SCHEMA,
        "label": "t", "wall_s": 0.1, "spans": [],
        "metrics": {"counters": {}}, "checkpoints": [],
        # schema 4: gateway lines without a trace context fail --check
        "trace_ctx": {"trace_id": "ef" * 16},
    }
    base.update(extra)
    return base


def _req_record(tenant="a", **extra):
    rec = {
        "id": "gw-000001", "tenant": tenant, "bucket": "b",
        "placement": "proof_parallel", "queue_latency_s": 0.01,
        "prove_wall_s": 0.5, "gateway": True,
    }
    rec.update(extra)
    return rec


def test_check_validates_tenant_record():
    good = _line(
        request=_req_record(),
        tenant={"id": "a", "charged_bytes": 10, "charged_compute_s": 0.5,
                "window_used_bytes": 10, "window_used_compute_s": 0.5},
    )
    assert report.validate_report(good) == []
    # gateway-admitted line MISSING the tenant record fails
    naked = _line(request=_req_record())
    assert any(
        "missing its tenant record" in p
        for p in report.validate_report(naked)
    )
    # ...but a plain in-process service line (no gateway flag) is fine
    local = _line(request={k: v for k, v in _req_record().items()
                           if k != "gateway"})
    assert report.validate_report(local) == []
    # negative charges fail
    neg = _line(request=_req_record(),
                tenant={"id": "a", "charged_bytes": -3})
    assert any("charged_bytes" in p for p in report.validate_report(neg))
    # a rejection line never proves
    rej = _line(tenant={"id": "b", "rejected": 429, "reason": "throttled",
                        "retry_after_s": 12.5})
    assert report.validate_report(rej) == []
    lying = _line(
        tenant={"id": "b", "rejected": 429, "reason": "throttled"},
        request=_req_record(tenant="b"),
    )
    assert any(
        "must never prove" in p for p in report.validate_report(lying)
    )
    # malformed shapes are named
    assert any(
        "tenant record malformed" in p
        for p in report.validate_report(_line(tenant=[1, 2]))
    )
    assert any(
        "id invalid" in p
        for p in report.validate_report(_line(tenant={"id": ""}))
    )


def test_slo_summarizes_tenants_and_shed_counts():
    lines = [
        _line(request=_req_record(tenant="a", prove_wall_s=1.0),
              tenant={"id": "a", "charged_bytes": 1,
                      "charged_compute_s": 1.0}),
        _line(request=_req_record(tenant="a", prove_wall_s=3.0,
                                  queue_latency_s=0.2),
              tenant={"id": "a", "charged_bytes": 1,
                      "charged_compute_s": 3.0}),
        _line(request=_req_record(tenant="b", prove_wall_s=2.0),
              tenant={"id": "b", "charged_bytes": 1,
                      "charged_compute_s": 2.0}),
        _line(tenant={"id": "b", "rejected": 429, "reason": "throttled",
                      "retry_after_s": 5.0}),
        _line(tenant={"id": "c", "rejected": 503, "reason": "shed"}),
    ]
    s = report.slo_summary(lines)
    assert s["requests"] == 3
    assert s["rejected"] == {"throttled": 1, "shed": 1}
    assert s["tenants"]["a"]["requests"] == 2
    assert s["tenants"]["a"]["prove_wall_p95_s"] == 3.0
    assert s["tenants"]["b"] == {
        "requests": 1, "rejected": 1,
        "queue_latency_p95_s": 0.01, "prove_wall_p95_s": 2.0,
    }
    assert s["tenants"]["c"]["requests"] == 0
    assert s["tenants"]["c"]["rejected"] == 1
    text = report.render_slo(s)
    assert "throttled(429)=1" in text and "shed=1" in text
    assert "tenant a" in text and "tenant c" in text


# ---------------------------------------------------------------------------
# Socket-free gateway routing
# ---------------------------------------------------------------------------


class _FakeProof:
    def __init__(self, payload):
        self._payload = payload

    def to_json(self):
        return json.dumps({"proof": self._payload})


def _fake_run_request(self, req, placement, packed=1, device=None):
    """Stands in for ProvingService._run_request: stamps a well-formed
    SLO record + a deterministic fake proof, no proving."""
    req.slo = {
        "schema": 1, "id": req.id, "tenant": req.tenant,
        "priority": req.priority, "bucket": req.bucket_key,
        "placement": placement.kind, "packed": packed,
        "occupancy": 0.125, "queue_latency_s": 0.001,
        "cache_hit": False, "prove_wall_s": 0.25,
    }
    if req.gateway:
        req.slo["gateway"] = True
    if req.trace:
        req.slo["trace_id"] = req.trace["trace_id"]
    req.proof = _FakeProof(req.bucket_key)
    with self._stats_lock:
        self.stats["served"] += 1
    req._done.set()
    return 1


@pytest.fixture
def stub_gateway(tmp_path, monkeypatch):
    """A Gateway over a ProvingService whose prove is stubbed out —
    routing, quotas, idempotency and drain logic without sockets or
    XLA. The worker loop is NOT started; tests drain explicitly."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from boojum_tpu.service import (
        Gateway,
        GatewayConfig,
        ProvingService,
        ServiceConfig,
        TenantSpec,
    )

    monkeypatch.setattr(
        ProvingService, "_run_request", _fake_run_request
    )
    rpt = str(tmp_path / "gw.jsonl")
    svc = ProvingService(
        ServiceConfig(precompile="off", report_path=rpt)
    )
    cfg = GatewayConfig(
        tenants=[
            TenantSpec("alice", "tok-alice", weight=2.0),
            TenantSpec("bob", "tok-bob", quota_bytes=1),
            TenantSpec("ops", "tok-ops", admin=True),
        ],
        spool_dir=str(tmp_path / "spool"),
        shed_mem_bytes=None,
    )
    gw = Gateway(svc, cfg, resolver=lambda spec: small_parts())
    return gw, svc, rpt


def _post(gw, path, token=None, body=b"{}", idem=None):
    headers = {}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    if idem:
        headers["Idempotency-Key"] = idem
    out = gw.handle("POST", path, headers, body)
    code, payload = out[0], json.loads(out[1])
    return code, payload, (out[3] if len(out) > 3 else {})


def _get(gw, path, token=None):
    headers = {"Authorization": f"Bearer {token}"} if token else {}
    out = gw.handle("GET", path, headers, b"")
    return out[0], out[1], out[2]


def test_gateway_auth_and_spec_validation(stub_gateway):
    gw, svc, _rpt = stub_gateway
    assert _post(gw, "/prove")[0] == 401
    assert _post(gw, "/prove", token="wrong")[0] == 401
    code, payload, _ = _post(gw, "/prove", token="tok-alice",
                             body=b"not json")
    assert code == 400 and "bad job spec" in payload["error"]
    code, payload, _ = _post(
        gw, "/prove", token="tok-alice",
        body=json.dumps({"priority": "warp"}).encode(),
    )
    assert code == 400 and "priority" in payload["error"]
    assert _post(gw, "/nope", token="tok-alice")[0] == 404
    assert gw.handle("PUT", "/prove", {}, b"")[0] == 405
    # admin verbs refuse non-admin tenants
    assert _post(gw, "/admin/drain", token="tok-alice")[0] == 403
    reg = svc.sampler.registry.to_dict()["counters"]
    assert reg["service.gateway.auth_failures"] >= 2


def test_gateway_ticket_status_proof_and_isolation(stub_gateway):
    gw, svc, rpt = stub_gateway
    code, ticket, _ = _post(gw, "/prove", token="tok-alice")
    assert code == 202 and ticket["status"] == "queued"
    job = ticket["job"]
    # queued: proof download is a 409, status visible to the owner only
    assert _get(gw, f"/jobs/{job}/proof", token="tok-alice")[0] == 409
    assert _get(gw, f"/jobs/{job}", token="tok-bob")[0] == 404
    assert svc.run_worker()["served"] == 1  # drain (stubbed prove)
    code, body, _ = _get(gw, f"/jobs/{job}", token="tok-alice")
    status = json.loads(body)
    assert code == 200 and status["status"] == "done"
    assert status["request"]["gateway"] is True
    code, proof_bytes, ctype = _get(
        gw, f"/jobs/{job}/proof", token="tok-alice"
    )
    assert code == 200 and ctype == "application/json"
    assert json.loads(proof_bytes)["proof"]
    # the admin tenant sees foreign jobs; strangers see 404
    assert _get(gw, f"/jobs/{job}", token="tok-ops")[0] == 200
    assert _get(gw, "/jobs/gw-999999", token="tok-alice")[0] == 404
    # the composed read plane answers under the same router
    assert _get(gw, "/healthz")[0] == 200
    assert b"boojum_tpu_" in _get(gw, "/metrics")[1]
    # the request line carries the tenant record and passes --check
    lines = report.load_reports(rpt)
    (req_line,) = [ln for ln in lines if "request" in ln]
    assert req_line["tenant"]["id"] == "alice"
    assert req_line["tenant"]["charged_bytes"] > 0
    assert report.validate_report(req_line) == []


def test_gateway_idempotent_replay_never_reproves(stub_gateway):
    gw, svc, _rpt = stub_gateway
    code, t1, _ = _post(gw, "/prove", token="tok-alice", idem="key-1")
    assert code == 202
    svc.run_worker()
    served = svc.summary()["served"]
    code, t2, _ = _post(gw, "/prove", token="tok-alice", idem="key-1")
    assert code == 200 and t2["replay"] is True
    assert t2["job"] == t1["job"] and t2["status"] == "done"
    assert svc.summary()["served"] == served  # no second prove
    assert svc.queue.depth() == 0
    # proof bytes identical across replayed downloads
    p1 = _get(gw, f"/jobs/{t1['job']}/proof", token="tok-alice")[1]
    p2 = _get(gw, f"/jobs/{t2['job']}/proof", token="tok-alice")[1]
    assert p1 == p2
    # same key, DIFFERENT tenant: a fresh job (keys are tenant-scoped)
    code, t3, _ = _post(gw, "/prove", token="tok-ops", idem="key-1")
    assert code == 202 and t3["job"] != t1["job"]
    counters = svc.sampler.registry.to_dict()["counters"]
    assert counters["service.gateway.replays"] == 1


def test_gateway_idempotency_reserved_before_serving(stub_gateway):
    """The (tenant, key) reservation happens atomically with the check:
    a duplicate POST arriving while the original is still QUEUED gets
    the original ticket (status queued) — never a second job. And a
    REJECTED admission rolls its reservation back so the key can be
    retried."""
    gw, svc, _rpt = stub_gateway
    code, t1, _ = _post(gw, "/prove", token="tok-alice", idem="dup")
    assert code == 202 and t1["status"] == "queued"
    # duplicate while the original is in flight: replay of the SAME
    # ticket, still queued, nothing new enters the service queue
    code, t2, _ = _post(gw, "/prove", token="tok-alice", idem="dup")
    assert code == 200 and t2["replay"] is True
    assert t2["job"] == t1["job"] and t2["status"] == "queued"
    assert svc.queue.depth() == 1
    assert svc.run_worker()["served"] == 1
    # a rejected admission releases its key: bad spec now, good later
    code, _p, _ = _post(gw, "/prove", token="tok-alice",
                        body=b"not json", idem="retry-me")
    assert code == 400
    code, t3, _ = _post(gw, "/prove", token="tok-alice", idem="retry-me")
    assert code == 202  # the key was NOT burnt by the 400
    svc.run_worker()
    # a duplicate landing while the winner is BETWEEN reservation and
    # admission gets 409-retry, never a ticket that might evaporate
    placeholder_id = None
    with gw._lock:
        placeholder_id = f"gw-{next(gw._ids):06d}"
        from boojum_tpu.service import GatewayJob

        gw._jobs[placeholder_id] = GatewayJob(
            id=placeholder_id, tenant="alice", spec={},
            idem_key="racing", created_ts=0.0,
        )
        gw._idem[("alice", "racing")] = placeholder_id
    code, payload, headers = _post(gw, "/prove", token="tok-alice",
                                   idem="racing")
    assert code == 409 and headers["Retry-After"]
    gw._unreserve(gw._jobs[placeholder_id])


def test_gateway_job_ledger_is_bounded(stub_gateway):
    """Finished tickets (and their idempotency keys) are evicted above
    max_jobs, oldest first; live tickets are never evicted."""
    gw, svc, _rpt = stub_gateway
    gw.config.max_jobs = 3
    ids = []
    for i in range(3):
        code, t, _ = _post(gw, "/prove", token="tok-alice",
                           idem=f"k{i}")
        assert code == 202
        ids.append(t["job"])
        svc.run_worker()  # finish each before the next admission
    code, t, _ = _post(gw, "/prove", token="tok-alice")
    assert code == 202
    ids.append(t["job"])
    # the oldest finished ticket fell off the ledger...
    assert ids[0] not in gw._jobs
    assert _get(gw, f"/jobs/{ids[0]}", token="tok-alice")[0] == 404
    assert set(ids[1:]) <= set(gw._jobs)
    # ...and its idempotency key with it: the key is reusable
    code, t_new, _ = _post(gw, "/prove", token="tok-alice", idem="k0")
    assert code == 202 and t_new["job"] != ids[0]
    svc.run_worker()


def test_gateway_quota_429_with_retry_after(stub_gateway):
    gw, svc, rpt = stub_gateway
    code, ticket, _ = _post(gw, "/prove", token="tok-bob")
    assert code == 202
    svc.run_worker()
    # bob's 1-byte budget is burnt by the first request's charge
    assert svc.quota.snapshot()["bob.used_bytes"] > 0
    code, payload, headers = _post(gw, "/prove", token="tok-bob")
    assert code == 429
    assert payload["retry_after_s"] > 0
    assert int(headers["Retry-After"]) >= 1
    # alice is untouched by bob's throttle
    assert _post(gw, "/prove", token="tok-alice")[0] == 202
    svc.run_worker()
    # the rejection rode the artifact and the whole file still checks
    lines = report.load_reports(rpt)
    rejects = [
        ln for ln in lines
        if (ln.get("tenant") or {}).get("rejected")
    ]
    assert len(rejects) == 1
    assert rejects[0]["tenant"]["id"] == "bob"
    assert rejects[0]["tenant"]["reason"] == "throttled"
    assert "request" not in rejects[0]
    for ln in lines:
        assert report.validate_report(ln) == [], ln.get("label")
    s = report.slo_summary(lines)
    assert s["rejected"]["throttled"] == 1
    assert s["tenants"]["bob"]["rejected"] == 1


def test_gateway_load_shed_bulk_only(stub_gateway):
    gw, svc, rpt = stub_gateway
    gw.config.shed_queue_depth = 1
    assert _post(gw, "/prove", token="tok-alice")[0] == 202  # depth -> 1
    code, payload, headers = _post(
        gw, "/prove", token="tok-alice",
        body=json.dumps({"priority": "bulk"}).encode(),
    )
    assert code == 503 and "shed" in payload["error"]
    assert headers["Retry-After"]
    # non-bulk lanes are exempt: load-shed protects latency work
    assert _post(gw, "/prove", token="tok-alice")[0] == 202
    counters = svc.sampler.registry.to_dict()["counters"]
    assert counters["service.gateway.shed"] == 1
    svc.run_worker()
    shed_lines = [
        ln for ln in report.load_reports(rpt)
        if (ln.get("tenant") or {}).get("reason") == "shed"
    ]
    assert len(shed_lines) == 1
    assert report.validate_report(shed_lines[0]) == []


def test_gateway_spools_bulk_jobs_for_the_fleet(stub_gateway):
    gw, svc, _rpt = stub_gateway
    from boojum_tpu.service import read_spool

    spec = {"priority": "bulk", "seed": 7}
    code, ticket, _ = _post(
        gw, "/prove", token="tok-alice", body=json.dumps(spec).encode()
    )
    assert code == 202 and ticket["status"] == "spooled"
    ((fname, spooled),) = read_spool(gw.config.spool_dir)
    assert fname == f"{ticket['job']}.json"
    assert spooled["job"] == ticket["job"]
    assert spooled["tenant"] == "alice"
    assert spooled["seed"] == 7 and spooled["priority"] == "bulk"
    # nothing entered the local queue: the fleet owns this job...
    assert svc.queue.depth() == 0
    # ...but the spool-file bytes WERE charged to alice's byte quota at
    # admission (the fleet owns only the compute axis)
    assert svc.quota.snapshot()["alice.used_bytes"] > 0
    # bob's 1-byte budget: his second spooled job throttles — spool
    # mode cannot bypass the quota
    assert _post(gw, "/prove", token="tok-bob",
                 body=json.dumps(spec).encode())[0] == 202
    assert _post(gw, "/prove", token="tok-bob",
                 body=json.dumps(spec).encode())[0] == 429
    # ticket remains queryable; corrupt spool entries are skipped
    assert _get(gw, f"/jobs/{ticket['job']}", token="tok-alice")[0] == 200
    with open(os.path.join(gw.config.spool_dir, "junk.json"), "w") as f:
        f.write("{truncated")
    assert len(read_spool(gw.config.spool_dir)) == 2


def test_gateway_admin_token_and_denial_counters(stub_gateway):
    """The standalone admin_token (no tenant row) can read any ticket
    AND call admin verbs; a known tenant probing admin verbs counts on
    admin_denied, not on the bad-token auth_failures alarm."""
    gw, svc, _rpt = stub_gateway
    gw.config.admin_token = "op5"
    code, ticket, _ = _post(gw, "/prove", token="tok-alice")
    assert code == 202
    svc.run_worker()
    assert _get(gw, f"/jobs/{ticket['job']}", token="op5")[0] == 200
    code, payload, _ = _post(gw, "/admin/reload-artifacts", token="op5")
    assert code == 200 and payload["reloaded"] is True
    before = dict(svc.sampler.registry.to_dict()["counters"])
    assert _post(gw, "/admin/drain", token="tok-alice")[0] == 403
    after = svc.sampler.registry.to_dict()["counters"]
    assert after["service.gateway.admin_denied"] == 1
    assert after.get("service.gateway.auth_failures", 0) == before.get(
        "service.gateway.auth_failures", 0
    )


def test_gateway_wait_jobs_api(stub_gateway):
    """The public harness surface: wait_jobs blocks for local jobs and
    refuses spooled ones; job() looks tickets up."""
    gw, svc, _rpt = stub_gateway
    code, t1, _ = _post(gw, "/prove", token="tok-alice")
    assert code == 202
    svc.run_worker()
    (req,) = gw.wait_jobs([t1["job"]], timeout_s=5.0)
    assert req.done() and gw.job(t1["job"]).status() == "done"
    with pytest.raises(KeyError):
        gw.wait_jobs(["gw-999999"])
    code, ts, _ = _post(
        gw, "/prove", token="tok-alice",
        body=json.dumps({"priority": "bulk"}).encode(),
    )
    assert code == 202
    with pytest.raises(ValueError, match="spooled"):
        gw.wait_jobs([ts["job"]])


def test_gateway_drain_and_reload_verbs(stub_gateway):
    gw, svc, rpt = stub_gateway
    code, ticket, _ = _post(gw, "/prove", token="tok-alice")
    assert code == 202
    svc.run_worker()
    # hot AOT reload: warm keys forgotten, queue untouched
    svc.warmer._warmed.add(("bucket", "proof_parallel"))
    code, payload, _ = _post(gw, "/admin/reload-artifacts",
                             token="tok-ops")
    assert code == 200 and payload["warm_keys_cleared"] == 1
    assert svc.warmer._warmed == set()
    # graceful drain: finishes (nothing in flight), flags drained,
    # then refuses new admissions 503 while replays still answer
    code, payload, _ = _post(gw, "/admin/drain", token="tok-ops")
    assert code == 200 and payload["drained"] is True
    assert gw.drained.is_set()
    assert payload["summary"]["served"] == 1
    code, payload, headers = _post(gw, "/prove", token="tok-alice")
    assert code == 503 and "draining" in payload["error"]
    assert headers["Retry-After"]
    counters = svc.sampler.registry.to_dict()["counters"]
    assert counters["service.gateway.drains"] == 1


def test_gateway_trace_propagation(stub_gateway):
    """ISSUE 17 tentpole: the gateway mints ONE trace at POST /prove
    (honoring an inbound X-Boojum-Trace header) and that id rides the
    ticket, the response header, the request line's trace_ctx, the
    queue.wait span, the 429 rejection line and the spool file — so a
    single request's whole story stitches under one trace_id."""
    gw, svc, rpt = stub_gateway
    from boojum_tpu.service import read_spool
    from boojum_tpu.utils import spans as spans_mod

    tid = "ab" * 16
    psid = "cd" * 8
    traced_headers = {
        "Authorization": "Bearer tok-alice",
        "X-Boojum-Trace": f"{tid}:{psid}",
    }
    out = gw.handle("POST", "/prove", traced_headers, b"{}")
    assert out[0] == 202
    ticket = json.loads(out[1])
    assert ticket["trace"] == tid
    assert out[3]["X-Boojum-Trace"] == tid
    # a header-less admission mints a fresh, distinct, well-formed id
    code, t2, h2 = _post(gw, "/prove", token="tok-alice")
    assert code == 202
    assert spans_mod.valid_trace_id(t2["trace"]) and t2["trace"] != tid
    assert h2["X-Boojum-Trace"] == t2["trace"]
    svc.run_worker()

    lines = report.load_reports(rpt)
    req_lines = [ln for ln in lines if "request" in ln]
    assert len(req_lines) == 2
    by_tid = {ln["trace_ctx"]["trace_id"]: ln for ln in req_lines}
    assert set(by_tid) == {tid, t2["trace"]}
    for ln in req_lines:
        # admission queueing is a REAL (backdated) span: queue.wait
        # roots the line's tree, chained to the gateway's admit span
        (qw,) = [sp for sp in ln["spans"] if sp["name"] == "queue.wait"]
        assert report.SPAN_ID_RE.match(qw["span_id"])
        assert qw["trace_id"] == ln["trace_ctx"]["trace_id"]
        assert qw["parent_span_id"] == ln["trace_ctx"]["parent_span_id"]
        assert qw["attrs"]["request"] == ln["request"]["id"]
        assert ln["request"]["trace_id"] == ln["trace_ctx"]["trace_id"]

    # bob's second request 429s AFTER his quota charge lands; the
    # rejection line still tells the trace's story
    assert _post(gw, "/prove", token="tok-bob")[0] == 202
    svc.run_worker()
    out = gw.handle(
        "POST", "/prove",
        {"Authorization": "Bearer tok-bob", "X-Boojum-Trace": tid}, b"{}",
    )
    assert out[0] == 429

    # a spooled bulk job: the trace context rides the spool file for
    # the fleet AND the admit span materializes in a gateway line
    out = gw.handle(
        "POST", "/prove", traced_headers,
        json.dumps({"priority": "bulk"}).encode(),
    )
    assert out[0] == 202 and json.loads(out[1])["status"] == "spooled"
    ((_fname, spooled),) = read_spool(gw.config.spool_dir)
    assert spooled["trace"]["trace_id"] == tid

    lines = report.load_reports(rpt)
    rejects = [
        ln for ln in lines if (ln.get("tenant") or {}).get("rejected")
    ]
    assert len(rejects) == 1
    assert rejects[0]["trace_ctx"]["trace_id"] == tid
    (spool_line,) = [
        ln for ln in lines if ln.get("label") == "gateway:spool"
    ]
    (admit,) = spool_line["spans"]
    assert admit["name"] == "gateway.admit"
    assert admit["trace_id"] == tid
    assert admit["parent_span_id"] == psid  # inbound header's parent
    (sw,) = admit["children"]
    assert sw["name"] == "gateway.spool_write"
    assert sw["parent_span_id"] == admit["span_id"]
    assert spool_line["trace_ctx"] == {
        "trace_id": tid, "parent_span_id": psid,
    }
    # every line validates and NO span_id repeats across the artifact
    for ln in lines:
        assert report.validate_report(ln) == [], ln.get("label")
    assert report.validate_artifact(lines) == []


def test_gateway_line_trace_rules_fail_closed():
    """--check's trace rules: a schema-4 gateway line WITHOUT trace_ctx
    fails, and two report lines sharing a span_id fail the artifact."""
    base = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "unix_ts": 1.0,
        "wall_s": 0.0,
        "spans": [],
        "metrics": {"counters": {}, "gauges": {}},
        "checkpoints": [],
    }
    naked = dict(base, label="gateway:throttled",
                 tenant={"id": "t", "rejected": 429, "reason": "throttled"})
    assert any(
        "missing trace_ctx" in p for p in report.validate_report(naked)
    )
    assert report.validate_report(
        dict(naked, trace_ctx={"trace_id": "ab" * 16})
    ) == []
    sp = {
        "name": "s", "start_s": 0.0, "wall_s": 0.0,
        "span_id": "11" * 8, "children": [],
    }
    a = dict(base, label="a", spans=[dict(sp)])
    b = dict(base, label="b", spans=[dict(sp)])
    assert report.validate_report(a) == []
    probs = report.validate_artifact([a, b])
    assert probs and "collides" in probs[0]


# ---------------------------------------------------------------------------
# Sockets: the error-counting satellite + the E2E acceptance run
# ---------------------------------------------------------------------------


def _http(url, method="GET", token=None, body=None, idem=None, timeout=30,
          trace=None):
    headers = {"Content-Type": "application/json"}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    if idem:
        headers["Idempotency-Key"] = idem
    if trace:
        headers["X-Boojum-Trace"] = trace
    req = urllib.request.Request(
        url, data=body, headers=headers, method=method
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers)


@pytest.mark.gateway
def test_http_metrics_500_body_and_error_counter(monkeypatch):
    """Satellite: a read-plane handler exception answers 500 WITH a
    JSON body and is charged to service.http.errors (visible on the
    next /metrics scrape) — never a dropped connection."""
    from boojum_tpu.service.http_metrics import MetricsPlane
    from boojum_tpu.utils import telemetry

    s = telemetry.TelemetrySampler(interval_s=5.0)
    s.sample_once()
    plane = MetricsPlane(s, port=0)
    plane.start()
    try:
        real = plane.render_metrics
        monkeypatch.setattr(
            plane, "render_metrics",
            lambda: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            _http(plane.url("/metrics"))
        assert exc.value.code == 500
        assert "boom" in json.loads(exc.value.read())["error"]
        monkeypatch.setattr(plane, "render_metrics", real)
        _status, body, _ = _http(plane.url("/metrics"))
        assert b"boojum_tpu_service_http_errors 1" in body
    finally:
        plane.stop()


def _wait_done(base, job, token, deadline_s=300.0):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        _status, body, _ = _http(f"{base}/jobs/{job}", token=token)
        ticket = json.loads(body)
        if ticket["status"] in ("done", "failed"):
            return ticket
        time.sleep(0.1)
    raise TimeoutError(f"job {job} still {ticket['status']}")


@pytest.mark.gateway
def test_e2e_two_tenants_over_http(tmp_path):
    """ISSUE 11 acceptance: two tenants over real loopback HTTP —
    proof bytes + checkpoint streams bit-identical to direct prove(),
    idempotent replay from the ledger without a second prove, one
    tenant 429-throttled while the other completes, drain -> the
    artifact passes prove_report.py --check and --slo shows tenants."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from boojum_tpu.service import (
        Gateway,
        GatewayConfig,
        ProvingService,
        ServiceConfig,
        TenantSpec,
    )

    asm, setup, cfg = small_parts()
    direct, direct_line = baseline()

    rpt = str(tmp_path / "gw_e2e.jsonl")
    svc = ProvingService(
        ServiceConfig(precompile="off", report_path=rpt,
                      telemetry_interval_s=0.2)
    )
    gw = Gateway(
        svc,
        GatewayConfig(
            tenants=[
                TenantSpec("alice", "tok-alice", weight=2.0),
                # bob's byte budget dies with his first proof download
                TenantSpec("bob", "tok-bob", quota_bytes=1),
            ],
            admin_token="tok-admin",
        ),
        resolver=lambda spec: (asm, setup, cfg),
    )
    port = gw.start()
    base = f"http://127.0.0.1:{port}"
    try:
        e2e_tid = "5a" * 16  # a client-minted trace id, honored end to end
        code, body, hdrs = _http(
            f"{base}/prove", "POST", token="tok-alice", body=b"{}",
            idem="alice-req-1", trace=e2e_tid,
        )
        assert code == 202
        assert hdrs["X-Boojum-Trace"] == e2e_tid
        ticket_a1 = json.loads(body)
        assert ticket_a1["trace"] == e2e_tid
        job_a1 = ticket_a1["job"]
        code, body, _ = _http(
            f"{base}/prove", "POST", token="tok-bob", body=b"{}"
        )
        assert code == 202
        job_b = json.loads(body)["job"]

        ta1 = _wait_done(base, job_a1, "tok-alice")
        tb = _wait_done(base, job_b, "tok-bob")
        assert ta1["status"] == "done" and tb["status"] == "done"

        # bit-parity over the wire: downloaded proof == direct prove()
        for job, tok in ((job_a1, "tok-alice"), (job_b, "tok-bob")):
            _s, proof_bytes, _h = _http(f"{base}/jobs/{job}/proof",
                                        token=tok)
            assert proof_bytes.decode() == direct.to_json(), job

        # idempotent replay: original ticket, zero extra proves
        served_before = svc.summary()["served"]
        code, body, _ = _http(
            f"{base}/prove", "POST", token="tok-alice", body=b"{}",
            idem="alice-req-1",
        )
        replay = json.loads(body)
        assert code == 200 and replay["replay"] is True
        assert replay["job"] == job_a1
        assert svc.summary()["served"] == served_before

        # bob exhausted his byte quota with that one proof; the charge
        # lands right after his line is written — wait for it, then the
        # next submit must 429 while alice keeps being served
        deadline = time.time() + 60
        while time.time() < deadline:
            if svc.quota.snapshot().get("bob.used_bytes", 0) > 0:
                break
            time.sleep(0.05)
        with pytest.raises(urllib.error.HTTPError) as exc:
            _http(f"{base}/prove", "POST", token="tok-bob", body=b"{}")
        assert exc.value.code == 429
        assert int(exc.value.headers["Retry-After"]) >= 1
        code, body, _ = _http(
            f"{base}/prove", "POST", token="tok-alice", body=b"{}"
        )
        assert code == 202
        job_a2 = json.loads(body)["job"]
        assert _wait_done(base, job_a2, "tok-alice")["status"] == "done"

        # per-tenant telemetry rides /metrics
        svc.sampler.sample_once()
        _s, metrics_body, _h = _http(f"{base}/metrics")
        text = metrics_body.decode()
        assert "boojum_tpu_service_gateway_admitted 3" in text
        assert "boojum_tpu_service_gateway_throttled 1" in text
        assert "boojum_tpu_telemetry_service_tenant_alice_used_bytes" \
            in text

        # graceful drain finishes in-flight work and stops admission
        code, body, _ = _http(
            f"{base}/admin/drain", "POST", token="tok-admin", body=b"{}"
        )
        drain = json.loads(body)
        assert code == 200 and drain["drained"] is True
        assert drain["summary"]["served"] == 3
        with pytest.raises(urllib.error.HTTPError) as exc:
            _http(f"{base}/prove", "POST", token="tok-alice", body=b"{}")
        assert exc.value.code == 503
    finally:
        gw.stop()

    # the artifact: 3 gateway request lines (tenant records attached,
    # checkpoint streams bit-identical to direct) + 1 rejection line
    lines = report.load_reports(rpt)
    req_lines = [ln for ln in lines if "request" in ln]
    assert len(req_lines) == 3
    base_stream = checkpoint_stream(direct_line)
    assert base_stream
    for ln in req_lines:
        assert checkpoint_stream(ln) == base_stream, ln["request"]["id"]
        assert ln["request"]["gateway"] is True
        assert ln["tenant"]["charged_bytes"] > 0
        assert report.validate_report(ln) == [], ln["request"]["id"]
    rejects = [ln for ln in lines
               if (ln.get("tenant") or {}).get("rejected")]
    assert len(rejects) == 1 and rejects[0]["tenant"]["id"] == "bob"

    # ISSUE 17 acceptance: ONE trace_id spans admission -> prove ->
    # proof download — the client-minted id tags exactly alice's first
    # request line, whose tree holds both the backdated queue.wait and
    # the real prove stages, and no span_id repeats across the artifact
    traced = [
        ln for ln in req_lines
        if (ln.get("trace_ctx") or {}).get("trace_id") == e2e_tid
    ]
    assert len(traced) == 1
    tr_names = {name.split("/")[-1]
                for name, _sp in report.flatten_spans(traced[0])}
    assert "queue.wait" in tr_names and "prove" in tr_names
    assert report.validate_artifact(lines) == []

    # the stdlib CLI gate agrees, end to end
    cli = os.path.join(REPO_ROOT, "scripts", "prove_report.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONSTARTUP"}
    chk = subprocess.run(
        [sys.executable, cli, "--check", rpt],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr
    slo = subprocess.run(
        [sys.executable, cli, "--slo", rpt],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert slo.returncode == 0, slo.stdout + slo.stderr
    assert "tenant alice" in slo.stdout
    assert "throttled(429)=1" in slo.stdout

    # --timeline stitches the artifact and the Perfetto export is valid
    # trace-event JSON carrying the queue-wait and prove-stage spans
    perfetto_out = str(tmp_path / "e2e_perfetto.json")
    tl = subprocess.run(
        [sys.executable, cli, "--timeline", rpt, "--perfetto",
         perfetto_out],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert tl.returncode == 0, tl.stdout + tl.stderr
    assert f"trace {e2e_tid[:8]}" in tl.stdout
    with open(perfetto_out) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert "queue.wait" in names and "prove" in names
    assert report.validate_perfetto(doc) == []
