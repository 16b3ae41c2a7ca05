"""ProveReport: the flight recorder's versioned JSONL artifact.

One report line per prove:
  {kind, schema, label, unix_ts, wall_s,
   spans:      [hierarchical span tree, utils/spans.py],
   metrics:    {counters, gauges, boundaries}, (utils/metrics.py),
   checkpoints:[{seq, round, label, digest}, ...]  — Fiat–Shamir state,
   compile_ledger: summary (when a CompileLedger is installed),
   host:       {platform, process_index}}

Transcript DIGEST CHECKPOINTS are the parity-triage axis: at every
Fiat–Shamir round the prover records blake2s(canonical LE64 encoding) of
what crossed the transcript — per-stage Merkle caps, drawn challenges, FRI
fold challenges, final monomials, query indices. Two proves of the same
witness produce byte-identical checkpoint streams; a bit-parity break
against compat/prove_reference.py (or a past report) localizes to the
FIRST diverging (round, label) instead of the final proof blob.

This module is intentionally stdlib-only at import time: the report CLI
(scripts/prove_report.py) loads it standalone — without importing
boojum_tpu (and therefore jax) — for render/diff/check of existing
artifacts. The recording entry points import spans/metrics lazily.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import json
import os
import re
import time

REPORT_KIND = "boojum_tpu.prove_report"
# schema 2 (ISSUE 9): lines may carry a `telemetry` record (background
# sampler time series, utils/telemetry.py) and a `trace` record (an
# on-demand jax.profiler capture attributable to the line); schema 3
# (ISSUE 12): lines may carry a `cost` record (utils/costmodel.py —
# per-stage analytic flops/bytes joined with measured walls into
# achieved GFLOP/s & GB/s, roofline regime and efficiency-vs-peak);
# schema 4 (ISSUE 17): every line carries a `trace_ctx` record
# ({"trace_id": 32-hex, "parent_span_id"?: 16-hex}) and every span a
# `span_id` (utils/spans.py) — the distributed-tracing plane
# `prove_report.py --timeline` stitches on. Older-schema lines remain
# valid for --check/--diff.
REPORT_SCHEMA = 4
ACCEPTED_SCHEMAS = (1, 2, 3, 4)

# id formats (BASELINE.md "Trace protocol"). Re-declared here rather
# than imported from utils/spans.py because report.py must stay
# loadable standalone (scripts/prove_report.py file-loads it with no
# package, no jax).
TRACE_ID_RE = re.compile(r"^[0-9a-f]{32}$")
SPAN_ID_RE = re.compile(r"^[0-9a-f]{16}$")

# field backends (field/spec.py SPECS keys, ISSUE 19). Re-declared for
# the same standalone-load reason as the id formats above.
FIELD_NAMES = ("goldilocks", "babybear")

# black-box forensics records (utils/blackbox.py): heartbeat/dump lines
# interleave with prove lines in the same JSONL artifact; fleet records
# are what `prove_report.py --fleet` emits from per-host artifacts.
# --check routes every line by kind (validate_line)
BLACKBOX_KIND = "boojum_tpu.blackbox"
BLACKBOX_SCHEMAS = (1,)
FLEET_KIND = "boojum_tpu.fleet"
FLEET_SCHEMAS = (1,)

# canonical Fiat–Shamir round order; validation checks checkpoint rounds
# never decrease along the stream
ROUND_ORDER = (0, 1, 2, 3, 4, 5)

# the proving service's placements (service/scheduler.py) — a request
# record carrying anything else fails validation
REQUEST_PLACEMENTS = ("shard_parallel", "proof_parallel")
# fields every per-request SLO record must carry (service/service.py);
# prove_wall_s is additionally required unless the record carries an
# error (a failed request may die before its wall is measured)
REQUEST_REQUIRED = ("id", "bucket", "placement", "queue_latency_s")


def _flatten_ints(values):
    out = []
    stack = [values]
    while stack:
        v = stack.pop()
        if isinstance(v, (list, tuple)):
            stack.extend(reversed(v))
        else:
            out.append(int(v))
    return out


def digest_of(values) -> str:
    """blake2s over the 8-byte little-endian words of the (possibly
    nested) integer sequence — the canonical checkpoint digest."""
    h = hashlib.blake2s()
    for v in _flatten_ints(values):
        h.update((v & ((1 << 64) - 1)).to_bytes(8, "little"))
    return h.hexdigest()


class CheckpointLog:
    def __init__(self):
        self.entries: list[dict] = []

    def add(self, round_: int, label: str, values):
        self.entries.append(
            {
                "seq": len(self.entries),
                "round": int(round_),
                "label": label,
                "digest": digest_of(values),
            }
        )


# process-global DEFAULT context; scoped logs (install_scoped_* /
# flight_recording(scoped=True)) override per execution context so
# packed concurrent proves keep disjoint checkpoint streams
_CHECKPOINTS: CheckpointLog | None = None
_CHECKPOINTS_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "boojum_tpu.checkpoint_log", default=None
)


def current_checkpoint_log() -> CheckpointLog | None:
    log = _CHECKPOINTS_CTX.get()
    return log if log is not None else _CHECKPOINTS


def install_checkpoint_log(log: CheckpointLog | None):
    """Swap the process-wide DEFAULT checkpoint log; returns the
    previous one."""
    global _CHECKPOINTS
    prev = _CHECKPOINTS
    _CHECKPOINTS = log
    return prev


def install_scoped_checkpoint_log(log: CheckpointLog | None):
    """Bind `log` to the CURRENT execution context only; returns a token
    for reset_scoped_checkpoint_log."""
    return _CHECKPOINTS_CTX.set(log)


def reset_scoped_checkpoint_log(token):
    _CHECKPOINTS_CTX.reset(token)


def checkpoint(round_: int, label: str, values):
    """Record one Fiat–Shamir digest checkpoint; no-op-cheap (one
    contextvar read, one global read) when nothing is recording."""
    log = current_checkpoint_log()
    if log is not None:
        log.add(round_, label, values)
        # a new transcript digest is forward motion — reset the
        # blackbox stall clock (utils/blackbox.py); only on the
        # recording path, the no-op path stays two reads
        from . import blackbox as _bb

        _bb.tick()


# ---------------------------------------------------------------------------
# Flight recording: spans + metrics + checkpoints as one unit
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bundles the three collectors for one recorded prove."""

    def __init__(self, label: str | None = None, sync: bool = True):
        from . import metrics as _metrics
        from . import spans as _spans

        self.label = label
        self.spans = _spans.SpanRecorder(sync=sync)
        self.metrics = _metrics.MetricsRegistry()
        self.checkpoints = CheckpointLog()
        self._t0 = time.perf_counter()
        self.wall_s: float | None = None
        # an on-demand jax.profiler capture directory for this recorded
        # window (profiling.maybe_trace_capture) — lands in the report
        # line's `trace` record so the trace is attributable
        self.trace_dir: str | None = None
        # the roofline cost record (utils/costmodel.attach_cost_record
        # stamps it at the end of a successful prove) — lands as the
        # line's schema-3 `cost` record
        self.cost: dict | None = None

    def close(self):
        if self.wall_s is None:
            self.wall_s = round(time.perf_counter() - self._t0, 6)


_FLIGHT: FlightRecorder | None = None
_FLIGHT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "boojum_tpu.flight_recorder", default=None
)


def current_flight_recorder() -> FlightRecorder | None:
    rec = _FLIGHT_CTX.get()
    return rec if rec is not None else _FLIGHT


@contextlib.contextmanager
def flight_recording(
    label: str | None = None, sync: bool = True, scoped: bool = False
):
    """Install a FlightRecorder (spans + metrics + checkpoints) for the
    duration of the block; restores whatever was installed before.

    `scoped=True` binds the collectors to the CURRENT execution context
    via contextvars instead of swapping the process-global defaults —
    the packed proving-service posture, where several requests record
    concurrently on pool threads without corrupting each other's spans,
    counters or checkpoint streams. The default (scoped=False) keeps the
    process-global swap bench/CLI flows rely on: threads they spawn
    mid-recording (the precompile pool) still see the recorder."""
    global _FLIGHT
    from . import metrics as _metrics
    from . import spans as _spans

    rec = FlightRecorder(label=label, sync=sync)
    if scoped:
        tok_flight = _FLIGHT_CTX.set(rec)
        tok_spans = _spans.install_scoped_recorder(rec.spans)
        tok_metrics = _metrics.install_scoped_registry(rec.metrics)
        tok_ckpt = install_scoped_checkpoint_log(rec.checkpoints)
        try:
            yield rec
        finally:
            rec.close()
            _spans.reset_scoped_recorder(tok_spans)
            _metrics.reset_scoped_registry(tok_metrics)
            reset_scoped_checkpoint_log(tok_ckpt)
            _FLIGHT_CTX.reset(tok_flight)
        return
    prev_flight = _FLIGHT
    _FLIGHT = rec
    prev_spans = _spans.install_recorder(rec.spans)
    prev_metrics = _metrics.install_registry(rec.metrics)
    prev_ckpt = install_checkpoint_log(rec.checkpoints)
    try:
        yield rec
    finally:
        rec.close()
        _spans.install_recorder(prev_spans)
        _metrics.install_registry(prev_metrics)
        install_checkpoint_log(prev_ckpt)
        _FLIGHT = prev_flight


def build_report(rec: FlightRecorder, extra: dict | None = None) -> dict:
    rec.close()
    d: dict = {
        "kind": REPORT_KIND,
        "schema": REPORT_SCHEMA,
        "label": rec.label,
        "unix_ts": round(time.time(), 3),
        "wall_s": rec.wall_s,
        "spans": rec.spans.tree(),
        "metrics": rec.metrics.to_dict(),
        "checkpoints": list(rec.checkpoints.entries),
    }
    # trace context (schema 4): the recorder's Dapper-style identity —
    # adopted from the gateway/spool/env when this line serves a
    # propagated trace, freshly minted otherwise. Either way every line
    # is stitchable; `--check` fails a gateway line without it.
    tid = getattr(rec.spans, "trace_id", None)
    if isinstance(tid, str) and TRACE_ID_RE.match(tid):
        tctx = {"trace_id": tid}
        psid = getattr(rec.spans, "parent_span_id", None)
        if isinstance(psid, str) and SPAN_ID_RE.match(psid):
            tctx["parent_span_id"] = psid
        d["trace_ctx"] = tctx
    if rec.trace_dir:
        d["trace"] = {"dir": rec.trace_dir}
    if getattr(rec, "cost", None):
        d["cost"] = rec.cost
    try:
        # the live telemetry plane's time series (utils/telemetry.py):
        # when a sampler is running, every report line carries the
        # service-wide memory/queue/in-flight samples that overlapped it
        from . import telemetry as _telemetry

        sampler = _telemetry.current_sampler()
        if sampler is not None:
            d["telemetry"] = sampler.snapshot()
    except Exception:
        pass
    try:
        from .profiling import current_compile_ledger

        ledger = current_compile_ledger()
        if ledger is not None:
            d["compile_ledger"] = ledger.summary()
    except Exception:
        pass
    try:
        import jax

        # the SAME identity block bench/bench_micro stamp — the five
        # fields _trend_identity groups gated series by, so report
        # artifacts from two machines never share a series
        from ..prover.aot import platform_info

        d["host"] = dict(
            platform_info(),
            platform=jax.default_backend(),
            process_index=jax.process_index(),
        )
    except Exception:
        pass
    if extra:
        d.update(extra)
    return d


def append_jsonl(path: str, report: dict):
    line = json.dumps(report, separators=(",", ":"))
    with open(path, "a") as f:
        f.write(line + "\n")


def load_reports(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def load_report(path: str, index: int = -1) -> dict:
    reports = load_reports(path)
    if not reports:
        raise ValueError(f"{path}: no report lines")
    return reports[index]


# ---------------------------------------------------------------------------
# Validation / analysis (pure dict functions — usable standalone)
# ---------------------------------------------------------------------------


def _walk_spans(spans, prefix=()):
    """Yield (path_tuple, span) depth-first."""
    for sp in spans:
        path = prefix + (sp.get("name", "?"),)
        yield path, sp
        yield from _walk_spans(sp.get("children", ()), path)


def flatten_spans(report: dict) -> list[tuple[str, dict]]:
    return [
        ("/".join(path), sp)
        for path, sp in _walk_spans(report.get("spans", ()))
    ]


# the prover's stage spans (prover._StageClock start calls) — the
# canonical per-round series both the roofline record
# (utils/costmodel.STAGE_NAMES aliases this) and the trend gate key on.
# Cache-state spans that also land under `prove` (aot_load, aot_warm,
# overlap_prefetch) are deliberately NOT stages: gating them would fail
# CI on artifact-store temperature, not prover performance.
PROVE_STAGES = (
    "round1_witness_commit",
    "round2_stage2_commit",
    "round3_quotient",
    "round4_evaluations",
    "round5_deep_fri",
    "queries",
)

# the cache-state spans themselves, for the places that must SUBTRACT
# them (the trend total_wall point) rather than merely not enumerate
# them (stage series)
CACHE_STATE_SPANS = ("aot_load", "aot_warm", "overlap_prefetch")


def _prove_root(spans):
    """The span a line's stage/coverage numbers describe: the `prove`
    span found ANYWHERE in the tree (the proving service nests it under
    its `service_request` root), first root as fallback. The LAST
    matching `prove` span wins: a long-lived bench/CLI recorder can
    hold several proves, and the numbers must come from the prove that
    just finished, not the first one."""
    spans = spans or []
    root = None
    for _path, sp in _walk_spans(spans):
        if sp.get("name") == "prove":
            root = sp
    if root is None and spans:
        root = spans[0]
    return root


def stage_walls(spans, names=None) -> dict:
    """{stage_name: summed wall_s} over the DIRECT children of the
    prove root (_prove_root). The one span-tree extraction both the
    roofline record (utils/costmodel.py) and the trend series share, so
    the perf gate and the cost record can never disagree about what a
    stage's wall is. `names` filters to a known stage set; None takes
    every child."""
    root = _prove_root(spans)
    walls: dict = {}
    for c in (root or {}).get("children", ()):
        nm = c.get("name")
        w = c.get("wall_s")
        if names is not None and nm not in names:
            continue
        if isinstance(w, (int, float)):
            walls[nm] = walls.get(nm, 0.0) + float(w)
    return walls


def span_coverage(report: dict) -> float:
    """Fraction of the prove root's wall covered by its direct children
    (the stage spans) — the SAME root selection as stage_walls, so one
    line's coverage and stage numbers always describe the same prove.
    0.0 when there is no usable tree."""
    root = _prove_root(report.get("spans") or [])
    if not root or not root.get("wall_s"):
        return 0.0
    covered = sum(
        c.get("wall_s") or 0.0 for c in root.get("children", ())
    )
    return min(1.0, covered / root["wall_s"])


def validate_report(report: dict) -> list[str]:
    """Schema + monotonicity checks; returns a list of problems (empty =
    valid). This is the `prove_report.py --check` gate."""
    problems: list[str] = []
    if report.get("kind") != REPORT_KIND:
        problems.append(f"kind is {report.get('kind')!r}, want {REPORT_KIND!r}")
    if report.get("schema") not in ACCEPTED_SCHEMAS:
        problems.append(
            f"schema is {report.get('schema')!r}, want one of "
            f"{ACCEPTED_SCHEMAS}"
        )
    wall = report.get("wall_s")
    if not isinstance(wall, (int, float)) or wall < 0:
        problems.append(f"wall_s invalid: {wall!r}")
    # context-scoped recording invariant (ISSUE 9): one report line is
    # ONE request's flight data. Span attrs carrying two distinct
    # request ids on a single line mean a scoped collector bled across
    # packed requests — the corruption mode the contextvar scoping
    # exists to prevent, so it must fail the gate loudly.
    span_request_ids = set()
    line_span_ids: dict = {}
    for path, sp in _walk_spans(report.get("spans", ())):
        attrs = sp.get("attrs")
        if isinstance(attrs, dict) and attrs.get("request") is not None:
            span_request_ids.add(str(attrs["request"]))
        w = sp.get("wall_s")
        if not isinstance(w, (int, float)) or w < 0:
            problems.append(f"span {'/'.join(path)}: wall_s invalid: {w!r}")
        st = sp.get("start_s")
        # a `backdated` span (queue.wait — utils/spans.py) legitimately
        # starts before its recorder's t0, i.e. at a negative offset
        if not isinstance(st, (int, float)) or (
            st < 0 and not sp.get("backdated")
        ):
            problems.append(f"span {'/'.join(path)}: start_s invalid: {st!r}")
        # span identity (schema 4): ids must be well-formed and unique
        # within the line — a collision means two spans would stitch
        # into the same timeline node
        sid = sp.get("span_id")
        if sid is not None or (
            isinstance(report.get("schema"), int) and report["schema"] >= 4
        ):
            if not (isinstance(sid, str) and SPAN_ID_RE.match(sid)):
                problems.append(
                    f"span {'/'.join(path)}: span_id malformed: {sid!r}"
                )
            elif sid in line_span_ids:
                problems.append(
                    f"span {'/'.join(path)}: span_id {sid} collides with "
                    f"span {line_span_ids[sid]}"
                )
            else:
                line_span_ids[sid] = "/".join(path)
        psid = sp.get("parent_span_id")
        if psid is not None and not (
            isinstance(psid, str) and SPAN_ID_RE.match(psid)
        ):
            problems.append(
                f"span {'/'.join(path)}: parent_span_id malformed: {psid!r}"
            )
        stid = sp.get("trace_id")
        if stid is not None and not (
            isinstance(stid, str) and TRACE_ID_RE.match(stid)
        ):
            problems.append(
                f"span {'/'.join(path)}: trace_id malformed: {stid!r}"
            )
        for c in sp.get("children", ()):
            cst = c.get("start_s")
            if (
                isinstance(cst, (int, float))
                and isinstance(st, (int, float))
                and cst + 1e-6 < st
            ):
                problems.append(
                    f"span {'/'.join(path)}: child {c.get('name')!r} starts "
                    f"before its parent"
                )
    ckpts = report.get("checkpoints")
    if not isinstance(ckpts, list):
        problems.append("checkpoints missing")
        ckpts = []
    last_seq = -1
    last_round = -1
    seen_labels = set()
    for e in ckpts:
        seq, rnd, label = e.get("seq"), e.get("round"), e.get("label")
        dg = e.get("digest")
        if not isinstance(seq, int) or seq <= last_seq:
            problems.append(f"checkpoint {label!r}: seq {seq!r} not increasing")
        else:
            last_seq = seq
        if not isinstance(rnd, int) or rnd < last_round:
            problems.append(
                f"checkpoint {label!r}: round {rnd!r} decreases "
                f"(after round {last_round})"
            )
        else:
            last_round = rnd
        if (rnd, label) in seen_labels:
            problems.append(f"checkpoint {label!r}: duplicate in round {rnd}")
        seen_labels.add((rnd, label))
        if not (
            isinstance(dg, str)
            and len(dg) == 64
            and all(c in "0123456789abcdef" for c in dg)
        ):
            problems.append(f"checkpoint {label!r}: digest malformed: {dg!r}")
    metrics = report.get("metrics")
    if not isinstance(metrics, dict) or "counters" not in metrics:
        problems.append("metrics missing or malformed")
    else:
        # ici.* — the explicit mesh collectives' bill (ISSUE 5). Every
        # gauge must be a finite non-negative number, and a nonzero
        # collective COUNTER must come with its byte gauge (and the pivot
        # timer for all_to_alls): a pivot that moved zero bytes means the
        # accounting seam in parallel/shard_sweep.py was bypassed.
        counters = metrics.get("counters")
        if not isinstance(counters, dict):
            if counters is not None:
                problems.append(
                    "metrics.counters malformed: "
                    f"{type(counters).__name__}"
                )
            counters = {}
        gauges = metrics.get("gauges")
        if not isinstance(gauges, dict):
            if gauges is not None:
                problems.append(
                    f"metrics.gauges malformed: {type(gauges).__name__}"
                )
            gauges = {}

        def _num(v):
            # non-numerics were flagged above; compare as 0 so one bad
            # value yields its problem line instead of a TypeError
            return v if isinstance(v, (int, float)) and v == v else 0

        for k, v in gauges.items():
            if not (k.startswith("ici.") or k.startswith("dcn.")):
                continue
            if not isinstance(v, (int, float)) or v != v or v < 0:
                problems.append(f"gauge {k}: invalid value {v!r}")
        # a collective's crossing bytes may split intra-host (ici.*) vs
        # cross-process (dcn.*) on a multi-host mesh — a counted
        # collective must have moved bytes on at least one fabric
        if _num(counters.get("ici.all_to_alls", 0)) > 0:
            if not (
                _num(gauges.get("ici.all_to_all_bytes", 0))
                + _num(gauges.get("dcn.all_to_all_bytes", 0))
            ) > 0:
                problems.append(
                    "ici.all_to_alls counted but the ici.all_to_all_bytes "
                    "+ dcn.all_to_all_bytes gauges are missing/zero"
                )
            if "ici.pivot_s" not in gauges:
                problems.append(
                    "ici.all_to_alls counted but ici.pivot_s gauge missing"
                )
        if _num(counters.get("ici.all_gathers", 0)) > 0 and not (
            _num(gauges.get("ici.all_gather_bytes", 0))
            + _num(gauges.get("dcn.all_gather_bytes", 0))
        ) > 0:
            problems.append(
                "ici.all_gathers counted but the ici.all_gather_bytes "
                "+ dcn.all_gather_bytes gauges are missing/zero"
            )
        # dcn.* counters carry the same counted-but-zero-bytes invariant
        for fam, gname in (
            ("dcn.all_to_alls", "dcn.all_to_all_bytes"),
            ("dcn.all_gathers", "dcn.all_gather_bytes"),
            ("dcn.host_gathers", "dcn.host_gather_bytes"),
        ):
            if _num(counters.get(fam, 0)) > 0 and not _num(
                gauges.get(gname, 0)
            ) > 0:
                problems.append(
                    f"{fam} counted but {gname} gauge is missing/zero"
                )
        # service.* — the proving service's queue/cache/SLO axis. Every
        # value must be a finite non-negative number, and evictions must
        # carry their byte gauge (an eviction that freed zero bytes means
        # the cache manager's accounting seam was bypassed).
        for src in (counters, gauges):
            for k, v in src.items():
                if not k.startswith("service."):
                    continue
                if not isinstance(v, (int, float)) or v != v or v < 0:
                    problems.append(
                        f"service metric {k}: invalid value {v!r}"
                    )
        if _num(counters.get("service.cache.evictions", 0)) > 0 and not _num(
            gauges.get("service.cache.evicted_bytes", 0)
        ) > 0:
            problems.append(
                "service.cache.evictions counted but "
                "service.cache.evicted_bytes gauge is missing/zero"
            )
        # aot.* — the AOT artifact store's axis (prover/aot.py). Every
        # value must be a finite non-negative number; warmed kernels
        # (hits+misses > 0) must carry the deserialize-time gauge; and a
        # line claiming every kernel was an artifact hit while its
        # compile ledger still counted cache misses (real compiles) is
        # LYING about its warm-up bill and must fail the gate.
        for src in (counters, gauges):
            for k, v in src.items():
                if not k.startswith("aot."):
                    continue
                if not isinstance(v, (int, float)) or v != v or v < 0:
                    problems.append(f"aot metric {k}: invalid value {v!r}")
        aot_hits = _num(counters.get("aot.hits", 0))
        aot_misses = _num(counters.get("aot.misses", 0))
        if (aot_hits + aot_misses) > 0 and "aot.deserialize_s" not in gauges:
            problems.append(
                "aot.hits/aot.misses counted but aot.deserialize_s "
                "gauge missing"
            )
        # the aot_hit-vs-compile cross-check compares LEDGER fields with
        # LEDGER fields (both process-cumulative): a line whose ledger
        # claims every warmed kernel deserialized from an artifact
        # (aot_hits > 0, aot_misses == 0) while the same ledger counted
        # persistent-cache misses means real compiles escaped the
        # artifact store — the zero-compile claim is false
        ledger = report.get("compile_ledger")
        if isinstance(ledger, dict):
            ledger_hits = _num(ledger.get("aot_hits", 0))
            ledger_misses = _num(ledger.get("aot_misses", 0))
            num_kernels = _num(ledger.get("num_kernels", 0))
            # fires only when the ledger claims FULL aot coverage —
            # every recorded kernel an artifact hit. A mixed-bucket
            # process (bucket A bundled, bucket B precompiled normally)
            # has num_kernels > aot_hits and is a supported state, not
            # a lie.
            if (
                ledger_hits > 0
                and ledger_misses == 0
                and ledger_hits == num_kernels
            ):
                compiles = _num(ledger.get("cache_misses", 0))
                if compiles > 0:
                    problems.append(
                        f"prove claims all-aot_hit kernels but the "
                        f"compile ledger records {int(compiles)} cache "
                        f"misses (real compiles escaped the artifact "
                        f"store)"
                    )
        # limb.* — the u64<->limb conversion tax (ISSUE 10). Counters
        # must be finite non-negative ints, and a line whose kernels
        # claim LIMB-RESIDENT dispatch (quotient.resident_coset_sweeps /
        # fri.resident_folds) while counting INTERIOR splits/joins is
        # lying about residency — the whole point of the resident mode
        # is that those are zero (edges are allowlisted under
        # limb.edge_*/limb.host_*).
        for k, v in counters.items():
            if not k.startswith("limb."):
                continue
            if not isinstance(v, int) or v < 0:
                problems.append(f"limb metric {k}: invalid value {v!r}")
        resident_claimed = (
            _num(counters.get("quotient.resident_coset_sweeps", 0)) > 0
            or _num(counters.get("fri.resident_folds", 0)) > 0
        )
        if resident_claimed:
            for k in ("limb.splits", "limb.joins"):
                if _num(counters.get(k, 0)) > 0:
                    problems.append(
                        f"resident-mode prove counted interior {k} = "
                        f"{counters.get(k)} (conversions must survive "
                        f"only at allowlisted edges)"
                    )
    # per-request SLO record (proving-service lines): the record the
    # --slo summary and dashboards key on — a request line missing its
    # queue latency or placement is unusable for SLO accounting and
    # must fail the --check gate
    request = report.get("request")
    if request is not None:
        if not isinstance(request, dict):
            problems.append(
                f"request record malformed: {type(request).__name__}"
            )
        else:
            for k in REQUEST_REQUIRED:
                if k not in request:
                    problems.append(f"request record missing {k!r}")
            ql = request.get("queue_latency_s")
            if "queue_latency_s" in request and (
                not isinstance(ql, (int, float)) or ql != ql or ql < 0
            ):
                problems.append(
                    f"request queue_latency_s invalid: {ql!r}"
                )
            pl = request.get("placement")
            if "placement" in request and pl not in REQUEST_PLACEMENTS:
                problems.append(
                    f"request placement {pl!r}: want one of "
                    f"{REQUEST_PLACEMENTS}"
                )
            pw = request.get("prove_wall_s")
            if "error" not in request and (
                not isinstance(pw, (int, float)) or pw != pw or pw < 0
            ):
                problems.append(
                    f"request prove_wall_s invalid: {pw!r}"
                )
            rf = request.get("field")
            if rf is not None and rf not in FIELD_NAMES:
                problems.append(
                    f"request field {rf!r}: want one of "
                    f"{sorted(FIELD_NAMES)}"
                )
            if request.get("id") is not None:
                span_request_ids.add(str(request["id"]))
    # per-tenant record (gateway lines, ISSUE 11): quota charges must be
    # sane non-negative numbers, a gateway-ADMITTED request line must
    # carry the record at all (the quota axis is the whole point of
    # admitting through the front door), and a REJECTED line (429 /
    # load-shed) must never claim a prove wall — nothing was proved.
    tenant = report.get("tenant")
    if tenant is not None:
        if not isinstance(tenant, dict):
            problems.append(
                f"tenant record malformed: {type(tenant).__name__}"
            )
            tenant = None
        else:
            tid = tenant.get("id")
            if not isinstance(tid, str) or not tid:
                problems.append(f"tenant record id invalid: {tid!r}")
            for k in (
                "charged_bytes", "charged_compute_s",
                "window_used_bytes", "window_used_compute_s",
                "retry_after_s",
            ):
                if k not in tenant:
                    continue
                v = tenant.get(k)
                if not isinstance(v, (int, float)) or v != v or v < 0:
                    problems.append(f"tenant {k} invalid: {v!r}")
            if tenant.get("rejected"):
                pw = (
                    request.get("prove_wall_s")
                    if isinstance(request, dict) else None
                )
                if isinstance(pw, (int, float)):
                    problems.append(
                        "rejected admission carries prove_wall_s "
                        f"({pw!r}): a 429/shed line must never prove"
                    )
    if (
        isinstance(request, dict)
        and request.get("gateway")
        and tenant is None
    ):
        problems.append(
            "gateway-admitted request line missing its tenant record"
        )
    if len(span_request_ids) > 1:
        problems.append(
            "line mixes request ids "
            f"{sorted(span_request_ids)}: scoped collectors bled "
            "across packed requests"
        )
    # trace context (schema 4, ISSUE 17): when present it must be
    # well-formed, and a GATEWAY line (an admitted request or a
    # gateway-authored reject/spool line) must carry it at all — an
    # orphan gateway trace defeats the entire propagation chain, so it
    # fails the gate rather than silently dropping off timelines.
    tctx = report.get("trace_ctx")
    if tctx is not None:
        if not isinstance(tctx, dict):
            problems.append(
                f"trace_ctx malformed: {type(tctx).__name__}"
            )
        else:
            tid = tctx.get("trace_id")
            if not (isinstance(tid, str) and TRACE_ID_RE.match(tid)):
                problems.append(f"trace_ctx trace_id malformed: {tid!r}")
            psid = tctx.get("parent_span_id")
            if psid is not None and not (
                isinstance(psid, str) and SPAN_ID_RE.match(psid)
            ):
                problems.append(
                    f"trace_ctx parent_span_id malformed: {psid!r}"
                )
    is_gateway_line = bool(
        (isinstance(request, dict) and request.get("gateway"))
        or str(report.get("label") or "").startswith("gateway")
    )
    if (
        tctx is None
        and is_gateway_line
        and isinstance(report.get("schema"), int)
        and report["schema"] >= 4
    ):
        problems.append(
            "gateway line missing trace_ctx: the admission that minted "
            "the trace failed to propagate it"
        )
    # telemetry record (schema 2, utils/telemetry.py): the background
    # sampler's time series. Samples must be time-ordered with finite
    # non-negative readings — a sampler writing junk would poison every
    # dashboard fed from these lines.
    telemetry = report.get("telemetry")
    if telemetry is not None:
        problems.extend(_validate_telemetry(telemetry))
    # cost record (schema 3, utils/costmodel.py): the roofline numbers
    # dashboards and the trend gate key on. A record claiming an
    # efficiency over a zero/absent denominator (no wall, no positive
    # peak) or attributing kernels the compile ledger never recorded is
    # fabricating attribution and must fail the gate.
    cost = report.get("cost")
    if cost is not None:
        problems.extend(
            _validate_cost(cost, report.get("compile_ledger"))
        )
        # field-claim cross-check (ISSUE 19): the BabyBear backend's
        # whole value is ONE u32 lane per element end-to-end — a line
        # whose cost record claims field=babybear while the same line's
        # counters record interior limb-plane conversions is running
        # Goldilocks plumbing under a BabyBear label and must fail the
        # gate (the limb.* counters only ever move on the (lo, hi)
        # plane paths).
        if isinstance(cost, dict) and cost.get("field") == "babybear":
            m = report.get("metrics")
            counters = (
                m.get("counters")
                if isinstance(m, dict)
                and isinstance(m.get("counters"), dict)
                else {}
            )
            for k in ("limb.splits", "limb.joins"):
                v = counters.get(k, 0)
                if isinstance(v, (int, float)) and v > 0:
                    problems.append(
                        f"cost record claims field=babybear but the "
                        f"line counted {k} = {counters.get(k)} (limb "
                        f"conversions are a Goldilocks-plane artifact "
                        f"— the babybear path must never touch them)"
                    )
    trace = report.get("trace")
    if trace is not None and not (
        isinstance(trace, dict) and isinstance(trace.get("dir"), str)
        and trace["dir"]
    ):
        problems.append(f"trace record malformed: {trace!r}")
    return problems


def _validate_cost(cost, ledger) -> list[str]:
    if not isinstance(cost, dict):
        return [f"cost record malformed: {type(cost).__name__}"]
    problems: list[str] = []
    device = cost.get("device")
    if not isinstance(device, dict):
        problems.append("cost record missing device peaks")
        device = {}

    def _bad(v):
        return not isinstance(v, (int, float)) or v != v

    field = cost.get("field")
    if field is not None and field not in FIELD_NAMES:
        problems.append(
            f"cost record field {field!r}: want one of "
            f"{sorted(FIELD_NAMES)}"
        )
    stages = cost.get("stages")
    if not isinstance(stages, dict) or not stages:
        problems.append("cost record has no stages")
        stages = {}
    entries = dict(stages)
    if isinstance(cost.get("total"), dict):
        entries["total"] = cost["total"]
    peak_by_regime = {
        "compute": device.get("peak_gflops"),
        "memory": device.get("peak_hbm_gbps"),
    }
    for name, st in entries.items():
        if not isinstance(st, dict):
            problems.append(f"cost stage {name}: not a dict")
            continue
        for k in ("flops", "hbm_bytes", "ici_bytes", "dcn_bytes"):
            v = st.get(k)
            if v is not None and (_bad(v) or v < 0):
                problems.append(f"cost stage {name}: {k} invalid: {v!r}")
        wall = st.get("wall_s")
        if wall is not None and (_bad(wall) or wall < 0):
            problems.append(f"cost stage {name}: wall_s invalid: {wall!r}")
        claimed = [
            k for k in ("achieved_gflops", "achieved_gbps", "efficiency")
            if st.get(k) is not None
        ]
        if claimed and not (
            isinstance(wall, (int, float)) and wall == wall and wall > 0
        ):
            problems.append(
                f"cost stage {name}: {claimed[0]} claimed over a "
                f"zero/absent wall (denominator) — wall_s={wall!r}"
            )
        for k in claimed:
            v = st.get(k)
            if _bad(v) or v < 0:
                problems.append(f"cost stage {name}: {k} invalid: {v!r}")
        eff = st.get("efficiency")
        if eff is not None:
            regime = st.get("regime")
            peak = peak_by_regime.get(regime)
            if not (
                isinstance(peak, (int, float)) and peak == peak and peak > 0
            ):
                problems.append(
                    f"cost stage {name}: efficiency claimed against a "
                    f"zero/absent {regime!r} peak (denominator) — "
                    f"device={peak!r}"
                )
    # the attribution cross-check: a cost record may only claim XLA
    # actuals for kernels the compile ledger actually recorded (the
    # `kernels` list is the analytic sheet's coverage — informational;
    # `attributed_kernels` is the evidence claim). Older ledgers without
    # a kernel-name set skip the check.
    kernels = cost.get("attributed_kernels")
    if kernels is not None and not isinstance(kernels, list):
        problems.append(
            f"cost attributed_kernels malformed: {type(kernels).__name__}"
        )
        kernels = None
    ledger_names = (
        ledger.get("kernel_names") if isinstance(ledger, dict) else None
    )
    if isinstance(kernels, list) and isinstance(ledger_names, list):
        alien = sorted(set(map(str, kernels)) - set(map(str, ledger_names)))
        if alien:
            problems.append(
                f"cost record claims XLA actuals for {len(alien)} "
                f"kernel(s) absent from the compile ledger (attribution "
                f"outran the evidence): {alien[:5]}"
            )
    return problems


def _validate_telemetry(telemetry) -> list[str]:
    if not isinstance(telemetry, dict):
        return [f"telemetry record malformed: {type(telemetry).__name__}"]
    problems: list[str] = []
    iv = telemetry.get("interval_s")
    if not isinstance(iv, (int, float)) or iv != iv or iv <= 0:
        problems.append(f"telemetry interval_s invalid: {iv!r}")
    ticks = telemetry.get("ticks")
    if not isinstance(ticks, int) or ticks < 0:
        problems.append(f"telemetry ticks invalid: {ticks!r}")
    samples = telemetry.get("samples")
    if not isinstance(samples, list):
        return problems + [
            f"telemetry samples missing/malformed: {type(samples).__name__}"
        ]
    last_t = float("-inf")
    for i, s in enumerate(samples):
        if not isinstance(s, dict):
            problems.append(f"telemetry sample {i}: not a dict")
            continue
        t = s.get("t_s")
        if not isinstance(t, (int, float)) or t != t or t < 0:
            problems.append(f"telemetry sample {i}: t_s invalid: {t!r}")
        elif t < last_t:
            problems.append(
                f"telemetry sample {i}: t_s {t} decreases (after {last_t})"
            )
        else:
            last_t = t
        for k, v in s.items():
            if k == "t_s":
                continue
            if not isinstance(v, (int, float)) or v != v or v < 0:
                problems.append(
                    f"telemetry sample {i}: {k} invalid: {v!r}"
                )
    return problems


# ---------------------------------------------------------------------------
# Black-box forensics records (utils/blackbox.py) + fleet aggregation
# ---------------------------------------------------------------------------


def validate_blackbox(rec: dict) -> list[str]:
    """--check gate for one blackbox heartbeat/dump line. The bar the
    forensics must clear to be trusted during an incident: monotonic
    seq, sane timestamps, and — for dumps — actual stacks plus a
    machine-usable reason, so a stall dump that lost its payload fails
    loudly instead of reading as 'no problem found'."""
    problems: list[str] = []
    if rec.get("kind") != BLACKBOX_KIND:
        problems.append(
            f"kind is {rec.get('kind')!r}, want {BLACKBOX_KIND!r}"
        )
    if rec.get("schema") not in BLACKBOX_SCHEMAS:
        problems.append(
            f"schema is {rec.get('schema')!r}, want one of "
            f"{BLACKBOX_SCHEMAS}"
        )
    record = rec.get("record")
    if record not in ("heartbeat", "dump"):
        problems.append(f"record invalid: {record!r}")
    seq = rec.get("seq")
    if not isinstance(seq, int) or seq < 1:
        problems.append(f"seq invalid: {seq!r}")
    for k in ("t_s", "unix_ts"):
        v = rec.get(k)
        if not isinstance(v, (int, float)) or v != v or v < 0:
            problems.append(f"{k} invalid: {v!r}")
    prog = rec.get("progress")
    if not isinstance(prog, int) or prog < 0:
        problems.append(f"progress invalid: {prog!r}")
    if not isinstance(rec.get("phase"), str):
        problems.append(f"phase invalid: {rec.get('phase')!r}")
    if "span" in rec and not (
        isinstance(rec["span"], str) and rec["span"]
    ):
        problems.append(f"span invalid: {rec['span']!r}")
    # trace stamps (ISSUE 17): incidents join the timeline by carrying
    # the live recorder's trace id and the innermost OPEN span's id
    tid = rec.get("trace_id")
    if tid is not None and not (
        isinstance(tid, str) and TRACE_ID_RE.match(tid)
    ):
        problems.append(f"trace_id malformed: {tid!r}")
    sid = rec.get("span_id")
    if sid is not None and not (
        isinstance(sid, str) and SPAN_ID_RE.match(sid)
    ):
        problems.append(f"span_id malformed: {sid!r}")
    if record != "dump":
        return problems
    reason = rec.get("reason")
    if not (isinstance(reason, str) and reason):
        problems.append(f"dump reason invalid: {reason!r}")
    if reason == "stall":
        ss = rec.get("stall_s")
        if not isinstance(ss, (int, float)) or ss <= 0:
            problems.append(f"stall dump: stall_s invalid: {ss!r}")
    if reason == "deadline" and not rec.get("deadline"):
        problems.append("deadline dump: deadline name missing")
    stacks = rec.get("stacks")
    if not isinstance(stacks, list) or not stacks:
        problems.append("dump stacks missing/empty")
    else:
        for i, st in enumerate(stacks):
            if not (
                isinstance(st, dict)
                and isinstance(st.get("thread"), str)
                and isinstance(st.get("stack"), list)
                and st["stack"]
            ):
                problems.append(f"dump stack {i} malformed")
    if not isinstance(rec.get("faulthandler"), str):
        problems.append("dump faulthandler text missing")
    hbs = rec.get("heartbeats")
    if not isinstance(hbs, list):
        problems.append("dump heartbeat trail missing")
    else:
        for i, hb in enumerate(hbs):
            if not (
                isinstance(hb, dict) and hb.get("record") == "heartbeat"
            ):
                problems.append(f"dump heartbeat {i} malformed")
    if "spans" in rec and not isinstance(rec["spans"], list):
        problems.append("dump spans malformed")
    # the dump's span path and span_id name the SAME span: both were
    # read from the live tree the dump also embeds. A disagreement means
    # the forensics raced the recorder and the dump's attribution cannot
    # be trusted — reject it rather than let an incident pin the wrong
    # stage.
    if (
        isinstance(sid, str)
        and SPAN_ID_RE.match(sid)
        and isinstance(rec.get("span"), str)
        and isinstance(rec.get("spans"), list)
    ):
        found = None
        for path, sp in _walk_spans(rec["spans"]):
            if sp.get("span_id") == sid:
                found = "/".join(path)
                break
        if found is None:
            problems.append(
                f"dump span_id {sid} not present in the embedded span tree"
            )
        elif found != rec["span"]:
            problems.append(
                f"dump span path {rec['span']!r} disagrees with span_id "
                f"{sid} (tree says {found!r})"
            )
    return problems


def validate_fleet(rec: dict) -> list[str]:
    """--check gate for a fleet record (`prove_report.py --fleet`
    output): host entries named and unique, stage stats internally
    consistent (max >= median, max_host a real host), stragglers
    referring to real stages/hosts."""
    problems: list[str] = []
    if rec.get("kind") != FLEET_KIND:
        problems.append(f"kind is {rec.get('kind')!r}, want {FLEET_KIND!r}")
    if rec.get("schema") not in FLEET_SCHEMAS:
        problems.append(
            f"schema is {rec.get('schema')!r}, want one of {FLEET_SCHEMAS}"
        )
    hosts = rec.get("hosts")
    if not isinstance(hosts, list) or not hosts:
        return problems + ["hosts missing/empty"]
    names = []
    for i, h in enumerate(hosts):
        if not isinstance(h, dict) or not h.get("host"):
            problems.append(f"host {i}: entry malformed")
            continue
        names.append(h["host"])
        off = h.get("clock_offset_s")
        if off is not None and (
            not isinstance(off, (int, float)) or off != off or off < 0
        ):
            problems.append(f"host {h['host']}: clock_offset_s invalid: {off!r}")
        stages = h.get("stages")
        if stages is not None and not isinstance(stages, dict):
            problems.append(f"host {h['host']}: stages malformed")
        for k in ("ici_bytes", "dcn_bytes", "transfer_bytes", "wall_s"):
            v = h.get(k)
            if v is not None and (
                not isinstance(v, (int, float)) or v != v or v < 0
            ):
                problems.append(f"host {h['host']}: {k} invalid: {v!r}")
    if len(set(names)) != len(names):
        problems.append(f"duplicate host names: {names}")
    n = rec.get("n_hosts")
    if n != len(hosts):
        problems.append(f"n_hosts {n!r} != len(hosts) {len(hosts)}")
    stages = rec.get("stages")
    if not isinstance(stages, dict):
        problems.append("stages missing")
        stages = {}
    for nm, st in stages.items():
        if not isinstance(st, dict):
            problems.append(f"stage {nm}: malformed")
            continue
        med, mx = st.get("median_s"), st.get("max_s")
        if not isinstance(med, (int, float)) or med < 0:
            problems.append(f"stage {nm}: median_s invalid: {med!r}")
        if not isinstance(mx, (int, float)) or mx < 0:
            problems.append(f"stage {nm}: max_s invalid: {mx!r}")
        if (
            isinstance(med, (int, float))
            and isinstance(mx, (int, float))
            and mx + 1e-9 < med
        ):
            problems.append(f"stage {nm}: max_s {mx} < median_s {med}")
        if st.get("max_host") not in names:
            problems.append(
                f"stage {nm}: max_host {st.get('max_host')!r} not a host"
            )
        walls = st.get("walls")
        if not isinstance(walls, dict):
            problems.append(f"stage {nm}: walls missing")
        else:
            for hn in walls:
                if hn not in names:
                    problems.append(f"stage {nm}: wall host {hn!r} unknown")
    for i, s in enumerate(rec.get("stragglers") or ()):
        if not isinstance(s, dict):
            problems.append(f"straggler {i}: malformed")
            continue
        if s.get("stage") not in stages:
            problems.append(f"straggler {i}: stage {s.get('stage')!r} unknown")
        if s.get("host") not in names:
            problems.append(f"straggler {i}: host {s.get('host')!r} unknown")
        r = s.get("ratio")
        if not isinstance(r, (int, float)) or r < 1.0:
            problems.append(f"straggler {i}: ratio invalid: {r!r}")
    clock = rec.get("clock")
    if not isinstance(clock, dict) or clock.get("method") not in (
        "barrier",
        "none",
    ):
        problems.append(f"clock malformed: {clock!r}")
    return problems


def validate_line(doc: dict) -> list[str]:
    """Route one artifact line to its kind's validator — the --check
    entry point now that blackbox dumps and fleet records interleave
    with prove lines in the same JSONL files."""
    kind = doc.get("kind")
    if kind == BLACKBOX_KIND:
        return validate_blackbox(doc)
    if kind == FLEET_KIND:
        return validate_fleet(doc)
    return validate_report(doc)


def validate_artifact(docs: list) -> list[str]:
    """Cross-LINE invariants over a whole artifact (the per-line checks
    are validate_line): span ids must be unique across every prove
    line's span tree — two lines sharing a span_id would stitch into
    one timeline node and silently merge two requests' history. Only
    REPORT_KIND trees define ids; blackbox dumps EMBED a snapshot of a
    live tree whose spans reappear in that recorder's final line, so
    they are references, not definitions."""
    problems: list[str] = []
    seen: dict = {}
    for i, d in enumerate(docs):
        if not isinstance(d, dict) or d.get("kind") != REPORT_KIND:
            continue
        for path, sp in _walk_spans(d.get("spans") or ()):
            sid = sp.get("span_id")
            if not (isinstance(sid, str) and SPAN_ID_RE.match(sid)):
                continue
            key = f"line {i} span {'/'.join(path)}"
            if sid in seen:
                problems.append(
                    f"span_id {sid} collides: {seen[sid]} vs {key}"
                )
            else:
                seen[sid] = key
    return problems


def _sum_gauges(metrics: dict, prefixes: tuple, contains: str) -> float | None:
    total = 0.0
    found = False
    for k, v in (metrics.get("gauges") or {}).items():
        if contains in k and any(k.startswith(p) for p in prefixes):
            if isinstance(v, (int, float)):
                total += float(v)
                found = True
    return total if found else None


def _fleet_host_entry(label: str, docs: list[dict]) -> dict:
    """Distill one host's artifact lines (multihost result line and/or
    per-host ProveReport JSONL and/or blackbox records) into one fleet
    host entry."""
    entry: dict = {"host": label}
    dumps = 0
    for d in docs:
        if not isinstance(d, dict):
            continue
        kind = d.get("kind")
        if kind == BLACKBOX_KIND:
            if d.get("record") == "dump":
                dumps += 1
            if d.get("phase"):
                entry["phase"] = d["phase"]
            continue
        if kind == REPORT_KIND:
            spans = d.get("spans") or []
            if any(
                sp.get("name") == "prove" for _p, sp in _walk_spans(spans)
            ):
                walls = stage_walls(spans)
                if walls:
                    entry["stages"] = {
                        k: round(v, 6) for k, v in walls.items()
                    }
                if isinstance(d.get("wall_s"), (int, float)):
                    entry["wall_s"] = d["wall_s"]
            m = d.get("metrics")
            if isinstance(m, dict):
                ici = _sum_gauges(m, ("ici.",), "bytes")
                if ici is not None:
                    entry["ici_bytes"] = entry.get("ici_bytes", 0.0) + ici
                dcn = _sum_gauges(m, ("dcn.",), "bytes")
                if dcn is not None:
                    entry["dcn_bytes"] = entry.get("dcn_bytes", 0.0) + dcn
                xfer = _sum_gauges(m, ("transfer.", "limb."), "bytes")
                if xfer is not None:
                    entry["transfer_bytes"] = (
                        entry.get("transfer_bytes", 0.0) + xfer
                    )
            continue
        # multihost_worker result line: {pid, proofs, ici, dcn, clock_sync}
        if "pid" in d and ("proofs" in d or "clock_sync" in d or "ici" in d):
            if isinstance(d.get("pid"), int):
                entry["pid"] = d["pid"]
            if isinstance(d.get("mesh_mode"), str):
                entry["mesh_mode"] = d["mesh_mode"]
            cs = d.get("clock_sync")
            if isinstance(cs, dict) and isinstance(
                cs.get("barrier_unix_ts"), (int, float)
            ):
                entry["barrier_unix_ts"] = cs["barrier_unix_ts"]
            for key, field in (("ici", "ici_bytes"), ("dcn", "dcn_bytes")):
                fam = d.get(key)
                if isinstance(fam, dict):
                    tot = sum(
                        float(v)
                        for k, v in fam.items()
                        if "bytes" in k and isinstance(v, (int, float))
                    )
                    if tot:
                        entry.setdefault(field, tot)
            rp = d.get("prove_report_path")
            if isinstance(rp, str) and rp:
                entry["prove_report_path"] = rp
    if dumps:
        entry["dumps"] = dumps
    return entry


def fleet_merge(
    host_docs: list,
    straggler_ratio: float = 1.5,
    min_abs_s: float = 0.05,
) -> dict:
    """Merge per-host artifacts into ONE mesh-wide fleet record
    (DIZK's lesson: cluster proving lives or dies on per-node straggler
    attribution). `host_docs` is [(label, [parsed lines...]), ...] —
    one element per host, typically a multihost_worker result file or
    its per-host ProveReport.

    Clock alignment: hosts that stamped a barrier-synchronized
    `clock_sync.barrier_unix_ts` (scripts/multihost_worker.py) all
    passed the same collective at the same instant, so the pairwise
    differences of those stamps ARE the wall-clock skews — no NTP
    assumption. Offsets are reported relative to the earliest host.

    Straggler rule: a stage straggles when its slowest host exceeds
    straggler_ratio x the across-host median AND by at least min_abs_s
    (sub-50ms spread is scheduling jitter, not a straggler)."""
    hosts = [_fleet_host_entry(lbl, docs) for lbl, docs in host_docs]
    # clock skew from barrier stamps
    stamps = {
        h["host"]: h["barrier_unix_ts"]
        for h in hosts
        if isinstance(h.get("barrier_unix_ts"), (int, float))
    }
    if len(stamps) >= 2:
        t0 = min(stamps.values())
        for h in hosts:
            if h["host"] in stamps:
                h["clock_offset_s"] = round(stamps[h["host"]] - t0, 6)
        clock = {
            "method": "barrier",
            "max_skew_s": round(max(stamps.values()) - t0, 6),
        }
    else:
        clock = {
            "method": "none",
            "note": (
                "fewer than 2 hosts carry clock_sync.barrier_unix_ts; "
                "stage walls are durations (skew-free) but timelines "
                "are unaligned"
            ),
        }
    # per-stage across-host stats
    stage_hosts: dict = {}
    for h in hosts:
        for nm, w in (h.get("stages") or {}).items():
            if isinstance(w, (int, float)):
                stage_hosts.setdefault(nm, {})[h["host"]] = float(w)
    stages: dict = {}
    stragglers: list = []
    for nm in sorted(stage_hosts):
        walls = stage_hosts[nm]
        med = _percentile(sorted(walls.values()), 0.5)
        max_host = max(walls, key=walls.get)
        mx = walls[max_host]
        stages[nm] = {
            "median_s": round(med, 6),
            "max_s": round(mx, 6),
            "max_host": max_host,
            "walls": {k: round(v, 6) for k, v in sorted(walls.items())},
        }
        if (
            len(walls) >= 2
            and med > 0
            and mx > med * straggler_ratio
            and (mx - med) >= min_abs_s
        ):
            stragglers.append(
                {
                    "stage": nm,
                    "host": max_host,
                    "wall_s": round(mx, 6),
                    "median_s": round(med, 6),
                    "ratio": round(mx / med, 4),
                }
            )
    return {
        "kind": FLEET_KIND,
        "schema": FLEET_SCHEMAS[-1],
        "unix_ts": time.time(),
        "n_hosts": len(hosts),
        "hosts": hosts,
        "stages": stages,
        "stragglers": stragglers,
        "clock": clock,
        "straggler_ratio": straggler_ratio,
    }


def render_fleet(rec: dict) -> str:
    """Text view of a fleet record: host roster with clock offsets and
    byte rollups, then the per-stage wall table (one column per host)
    with stragglers flagged."""
    lines = []
    clock = rec.get("clock") or {}
    skew = clock.get("max_skew_s")
    lines.append(
        f"fleet: {rec.get('n_hosts')} hosts, clock={clock.get('method')}"
        + (f" (max skew {skew}s)" if skew is not None else "")
    )
    if clock.get("note"):
        lines.append(f"  note: {clock['note']}")
    hosts = rec.get("hosts") or []
    lines.append(
        f"  {'host':<16} {'offset_s':>9} {'wall_s':>9} "
        f"{'ici_MB':>9} {'dcn_MB':>9} {'xfer_MB':>9} {'dumps':>6}"
    )
    for h in hosts:
        def _mb(v):
            return f"{v / 1e6:.2f}" if isinstance(v, (int, float)) else "-"

        off = h.get("clock_offset_s")
        wall = h.get("wall_s")
        lines.append(
            f"  {h.get('host', '?'):<16} "
            f"{off if off is not None else '-':>9} "
            f"{f'{wall:.3f}' if isinstance(wall, (int, float)) else '-':>9} "
            f"{_mb(h.get('ici_bytes')):>9} "
            f"{_mb(h.get('dcn_bytes')):>9} "
            f"{_mb(h.get('transfer_bytes')):>9} "
            f"{h.get('dumps', 0):>6}"
        )
    stages = rec.get("stages") or {}
    if stages:
        names = [h.get("host", "?") for h in hosts]
        header = "  " + f"{'stage':<26}" + "".join(
            f"{n[:12]:>13}" for n in names
        ) + f"{'median':>10}{'max':>10}"
        lines.append("stage walls (s):")
        lines.append(header)
        flagged = {
            (s["stage"], s["host"]) for s in rec.get("stragglers") or ()
        }
        for nm, st in stages.items():
            cells = []
            for n in names:
                w = (st.get("walls") or {}).get(n)
                cells.append(
                    f"{w:.3f}" if isinstance(w, (int, float)) else "-"
                )
            row = f"  {nm:<26}" + "".join(f"{c:>13}" for c in cells)
            row += f"{st.get('median_s'):>10}{st.get('max_s'):>10}"
            if any((nm, n) in flagged for n in names):
                row += "  << STRAGGLER"
            lines.append(row)
    for s in rec.get("stragglers") or ():
        lines.append(
            f"STRAGGLER: {s['stage']} on {s['host']}: {s['wall_s']}s "
            f"vs median {s['median_s']}s (x{s['ratio']})"
        )
    if not rec.get("stragglers"):
        lines.append("no stragglers")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Distributed-trace timeline (ISSUE 17) — pure dict functions; the
# `prove_report.py --timeline` payoff surface. Merge N per-host
# artifacts, align their clocks with the same barrier stamps fleet_merge
# uses, stitch spans into per-trace event lists, and render either an
# ASCII swimlane or Chrome trace-event JSON loadable in Perfetto.
# ---------------------------------------------------------------------------

TIMELINE_KIND = "boojum_tpu.timeline"
TIMELINE_SCHEMAS = (1,)
# bucket for events whose line predates schema 4 (or lost its context):
# still rendered, clearly labeled as unstitched
UNTRACED = "untraced"


def _timeline_line_events(label: str, d: dict, off: float) -> list:
    """Flatten one ProveReport line's span tree into absolute-time span
    events. The line's `unix_ts` is stamped when the recorder CLOSES,
    so the recording started at unix_ts - wall_s; each span sits at its
    start_s offset from there (queue.wait's negative, backdated start
    lands it before the recording window — exactly where the wait
    happened). `off` is the host's barrier-derived clock offset."""
    unix_ts, wall = d.get("unix_ts"), d.get("wall_s")
    if not (
        isinstance(unix_ts, (int, float)) and isinstance(wall, (int, float))
    ):
        return []
    t0_abs = float(unix_ts) - float(wall) - off
    line_tid = (d.get("trace_ctx") or {}).get("trace_id")
    out: list = []

    def _walk(sp, tid):
        if not isinstance(sp, dict):
            return
        if isinstance(sp.get("trace_id"), str):
            tid = sp["trace_id"]
        attrs = sp.get("attrs") or {}
        # batch-scoped work (scheduler warm spans) recorded OUTSIDE the
        # request's scoped recorder joins the trace via an explicit
        # `trace` attr stamped by the scheduler
        a_tid = attrs.get("trace")
        if isinstance(a_tid, str) and TRACE_ID_RE.match(a_tid):
            tid = a_tid
        start, w = sp.get("start_s"), sp.get("wall_s")
        if isinstance(start, (int, float)) and isinstance(w, (int, float)):
            ev = {
                "name": sp.get("name"),
                "host": label,
                "label": d.get("label"),
                "trace_id": tid,
                "span_id": sp.get("span_id"),
                "parent_span_id": sp.get("parent_span_id"),
                "t_s": round(t0_abs + float(start), 6),
                "wall_s": float(w),
            }
            for k in ("sync_s", "overlap_s", "error"):
                if k in sp:
                    ev[k] = sp[k]
            out.append(ev)
        for c in sp.get("children") or ():
            _walk(c, tid)

    for root in d.get("spans") or ():
        _walk(root, line_tid)
    return out


def _timeline_line_counters(label: str, d: dict, off: float) -> list:
    """Telemetry samples as absolute-time counter points (Perfetto "C"
    tracks). Needs the sampler's `t0_unix_ts` anchor (schema 4,
    utils/telemetry.py) — samples only carry monotonic offsets."""
    tele = d.get("telemetry")
    if not isinstance(tele, dict):
        return []
    anchor = tele.get("t0_unix_ts")
    if not isinstance(anchor, (int, float)):
        return []
    out = []
    for s in tele.get("samples") or ():
        if not isinstance(s, dict):
            continue
        t = s.get("t_s")
        if not isinstance(t, (int, float)):
            continue
        ts = round(float(anchor) + float(t) - off, 6)
        for k, v in s.items():
            if k == "t_s" or not isinstance(v, (int, float)):
                continue
            out.append({"host": label, "name": k, "t_s": ts, "value": v})
    return out


def _timeline_blackbox_event(label: str, d: dict, off: float):
    """A heartbeat/dump line as an instant event: incidents join the
    timeline via the trace/open-span ids the blackbox stamps."""
    unix_ts = d.get("unix_ts")
    if not isinstance(unix_ts, (int, float)):
        return None
    record = d.get("record")
    name = f"blackbox.{record}"
    if record == "dump" and d.get("reason"):
        name = f"blackbox.{d['reason']}"
    ev = {
        "instant": record,
        "name": name,
        "host": label,
        "t_s": round(float(unix_ts) - off, 6),
    }
    for k in ("trace_id", "span_id", "span", "phase", "reason"):
        if d.get(k):
            ev[k] = d[k]
    return ev


def timeline_merge(
    host_docs: list,
    straggler_ratio: float = 1.5,
    min_abs_s: float = 0.05,
) -> dict:
    """Stitch per-host artifacts into ONE timeline record. `host_docs`
    is [(label, [parsed lines...]), ...] — report JSONL, multihost
    result lines, blackbox sidecars, in any mix.

    Clock alignment: identical to fleet_merge — hosts that stamped a
    barrier-synchronized `clock_sync.barrier_unix_ts` all passed the
    same collective at the same instant, so stamp differences ARE the
    skews; every host's events shift by its offset from the earliest
    host. Without two stamped hosts, events stay on raw wall clocks
    (noted in `clock`).

    Straggler rule (per trace): a span name appearing on >= 2 hosts
    flags its slowest host when it exceeds straggler_ratio x the
    across-host median by at least min_abs_s."""
    stamps: dict = {}
    for lbl, docs in host_docs:
        for d in docs:
            if not isinstance(d, dict):
                continue
            cs = d.get("clock_sync")
            if isinstance(cs, dict) and isinstance(
                cs.get("barrier_unix_ts"), (int, float)
            ):
                stamps[lbl] = float(cs["barrier_unix_ts"])
    if len(stamps) >= 2:
        t0c = min(stamps.values())
        offsets = {h: round(s - t0c, 6) for h, s in stamps.items()}
        clock = {
            "method": "barrier",
            "max_skew_s": round(max(stamps.values()) - t0c, 6),
        }
    else:
        offsets = {}
        clock = {
            "method": "none",
            "note": (
                "fewer than 2 hosts carry clock_sync.barrier_unix_ts; "
                "events are on raw per-host wall clocks"
            ),
        }
    events: list = []
    marks: list = []
    counters: list = []
    for lbl, docs in host_docs:
        off = offsets.get(lbl, 0.0)
        for d in docs:
            if not isinstance(d, dict):
                continue
            kind = d.get("kind")
            if kind == REPORT_KIND:
                events.extend(_timeline_line_events(lbl, d, off))
                counters.extend(_timeline_line_counters(lbl, d, off))
            elif kind == BLACKBOX_KIND:
                ev = _timeline_blackbox_event(lbl, d, off)
                if ev is not None:
                    events.append(ev)
            elif "pid" in d and isinstance(d.get("clock_sync"), dict):
                ts = d["clock_sync"].get("barrier_unix_ts")
                if isinstance(ts, (int, float)):
                    # aligned barrier instants from every host coincide
                    # by construction — the visual proof the alignment
                    # worked when loaded in Perfetto
                    marks.append(
                        {
                            "instant": "clock_sync",
                            "name": "clock_sync.barrier",
                            "host": lbl,
                            "t_s": round(float(ts) - off, 6),
                        }
                    )
    # telemetry snapshots overlap across lines from the same sampler —
    # dedupe counter points on (host, series, timestamp)
    seen_pts = set()
    uniq_counters = []
    for c in counters:
        key = (c["host"], c["name"], c["t_s"])
        if key not in seen_pts:
            seen_pts.add(key)
            uniq_counters.append(c)
    counters = sorted(uniq_counters, key=lambda c: c["t_s"])
    # group into per-trace event lists; instants without a trace id are
    # global marks
    by_trace: dict = {}
    for ev in events:
        tid = ev.get("trace_id")
        if not tid and ev.get("instant"):
            marks.append(ev)
            continue
        by_trace.setdefault(tid or UNTRACED, []).append(ev)
    traces: list = []
    all_stragglers: list = []
    for tid, evs in by_trace.items():
        evs.sort(key=lambda e: (e["t_s"], -e.get("wall_s", 0.0)))
        t0 = min(e["t_s"] for e in evs)
        t1 = max(e["t_s"] + e.get("wall_s", 0.0) for e in evs)
        span_evs = [e for e in evs if "wall_s" in e]
        # per-name across-host straggler attribution within the trace
        by_name: dict = {}
        for e in span_evs:
            walls = by_name.setdefault(e["name"], {})
            walls[e["host"]] = max(walls.get(e["host"], 0.0), e["wall_s"])
        stragglers = []
        for nm in sorted(by_name):
            walls = by_name[nm]
            if len(walls) < 2:
                continue
            med = _percentile(sorted(walls.values()), 0.5)
            max_host = max(walls, key=walls.get)
            mx = walls[max_host]
            if (
                med > 0
                and mx > med * straggler_ratio
                and (mx - med) >= min_abs_s
            ):
                stragglers.append(
                    {
                        "span": nm,
                        "host": max_host,
                        "wall_s": round(mx, 6),
                        "median_s": round(med, 6),
                        "ratio": round(mx / med, 4),
                    }
                )
                for e in span_evs:
                    if (
                        e["name"] == nm
                        and e["host"] == max_host
                        and e["wall_s"] == mx
                    ):
                        e["straggler"] = True
        for s in stragglers:
            all_stragglers.append(dict(s, trace_id=tid))
        traces.append(
            {
                "trace_id": tid,
                "t0_unix_ts": round(t0, 6),
                "wall_s": round(t1 - t0, 6),
                "hosts": sorted({e["host"] for e in evs}),
                "n_spans": len(span_evs),
                "n_instants": len(evs) - len(span_evs),
                "events": evs,
                "stragglers": stragglers,
            }
        )
    # chronological, with the untraced bucket last
    traces.sort(
        key=lambda t: (t["trace_id"] == UNTRACED, t["t0_unix_ts"])
    )
    hosts = sorted({lbl for lbl, _docs in host_docs})
    return {
        "kind": TIMELINE_KIND,
        "schema": TIMELINE_SCHEMAS[-1],
        "unix_ts": time.time(),
        "hosts": hosts,
        "clock": clock,
        "offsets": offsets,
        "n_traces": len(traces),
        "traces": traces,
        "marks": sorted(marks, key=lambda m: m["t_s"]),
        "counters": counters,
        "stragglers": all_stragglers,
    }


def _event_depth(ev: dict, by_id: dict, limit: int = 12) -> int:
    depth = 0
    cur = ev
    while depth < limit:
        psid = cur.get("parent_span_id")
        if not psid or psid not in by_id:
            break
        cur = by_id[psid]
        depth += 1
    return depth


def render_timeline(rec: dict, width: int = 48, max_rows: int = 48) -> str:
    """ASCII swimlane per trace: one row per span (indented by stitch
    depth), a scaled `=` bar positioned in the trace's window, instants
    as `!` markers, stragglers flagged."""
    lines = []
    clock = rec.get("clock") or {}
    skew = clock.get("max_skew_s")
    lines.append(
        f"timeline: {len(rec.get('hosts') or ())} hosts, "
        f"{rec.get('n_traces')} traces, clock={clock.get('method')}"
        + (f" (max skew {skew}s)" if skew is not None else "")
    )
    if clock.get("note"):
        lines.append(f"  note: {clock['note']}")
    for off_host in sorted(rec.get("offsets") or {}):
        lines.append(
            f"  offset {off_host}: +{rec['offsets'][off_host]}s"
        )
    for tr in rec.get("traces") or ():
        tid = tr.get("trace_id") or "?"
        head = tid if tid == UNTRACED else tid[:8]
        lines.append(
            f"trace {head}: {len(tr.get('hosts') or ())} host(s), "
            f"{tr.get('wall_s')}s, {tr.get('n_spans')} spans, "
            f"{tr.get('n_instants')} instants"
        )
        evs = tr.get("events") or []
        by_id = {
            e["span_id"]: e for e in evs if e.get("span_id")
        }
        t0 = tr.get("t0_unix_ts", 0.0)
        dur = max(tr.get("wall_s") or 0.0, 1e-9)
        shown = evs[:max_rows]
        for ev in shown:
            sidx = int((ev["t_s"] - t0) / dur * width)
            sidx = min(max(sidx, 0), width - 1)
            if "wall_s" in ev:
                slen = max(1, int(ev["wall_s"] / dur * width))
                slen = min(slen, width - sidx)
                bar = "." * sidx + "=" * slen
                tail = f" {ev['wall_s']:.3f}s"
            else:
                bar = "." * sidx + "!"
                tail = ""
            bar = bar.ljust(width, ".")
            depth = _event_depth(ev, by_id)
            name = "  " * depth + str(ev.get("name"))
            flag = ""
            if ev.get("straggler"):
                flag = " <- straggler"
            if ev.get("error"):
                flag += f" [error: {ev['error']}]"
            lines.append(
                f"  {ev.get('host', '?'):<12} {name:<28.28} "
                f"[{bar}]{tail}{flag}"
            )
        if len(evs) > len(shown):
            lines.append(f"  ... {len(evs) - len(shown)} more events")
        for s in tr.get("stragglers") or ():
            lines.append(
                f"  straggler: {s['span']} on {s['host']} "
                f"({s['wall_s']}s vs median {s['median_s']}s, "
                f"x{s['ratio']})"
            )
    return "\n".join(lines)


def perfetto_events(rec: dict) -> dict:
    """A timeline record as Chrome trace-event JSON (the format Perfetto
    and chrome://tracing load): hosts become processes, traces become
    threads, spans become "X" complete events, dumps/heartbeats/barrier
    marks become "i" instants, telemetry series become "C" counters.
    Timestamps are microseconds from the earliest stitched event."""
    traces = rec.get("traces") or []
    marks = rec.get("marks") or []
    counters = rec.get("counters") or []
    all_ts = (
        [e["t_s"] for tr in traces for e in tr.get("events") or ()]
        + [m["t_s"] for m in marks]
        + [c["t_s"] for c in counters]
    )
    base = min(all_ts) if all_ts else 0.0

    def _us(t):
        return round(max(t - base, 0.0) * 1e6, 3)

    host_pid = {h: i + 1 for i, h in enumerate(rec.get("hosts") or ())}
    out = []
    for h, pid in host_pid.items():
        out.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": h},
            }
        )
    for ti, tr in enumerate(traces):
        tid_n = ti + 1
        label = tr.get("trace_id") or "?"
        if label != UNTRACED:
            label = f"trace {label[:8]}"
        for h in tr.get("hosts") or ():
            out.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": host_pid.get(h, 0),
                    "tid": tid_n,
                    "args": {"name": label},
                }
            )
        for ev in tr.get("events") or ():
            pid = host_pid.get(ev.get("host"), 0)
            args = {
                k: ev[k]
                for k in (
                    "trace_id",
                    "span_id",
                    "parent_span_id",
                    "host",
                    "label",
                    "sync_s",
                    "overlap_s",
                    "error",
                    "straggler",
                    "span",
                    "phase",
                    "reason",
                )
                if ev.get(k) is not None
            }
            if "wall_s" in ev:
                out.append(
                    {
                        "name": str(ev.get("name")),
                        "ph": "X",
                        "cat": "span",
                        "ts": _us(ev["t_s"]),
                        "dur": round(max(ev["wall_s"], 0.0) * 1e6, 3),
                        "pid": pid,
                        "tid": tid_n,
                        "args": args,
                    }
                )
            else:
                out.append(
                    {
                        "name": str(ev.get("name")),
                        "ph": "i",
                        "s": "t",
                        "cat": "blackbox",
                        "ts": _us(ev["t_s"]),
                        "pid": pid,
                        "tid": tid_n,
                        "args": args,
                    }
                )
    for m in marks:
        out.append(
            {
                "name": str(m.get("name")),
                "ph": "i",
                "s": "p",
                "cat": "mark",
                "ts": _us(m["t_s"]),
                "pid": host_pid.get(m.get("host"), 0),
                "tid": 0,
                "args": {
                    k: m[k]
                    for k in ("host", "span", "phase", "reason")
                    if m.get(k) is not None
                },
            }
        )
    for c in counters:
        out.append(
            {
                "name": str(c["name"]),
                "ph": "C",
                "ts": _us(c["t_s"]),
                "pid": host_pid.get(c.get("host"), 0),
                "tid": 0,
                "args": {"value": c["value"]},
            }
        )
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def validate_perfetto(doc: dict) -> list[str]:
    """Sanity gate for emitted Chrome trace-event JSON (the ci_gate
    --timeline leg's bar): a traceEvents list whose every event has a
    name, a known phase, non-negative numeric timestamps, and — for
    "X" complete events — a non-negative duration."""
    problems: list[str] = []
    if not isinstance(doc, dict) or not isinstance(
        doc.get("traceEvents"), list
    ):
        return ["traceEvents missing"]
    evs = doc["traceEvents"]
    if not evs:
        problems.append("traceEvents empty")
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        if not (isinstance(ev.get("name"), str) and ev["name"]):
            problems.append(f"event {i}: name missing")
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "C"):
            problems.append(f"event {i}: ph invalid: {ph!r}")
        if not isinstance(ev.get("pid"), int):
            problems.append(f"event {i}: pid invalid: {ev.get('pid')!r}")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts != ts or ts < 0:
            problems.append(f"event {i}: ts invalid: {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur != dur or dur < 0:
                problems.append(f"event {i}: dur invalid: {dur!r}")
        if ph == "i" and ev.get("s") not in ("t", "p", "g"):
            problems.append(f"event {i}: instant scope invalid: {ev.get('s')!r}")
        if len(problems) > 25:
            problems.append("... (truncated)")
            break
    return problems


def diff_reports(a: dict, b: dict, top: int = 10) -> dict:
    """Regression-triage diff: per-span wall deltas (matched by tree path,
    repeated paths summed) and the FIRST diverging digest checkpoint."""

    def _span_walls(report):
        walls: dict[str, float] = {}
        for path, sp in flatten_spans(report):
            walls[path] = walls.get(path, 0.0) + (sp.get("wall_s") or 0.0)
        return walls

    wa, wb = _span_walls(a), _span_walls(b)
    deltas = []
    for path in sorted(set(wa) | set(wb)):
        va, vb = wa.get(path), wb.get(path)
        deltas.append(
            {
                "span": path,
                "a_s": None if va is None else round(va, 6),
                "b_s": None if vb is None else round(vb, 6),
                "delta_s": (
                    None
                    if va is None or vb is None
                    else round(vb - va, 6)
                ),
            }
        )
    # real deltas first (largest |delta| on top); spans present in only one
    # report sort LAST — they must never crowd genuine regressions out of
    # the top-N window
    deltas.sort(
        key=lambda d: (
            d["delta_s"] is None,
            -abs(d["delta_s"]) if d["delta_s"] is not None else 0.0,
        )
    )

    ca = a.get("checkpoints") or []
    cb = b.get("checkpoints") or []
    first_div = None
    for ea, eb in zip(ca, cb):
        if (
            ea.get("label") != eb.get("label")
            or ea.get("round") != eb.get("round")
            or ea.get("digest") != eb.get("digest")
        ):
            first_div = {
                "seq": ea.get("seq"),
                "round": ea.get("round"),
                "label": ea.get("label"),
                "a_digest": ea.get("digest"),
                "b_digest": eb.get("digest"),
                "b_label": eb.get("label"),
            }
            break
    if first_div is None and len(ca) != len(cb):
        longer = ca if len(ca) > len(cb) else cb
        e = longer[min(len(ca), len(cb))]
        first_div = {
            "seq": e.get("seq"),
            "round": e.get("round"),
            "label": e.get("label"),
            "a_digest": e.get("digest") if len(ca) > len(cb) else None,
            "b_digest": e.get("digest") if len(cb) > len(ca) else None,
            "length_mismatch": [len(ca), len(cb)],
        }

    def _counters(r):
        return (r.get("metrics") or {}).get("counters") or {}

    na, nb = _counters(a), _counters(b)
    counter_deltas = {
        k: [na.get(k), nb.get(k)]
        for k in sorted(set(na) | set(nb))
        if na.get(k) != nb.get(k)
    }

    # cost-record diff (ISSUE 12 satellite): per-stage roofline
    # efficiency deltas alongside the wall deltas — "round3 got slower"
    # and "round3 got FURTHER from peak" are different regressions
    def _cost_stages(r):
        c = r.get("cost")
        return (c.get("stages") or {}) if isinstance(c, dict) else {}

    sa, sb_ = _cost_stages(a), _cost_stages(b)
    cost_deltas = {}
    for st in sorted(set(sa) | set(sb_)):
        ea = sa.get(st) if isinstance(sa.get(st), dict) else {}
        eb = sb_.get(st) if isinstance(sb_.get(st), dict) else {}
        fa, fb = ea.get("efficiency"), eb.get("efficiency")
        ga, gb = ea.get("achieved_gflops"), eb.get("achieved_gflops")
        if fa is None and fb is None and ga is None and gb is None:
            continue
        ent = {
            "efficiency": [fa, fb],
            "achieved_gflops": [ga, gb],
            "regime": [ea.get("regime"), eb.get("regime")],
        }
        if isinstance(fa, (int, float)) and isinstance(fb, (int, float)):
            ent["efficiency_delta"] = round(fb - fa, 6)
        cost_deltas[st] = ent

    return {
        "wall_a_s": a.get("wall_s"),
        "wall_b_s": b.get("wall_s"),
        "span_deltas": deltas[:top],
        "first_checkpoint_divergence": first_div,
        "num_checkpoints": [len(ca), len(cb)],
        "counter_deltas": counter_deltas,
        "cost_deltas": cost_deltas,
    }


def _percentile(sorted_vals: list[float], q: float) -> float | None:
    """Nearest-rank percentile over an already-sorted list (stdlib-only,
    deterministic; None on empty input)."""
    if not sorted_vals:
        return None
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def slo_summary(reports: list[dict]) -> dict:
    """Aggregate the per-request SLO records of a proving-service report
    artifact: p50/p95 queue latency and prove wall, overall proofs/sec
    (served count over the submit-to-done span), per-placement and
    per-priority counts, cache hit rate. Lines without a `request`
    record (plain proves, bench reps) are ignored."""
    reqs = [r["request"] for r in reports
            if isinstance(r.get("request"), dict)]
    ok = [q for q in reqs if "error" not in q]
    lat = sorted(
        q["queue_latency_s"] for q in reqs
        if isinstance(q.get("queue_latency_s"), (int, float))
    )
    walls = sorted(
        q["prove_wall_s"] for q in ok
        if isinstance(q.get("prove_wall_s"), (int, float))
    )
    # the artifact's serving span: earliest request START (each line is
    # stamped at completion, so start = unix_ts - the recording wall) to
    # the last completion — anchoring at the first COMPLETION would drop
    # that request's entire service time and overstate proofs/sec by
    # N/(N-1)
    starts = []
    ends = []
    for r in reports:
        if not isinstance(r.get("request"), dict):
            continue
        ts = r.get("unix_ts")
        if not isinstance(ts, (int, float)):
            continue
        wall = r.get("wall_s")
        starts.append(ts - (wall if isinstance(wall, (int, float)) else 0))
        ends.append(ts)
    span_s = (max(ends) - min(starts)) if ends else None
    total_wall = sum(walls)
    placements: dict[str, int] = {}
    priorities: dict[str, int] = {}
    cache_hits = 0
    for q in reqs:
        placements[str(q.get("placement"))] = (
            placements.get(str(q.get("placement")), 0) + 1
        )
        priorities[str(q.get("priority"))] = (
            priorities.get(str(q.get("priority")), 0) + 1
        )
        if q.get("cache_hit"):
            cache_hits += 1

    def r6(v):
        return None if v is None else round(v, 6)

    # per-tenant axis (ISSUE 11): latency/wall percentiles per tenant id
    # over the request records, plus the gateway's rejected admissions
    # (tenant records with `rejected` set: 429 quota throttles and
    # load-sheds) — the fairness/quota numbers a multi-tenant deploy
    # watches
    tenants: dict[str, dict] = {}

    def _tslot(tid: str) -> dict:
        return tenants.setdefault(
            tid, {"requests": 0, "lat": [], "walls": [], "rejected": 0}
        )

    for q in reqs:
        slot = _tslot(str(q.get("tenant", "default")))
        slot["requests"] += 1
        if isinstance(q.get("queue_latency_s"), (int, float)):
            slot["lat"].append(q["queue_latency_s"])
        if "error" not in q and isinstance(
            q.get("prove_wall_s"), (int, float)
        ):
            slot["walls"].append(q["prove_wall_s"])
    shed = {"throttled": 0, "shed": 0}
    for r in reports:
        t = r.get("tenant")
        if not isinstance(t, dict) or not t.get("rejected"):
            continue
        _tslot(str(t.get("id", "default")))["rejected"] += 1
        reason = t.get("reason")
        if reason not in shed:
            # legacy/foreign lines without a reason: classify by code
            reason = "throttled" if t.get("rejected") == 429 else "shed"
        shed[reason] += 1
    tenant_summary = {
        tid: {
            "requests": s["requests"],
            "rejected": s["rejected"],
            "queue_latency_p95_s": r6(_percentile(sorted(s["lat"]), 0.95)),
            "prove_wall_p95_s": r6(_percentile(sorted(s["walls"]), 0.95)),
        }
        for tid, s in sorted(tenants.items())
    }

    # artifact-hit rate over the artifact's lines: every aot.hits /
    # aot.misses counter recorded anywhere in the stream (service warm
    # phases, bench warm-ups) — the deployment-health axis the AOT
    # bundle store adds
    aot_hits = aot_misses = 0
    resident_lines = 0
    for r in reports:
        c = (r.get("metrics") or {}).get("counters") or {}
        if isinstance(c, dict):
            h, m = c.get("aot.hits", 0), c.get("aot.misses", 0)
            # skip malformed values like every other field here — one
            # junk line must not kill the whole --slo summary
            aot_hits += h if isinstance(h, (int, float)) else 0
            aot_misses += m if isinstance(m, (int, float)) else 0
            rs = c.get("quotient.resident_coset_sweeps", 0)
            if isinstance(rs, (int, float)) and rs > 0:
                resident_lines += 1

    # roofline axis (ISSUE 12): aggregate per-stage efficiency over the
    # lines carrying a cost record — the "how far from the hardware"
    # number next to the wall percentiles
    cost_lines = 0
    stage_eff: dict[str, list] = {}
    stage_regimes: dict[str, dict] = {}
    # field-backend axis (ISSUE 20): which field each line proved under —
    # bench lines stamp it top-level, report lines carry it in the cost
    # record; a babybear deploy's wall/byte numbers are not comparable to
    # goldilocks ones, so the summary names the split
    field_lines: dict[str, int] = {}
    for r in reports:
        c = r.get("cost")
        fld = r.get("field") or (
            c.get("field") if isinstance(c, dict) else None
        )
        if isinstance(fld, str):
            field_lines[fld] = field_lines.get(fld, 0) + 1
        if not isinstance(c, dict):
            continue
        cost_lines += 1
        for st, ent in (c.get("stages") or {}).items():
            if not isinstance(ent, dict):
                continue
            eff = ent.get("efficiency")
            if isinstance(eff, (int, float)) and eff == eff:
                stage_eff.setdefault(st, []).append(float(eff))
            reg = ent.get("regime")
            if isinstance(reg, str):
                slot = stage_regimes.setdefault(st, {})
                slot[reg] = slot.get(reg, 0) + 1
    roofline_summary = {
        "lines": cost_lines,
        "stages": {
            st: {
                "mean_efficiency": round(sum(v) / len(v), 6),
                "regimes": dict(sorted(stage_regimes.get(st, {}).items())),
            }
            for st, v in sorted(stage_eff.items())
        },
    }

    return {
        # which representation served: lines whose kernels dispatched
        # limb-RESIDENT (ISSUE 10) — BENCH/SLO deltas are attributable
        "limb_resident_lines": resident_lines,
        # field backend per line (ISSUE 20), e.g. {"babybear": 3}
        "fields": dict(sorted(field_lines.items())),
        "requests": len(reqs),
        "served": len(ok),
        "failed": len(reqs) - len(ok),
        "queue_latency_p50_s": r6(_percentile(lat, 0.50)),
        "queue_latency_p95_s": r6(_percentile(lat, 0.95)),
        "prove_wall_p50_s": r6(_percentile(walls, 0.50)),
        "prove_wall_p95_s": r6(_percentile(walls, 0.95)),
        # proofs/sec over the serving span when the artifact covers more
        # than one completion; else the sequential-throughput bound
        "proofs_per_sec": r6(
            len(ok) / span_s if span_s and span_s > 0
            else (len(ok) / total_wall if total_wall > 0 else None)
        ),
        "placements": dict(sorted(placements.items())),
        "priorities": dict(sorted(priorities.items())),
        "cache_hit_rate": (
            round(cache_hits / len(reqs), 4) if reqs else None
        ),
        "tenants": tenant_summary,
        "rejected": shed,
        "roofline": roofline_summary,
        "aot_kernels_warmed": aot_hits + aot_misses,
        "aot_hit_rate": (
            round(aot_hits / (aot_hits + aot_misses), 4)
            if (aot_hits + aot_misses)
            else None
        ),
    }


def render_slo(summary: dict) -> str:
    lines = [
        f"service SLO: {summary['requests']} requests "
        f"({summary['served']} served, {summary['failed']} failed)",
        f"  queue latency p50={summary['queue_latency_p50_s']}s "
        f"p95={summary['queue_latency_p95_s']}s",
        f"  prove wall    p50={summary['prove_wall_p50_s']}s "
        f"p95={summary['prove_wall_p95_s']}s",
        f"  proofs/sec    {summary['proofs_per_sec']}",
        f"  cache hit rate {summary['cache_hit_rate']}",
    ]
    if summary.get("aot_kernels_warmed"):
        lines.append(
            f"  aot artifacts {summary['aot_hit_rate']} hit rate over "
            f"{summary['aot_kernels_warmed']} warmed kernels"
        )
    if summary.get("limb_resident_lines"):
        lines.append(
            f"  limb-resident {summary['limb_resident_lines']} lines "
            f"dispatched the resident kernel set"
        )
    if summary.get("fields"):
        lines.append(
            "  field backend "
            + ", ".join(
                f"{k}={v}" for k, v in summary["fields"].items()
            )
        )
    if summary.get("placements"):
        lines.append(
            "  placements    "
            + ", ".join(
                f"{k}={v}" for k, v in summary["placements"].items()
            )
        )
    if summary.get("priorities"):
        lines.append(
            "  priorities    "
            + ", ".join(
                f"{k}={v}" for k, v in summary["priorities"].items()
            )
        )
    roof = summary.get("roofline") or {}
    if roof.get("lines"):
        lines.append(
            f"  roofline      {roof['lines']} line(s) with cost records"
        )
        for st, ent in (roof.get("stages") or {}).items():
            regimes = ",".join(
                f"{k}={v}" for k, v in (ent.get("regimes") or {}).items()
            )
            lines.append(
                f"    {st:<24} mean eff "
                f"{100 * ent['mean_efficiency']:.2f}%"
                + (f" [{regimes}]" if regimes else "")
            )
    rejected = summary.get("rejected") or {}
    if any(rejected.values()):
        lines.append(
            f"  rejected      throttled(429)={rejected.get('throttled', 0)} "
            f"shed={rejected.get('shed', 0)}"
        )
    for tid, t in (summary.get("tenants") or {}).items():
        lines.append(
            f"  tenant {tid:<12} {t['requests']} requests, "
            f"queue p95={t['queue_latency_p95_s']}s "
            f"wall p95={t['prove_wall_p95_s']}s, "
            f"rejected={t['rejected']}"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Rendering
# ---------------------------------------------------------------------------


def render_report(report: dict, top: int = 10) -> str:
    lines = []
    wall = report.get("wall_s") or 0.0
    lines.append(
        f"ProveReport schema={report.get('schema')} "
        f"label={report.get('label')!r} wall={wall:.3f}s "
        f"coverage={span_coverage(report) * 100:.1f}%"
    )
    spans = report.get("spans") or []

    def _emit(sp, depth):
        w = sp.get("wall_s") or 0.0
        pct = f"{100 * w / wall:5.1f}%" if wall else "     "
        extras = ""
        if sp.get("sync_s"):
            extras += f" sync={sp['sync_s']:.3f}s"
            if w:
                # occupancy: how much of the span the host spent BLOCKED
                # on the device (sync_s/wall) — the overlapped pipeline's
                # regression signal
                extras += f" occ={100 * sp['sync_s'] / w:.0f}%"
        if sp.get("overlap_s"):
            extras += f" ovl={sp['overlap_s']:.3f}s"
        attrs = sp.get("attrs")
        if isinstance(attrs, dict) and attrs.get("resident"):
            # the limb-residency flag (ISSUE 10): which representation
            # this span's kernels computed in, visible in the tree
            extras += " resident"
        if sp.get("error"):
            extras += f" ERROR={sp['error']!r}"
        lines.append(
            f"  {'  ' * depth}{sp.get('name'):<{max(4, 40 - 2 * depth)}}"
            f"{w:9.3f}s {pct}{extras}"
        )
        for c in sp.get("children", ()):
            _emit(c, depth + 1)

    for sp in spans:
        _emit(sp, 0)

    flat = [
        (path, sp.get("wall_s") or 0.0, sp.get("sync_s") or 0.0)
        for path, sp in flatten_spans(report)
        if not sp.get("children")
    ]
    flat.sort(key=lambda t: -t[1])
    if flat:
        lines.append(f"  top {min(top, len(flat))} leaf spans:")
        for path, w, s in flat[:top]:
            occ = f" sync={s:.3f}s occ={100 * s / w:.0f}%" if s and w else ""
            lines.append(f"    {w:9.3f}s{occ}  {path}")

    counters = (report.get("metrics") or {}).get("counters") or {}
    if counters:
        lines.append("  counters:")
        for k, v in counters.items():
            lines.append(f"    {k} = {v}")
    gauges = (report.get("metrics") or {}).get("gauges") or {}
    if gauges:
        lines.append("  gauges:")
        for k, v in gauges.items():
            lines.append(f"    {k} = {v}")
    ckpts = report.get("checkpoints") or []
    lines.append(f"  checkpoints: {len(ckpts)}")
    for e in ckpts:
        lines.append(
            f"    [{e.get('seq'):>3}] r{e.get('round')} "
            f"{e.get('label'):<28} {str(e.get('digest'))[:16]}…"
        )
    telemetry = report.get("telemetry")
    if isinstance(telemetry, dict):
        samples = telemetry.get("samples") or []
        keys = sorted(
            {k for s in samples if isinstance(s, dict) for k in s}
            - {"t_s"}
        )
        lines.append(
            f"  telemetry: {len(samples)} samples @ "
            f"{telemetry.get('interval_s')}s "
            f"({telemetry.get('ticks')} ticks) keys={keys}"
        )
    trace = report.get("trace")
    if isinstance(trace, dict):
        lines.append(f"  profiler trace: {trace.get('dir')}")
    cost = report.get("cost")
    if isinstance(cost, dict):
        tot = cost.get("total") or {}
        lines.append(
            f"  cost: total {tot.get('achieved_gflops')} GFLOP/s, "
            f"{tot.get('achieved_gbps')} GB/s, "
            f"regime={tot.get('regime')} "
            f"eff={tot.get('efficiency')} (--roofline for the "
            f"per-stage table)"
        )
    request = report.get("request")
    if isinstance(request, dict):
        lines.append(
            f"  request: {request.get('id')} "
            f"[{request.get('priority')}/{request.get('tenant')}] "
            f"bucket={request.get('bucket')} "
            f"placement={request.get('placement')} "
            f"queue={request.get('queue_latency_s')}s "
            f"wall={request.get('prove_wall_s')}s "
            f"cache_hit={request.get('cache_hit')}"
        )
    ledger = report.get("compile_ledger")
    if ledger:
        lines.append(
            f"  compile ledger: {ledger.get('num_kernels')} kernels, "
            f"precompile {ledger.get('precompile_total_s')}s, "
            f"{ledger.get('num_dispatch_compiles')} dispatch compiles"
        )
        hits = ledger.get("aot_hits") or 0
        misses = ledger.get("aot_misses") or 0
        if hits + misses:
            lines.append(
                f"  aot artifacts: {hits}/{hits + misses} kernels "
                f"deserialized "
                f"({100 * hits / (hits + misses):.1f}% hit rate), "
                f"deserialize {ledger.get('aot_deserialize_s')}s"
            )
    return "\n".join(lines)


def render_diff(diff: dict) -> str:
    lines = [
        f"wall: {diff.get('wall_a_s')}s -> {diff.get('wall_b_s')}s",
        f"checkpoints: {diff['num_checkpoints'][0]} vs "
        f"{diff['num_checkpoints'][1]}",
    ]
    fd = diff.get("first_checkpoint_divergence")
    if fd is None:
        lines.append("digest checkpoints: IDENTICAL (no divergence)")
    else:
        lines.append(
            f"FIRST DIVERGING CHECKPOINT: seq={fd.get('seq')} "
            f"round={fd.get('round')} label={fd.get('label')!r}"
        )
        lines.append(
            f"  a={fd.get('a_digest')}\n  b={fd.get('b_digest')}"
        )
        if fd.get("length_mismatch"):
            lines.append(f"  (length mismatch: {fd['length_mismatch']})")
    lines.append("span wall deltas (top by |delta|):")
    for d in diff.get("span_deltas", ()):
        a = "-" if d["a_s"] is None else f"{d['a_s']:.3f}"
        b = "-" if d["b_s"] is None else f"{d['b_s']:.3f}"
        dl = "-" if d["delta_s"] is None else f"{d['delta_s']:+.3f}"
        lines.append(f"  {dl:>10}s  {a:>9} -> {b:<9}  {d['span']}")
    if diff.get("counter_deltas"):
        lines.append("counter deltas:")
        for k, (a, b) in diff["counter_deltas"].items():
            lines.append(f"  {k}: {a} -> {b}")
    if diff.get("cost_deltas"):
        lines.append("cost (roofline) deltas:")
        for st, ent in diff["cost_deltas"].items():
            fa, fb = ent.get("efficiency", [None, None])
            dl = ent.get("efficiency_delta")
            dl_s = f" ({dl:+.4f})" if isinstance(dl, (int, float)) else ""
            ra, rb = ent.get("regime", [None, None])
            reg = ra if ra == rb else f"{ra}->{rb}"
            lines.append(
                f"  {st}: efficiency {fa} -> {fb}{dl_s} [{reg}]"
            )
    return "\n".join(lines)


def render_roofline(report: dict) -> str:
    """Render one line's `cost` record as a per-stage roofline table:
    measured wall, achieved GFLOP/s & GB/s against the device peaks,
    arithmetic intensity, regime and efficiency fraction."""
    cost = report.get("cost")
    if not isinstance(cost, dict):
        return "no cost record on this line (schema < 3, or " \
               "BOOJUM_TPU_COST=0 / no flight recorder during the prove)"
    lines = []
    dev = cost.get("device") or {}
    lines.append(
        f"roofline: device {dev.get('kind')!r} "
        f"peak {dev.get('peak_gflops')} GFLOP/s, "
        f"{dev.get('peak_hbm_gbps')} GB/s HBM"
        + (
            f", {dev.get('peak_ici_gbps')} GB/s ICI"
            if dev.get("peak_ici_gbps") else ""
        )
        + f" [{dev.get('source')}]"
    )
    header = (
        f"  {'stage':<24} {'wall_s':>10} {'GFLOP/s':>9} {'GB/s':>9}"
        f" {'int.':>8}  {'regime':<8}{'eff':>8}"
    )
    lines.append(header)

    def _num(v, nd=4):
        return f"{v:.{nd}g}" if isinstance(v, (int, float)) else "-"

    def _row(name, ent):
        if not isinstance(ent, dict):
            return
        eff = ent.get("efficiency")
        lines.append(
            f"  {name:<24}"
            f" {_num(ent.get('wall_s'), 6):>10}"
            f" {_num(ent.get('achieved_gflops')):>9}"
            f" {_num(ent.get('achieved_gbps')):>9}"
            f" {_num(ent.get('intensity_flop_per_byte')):>8}"
            f"  {ent.get('regime', '-'):<8}"
            + (f"{100 * eff:>7.2f}%" if isinstance(eff, (int, float))
               else f"{'-':>8}")
        )

    for name, ent in (cost.get("stages") or {}).items():
        _row(name, ent)
    if isinstance(cost.get("total"), dict):
        _row("TOTAL", cost["total"])
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Perf trend + regression gate (ISSUE 12): a per-stage trajectory over a
# history of artifacts — ProveReport JSONL files, bench.py JSON lines,
# the repo's BENCH_*.json round wrappers, bench_micro.py line files —
# with a gate that exits nonzero when the LAST point regresses beyond a
# noise threshold against the median of its predecessors.
# ---------------------------------------------------------------------------

# bench statuses whose value is not a steady-state measurement — an
# elapsed lower bound (no_prove) or a compile-laden warm-up wall
# (warm_only) — excluded from every trend series
_TREND_SKIP_STATUSES = ("no_prove", "warm_only")


def _trend_identity(d: dict) -> str:
    """Compact machine/software identity of one artifact line (the
    `host` block bench.py / bench_micro.py stamp): micro lines from two
    machines or jax versions must never share a gated series. The field
    backend is part of the identity too (ISSUE 20): a babybear point
    moves half the bytes of the same goldilocks geometry, so mixing the
    two in one gated series would mask (or fabricate) a regression."""
    h = d.get("host")
    parts = (
        [
            str(h.get(k))
            for k in ("host_fp", "device_kind", "backend", "jax", "jaxlib")
            if h.get(k) is not None
        ]
        if isinstance(h, dict)
        else []
    )
    cost = d.get("cost")
    fld = d.get("field") or (
        cost.get("field") if isinstance(cost, dict) else None
    )
    if fld and fld != "goldilocks":
        # goldilocks stays unsuffixed so the repo's pre-field history
        # (and the ""-identity legacy-adoption pathway) keeps gating
        parts.append(f"field={fld}")
    return "@".join(parts)


def _point_values_from_report(rep: dict) -> dict:
    values: dict = {}
    wall = rep.get("wall_s")
    if isinstance(wall, (int, float)):
        # total_wall gates prover performance, not artifact-store
        # temperature: a cold-cache process spends most of its wall in
        # aot_load/aot_warm (compile/deserialize), and gating on it
        # would fire on cache state — the same exclusion the stage
        # series get by keying on PROVE_STAGES
        w = float(wall)
        root = _prove_root(rep.get("spans"))
        for c in (root or {}).get("children", ()):
            cw = c.get("wall_s")
            if c.get("name") in CACHE_STATE_SPANS and isinstance(
                cw, (int, float)
            ):
                w -= float(cw)
        values["total_wall"] = {"value": max(0.0, w), "unit": "s"}
    for nm, w in stage_walls(
        rep.get("spans"), names=PROVE_STAGES
    ).items():
        values[f"stage:{nm}"] = {"value": w, "unit": "s"}
    cost = rep.get("cost")
    if isinstance(cost, dict):
        for st, ent in (cost.get("stages") or {}).items():
            eff = ent.get("efficiency") if isinstance(ent, dict) else None
            if isinstance(eff, (int, float)):
                values[f"efficiency:{st}"] = {
                    "value": float(eff), "unit": "frac"
                }
    # cross-host byte gauges (multi-host shard_map proves): dcn:<name>
    # series gate DCN traffic regressions on MULTICHIP rounds
    metrics = rep.get("metrics")
    if isinstance(metrics, dict):
        for k, v in (metrics.get("gauges") or {}).items():
            if (
                k.startswith("dcn.")
                and k.endswith("bytes")
                and isinstance(v, (int, float))
            ):
                values[f"dcn:{k[len('dcn.'):]}"] = {
                    "value": float(v), "unit": "B"
                }
    return values


def _point_values_from_bench(line: dict) -> dict:
    values: dict = {}
    status = str(line.get("status") or "")
    if any(s in status for s in _TREND_SKIP_STATUSES):
        return values
    v, unit = line.get("value"), str(line.get("unit") or "")
    metric = str(line.get("metric") or "")
    if isinstance(v, (int, float)):
        if unit == "s" and metric.endswith("_prove_wall"):
            values["total_wall"] = {"value": float(v), "unit": "s"}
        elif metric:
            values[metric] = {"value": float(v), "unit": unit}
    stages = line.get("stages")
    if isinstance(stages, dict):
        for nm, w in stages.items():
            if isinstance(w, (int, float)):
                values[f"stage:{nm}"] = {"value": float(w), "unit": "s"}
    # multihost worker/bench lines carrying a per-mode dcn gauge dict
    # (scripts/multihost_worker.py result stamps) feed the same dcn:
    # series as report lines
    dcn = line.get("dcn")
    if isinstance(dcn, dict):
        for k, v in dcn.items():
            if "bytes" not in k or not isinstance(v, (int, float)):
                continue
            name = k[len("dcn."):] if k.startswith("dcn.") else k
            values[f"dcn:{name}"] = {"value": float(v), "unit": "B"}
    return values


def _metric_line_from_tail(tail) -> dict | None:
    """The LAST JSON metric line embedded in a wrapper's captured
    stdout/stderr tail (bench.py emits exactly one; XLA noise around it
    is skipped). None when the run died before emitting one."""
    if not isinstance(tail, str) or not tail:
        return None
    for ln in reversed(tail.splitlines()):
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            d = json.loads(ln)
        except ValueError:
            continue
        if isinstance(d, dict) and "metric" in d:
            return d
    return None


def load_trend_file(path: str) -> list[dict]:
    """Parse ONE artifact file into trend points (usually one point; a
    bench_micro line file yields one point carrying every metric).
    Unparseable files yield an empty list — the caller reports them."""
    base = os.path.basename(path)
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return []
    lines = [ln for ln in text.splitlines() if ln.strip()]
    docs = []
    try:
        # whole-file JSON first (BENCH_*.json wrappers are indented)
        docs = [json.loads(text)]
    except ValueError:
        for ln in lines:
            try:
                docs.append(json.loads(ln))
            except ValueError:
                continue
    if not docs:
        return []
    # round wrappers: BENCH {n, cmd, rc, parsed} and MULTICHIP
    # {n_devices, rc, ok, tail}. MULTICHIP wrappers carry no `parsed`
    # block (and no `n`): the metric line — when the run got far enough
    # to emit one — is recovered from the captured `tail`, and the
    # round number from the `_rNN` filename, so multi-host history
    # rides the same ordered, identity-grouped series as BENCH rounds
    if (
        len(docs) == 1
        and isinstance(docs[0], dict)
        and ("parsed" in docs[0] or ("tail" in docs[0] and "rc" in docs[0]))
    ):
        wrapper = docs[0]
        parsed = wrapper.get("parsed")
        if not isinstance(parsed, dict):
            parsed = _metric_line_from_tail(wrapper.get("tail"))
        order = wrapper.get("n")
        if not isinstance(order, (int, float)):
            m = re.search(r"_r(\d+)", base)
            order = int(m.group(1)) if m else None
        if not isinstance(parsed, dict):
            return []
        values = _point_values_from_bench(parsed)
        if not values:
            return []
        return [{
            "source": base, "label": base,
            "order": order if isinstance(order, (int, float)) else None,
            "identity": _trend_identity(parsed), "values": values,
        }]
    reports = [
        d for d in docs
        if isinstance(d, dict) and d.get("kind") == REPORT_KIND
    ]
    if reports:
        # a report artifact: the LAST line holding an actual prove span
        # is the settled (warm) prove — a gateway 429/shed reject line
        # (wall_s=0.0, no spans) can trail the artifact and must not
        # become its trend point (a 0.0 baseline fires false
        # regressions; a 0.0 head masks real ones). The FILE name is
        # the point label — two artifacts recording the same prove
        # label ("rep3") must still be distinct trend columns
        proved = [
            d for d in reports
            if any(
                sp.get("name") == "prove"
                for _p, sp in _walk_spans(d.get("spans") or [])
            )
        ]
        if not proved:
            return []
        rep = proved[-1]
        values = _point_values_from_report(rep)
        if not values:
            return []
        return [{
            "source": base, "label": base,
            "order": None, "identity": _trend_identity(rep),
            "values": values,
        }]
    # bench.py raw line(s) / bench_micro line file: fold every metric
    # line into one point (micro lines share one run identity)
    values: dict = {}
    identity = ""
    for d in docs:
        if not isinstance(d, dict):
            continue
        values.update(_point_values_from_bench(d))
        identity = identity or _trend_identity(d)
    if not values:
        return []
    return [{
        "source": base, "label": base, "order": None,
        "identity": identity, "values": values,
    }]


def load_trend_points(paths: list[str]) -> tuple[list[dict], list[str]]:
    """Expand paths (directories glob *.json/*.jsonl, sorted by name;
    files load directly, in the order given) into trend points plus
    notes about anything skipped."""
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            names = sorted(os.listdir(p))
            files.extend(
                os.path.join(p, n) for n in names
                if n.endswith((".json", ".jsonl"))
            )
        else:
            files.append(p)
    points: list[dict] = []
    notes: list[str] = []
    for f in files:
        pts = load_trend_file(f)
        for p in pts:
            p["path"] = f
        if pts:
            points.extend(pts)
        else:
            notes.append(f"{f}: no usable trend data (skipped)")
    # BENCH round wrappers carry the round number `n`: order THOSE
    # points by it (in place, other points keep their CLI/filename
    # positions) — lexicographic filenames put r10 before r9 otherwise
    idxs = [
        i for i, p in enumerate(points)
        if isinstance(p.get("order"), (int, float))
    ]
    for i, p in zip(
        idxs, sorted((points[i] for i in idxs), key=lambda p: p["order"])
    ):
        points[i] = p
    # duplicate labels (runA/report.jsonl vs runB/report.jsonl) would
    # collapse into one rendered column: disambiguate with the parent
    # directory
    seen: dict = {}
    for p in points:
        seen[p["label"]] = seen.get(p["label"], 0) + 1
    for p in points:
        if seen[p["label"]] > 1:
            parent = os.path.basename(os.path.dirname(p.get("path", "")))
            if parent:
                p["label"] = f"{parent}/{p['label']}"
    return points, notes


def _series_direction(unit: str) -> str | None:
    """'lower' / 'higher' = which direction is BETTER; None = not
    gated (dimensionless series ride the table only). Byte series
    (the dcn:* cross-host traffic gauges) gate lower-is-better: a
    multi-host round that suddenly moves more DCN bytes regressed."""
    if unit == "s":
        return "lower"
    if unit == "B":
        return "lower"
    if unit.endswith("/s"):
        return "higher"
    return None


def trend_series(points: list[dict]) -> dict:
    """{(identity, series_name): {"unit", "points": [(label, value)]}}
    in artifact order.

    Legacy artifacts predate the identity block (identity "") — when a
    metric's points span the empty identity and exactly ONE real one,
    the legacy points join that identity's series, so the repo's
    pre-identity BENCH history keeps gating new identity-stamped runs
    instead of being silently orphaned into an ungated 1-point series.
    Two or more real identities keep the split: attributing unlabeled
    history to one of several machines would gate apples against
    oranges."""
    idents_by_name: dict = {}
    for pt in points:
        ident = pt.get("identity") or ""
        for name in (pt.get("values") or {}):
            idents_by_name.setdefault(name, set()).add(ident)
    adopt = {}
    for name, idents in idents_by_name.items():
        real = sorted(i for i in idents if i)
        if "" in idents and len(real) == 1:
            adopt[name] = real[0]
    out: dict = {}
    for pt in points:
        for name, ent in (pt.get("values") or {}).items():
            ident = pt.get("identity") or ""
            if not ident:
                ident = adopt.get(name, "")
            slot = out.setdefault(
                (ident, name), {"unit": ent.get("unit", ""), "points": []}
            )
            slot["points"].append((pt.get("label"), float(ent["value"])))
    return out


def trend_gate(
    series: dict,
    threshold: float = 0.2,
    min_abs_s: float = 0.05,
    min_points: int = 2,
) -> list[dict]:
    """Regression verdicts: for every gated series with >= min_points
    points, compare the LAST point against the MEDIAN of its
    predecessors; a lower-is-better series regresses when the last point
    exceeds baseline*(1+threshold) (and by an absolute noise floor:
    min_abs_s for seconds — sub-50ms jitter is noise, not regression —
    1 KiB for byte series); a higher-is-better series regresses below
    baseline*(1-threshold)."""
    regressions = []
    for (identity, name), slot in sorted(series.items()):
        direction = _series_direction(slot.get("unit", ""))
        if direction is None:
            continue
        pts = slot["points"]
        if len(pts) < max(2, min_points):
            continue
        prior = sorted(v for _l, v in pts[:-1])
        base = _percentile(prior, 0.5)
        last_label, last = pts[-1]
        if base is None or base != base:
            continue
        bad = False
        if direction == "lower":
            unit = slot.get("unit")
            floor = {"s": min_abs_s, "B": 1024.0}.get(unit)
            bad = last > base * (1.0 + threshold) and (
                floor is None or (last - base) >= floor
            )
        else:
            bad = last < base * (1.0 - threshold)
        if bad:
            regressions.append({
                "series": name,
                "identity": identity,
                "baseline": round(base, 6),
                "last": round(last, 6),
                "last_label": last_label,
                "ratio": round(last / base, 4) if base else None,
                "direction": direction,
            })
    return regressions


def render_trend(
    series: dict,
    regressions: list[dict] | None = None,
    labels: list | None = None,
) -> str:
    """Text trajectory table: one row per series, one column per
    artifact, regressed series flagged. `labels` pins the column order
    to the ARTIFACT order (pass `[p["label"] for p in points]`); the
    fallback — first appearance across series — can interleave columns
    when early artifacts lack the first series."""
    regressed = {
        (r["identity"], r["series"]) for r in (regressions or ())
    }
    lines = []
    if labels is not None:
        ordered: list = []
        for lb in labels:
            if lb not in ordered:
                ordered.append(lb)
        labels = ordered
    else:
        labels = []
        for slot in series.values():
            for lbl, _v in slot["points"]:
                if lbl not in labels:
                    labels.append(lbl)
    lines.append(
        "trend over " + " -> ".join(str(lb) for lb in labels)
    )
    for (identity, name), slot in sorted(series.items()):
        by_label = dict(slot["points"])
        cells = []
        for lbl in labels:
            v = by_label.get(lbl)
            cells.append(f"{v:.4g}" if isinstance(v, float) else "-")
        flag = "  << REGRESSED" if (identity, name) in regressed else ""
        ident = f" [{identity}]" if identity else ""
        lines.append(
            f"  {name:<28}{ident} "
            + " | ".join(f"{c:>9}" for c in cells)
            + f"  ({slot.get('unit')}){flag}"
        )
    for r in regressions or ():
        lines.append(
            f"REGRESSION: {r['series']} {r['baseline']} -> {r['last']} "
            f"(x{r['ratio']}, {r['direction']}-is-better, "
            f"last={r['last_label']})"
        )
    return "\n".join(lines)


def default_report_path() -> str | None:
    """The BOOJUM_TPU_REPORT env target (None = reporting off)."""
    p = os.environ.get("BOOJUM_TPU_REPORT")
    return p or None
