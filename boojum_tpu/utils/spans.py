"""Hierarchical prover spans — the flight recorder's time axis.

Counterpart of the reference's scoped `firestorm` profiling macros
(`profile_fn!/profile_section!`, reference src/lib.rs:80): where the old
`stage_timer` emitted a FLAT per-stage wall-clock list, `span()` records a
parent/child TREE. Every span carries wall time, start offset, optional
attributes, an `error` field when its body raised (partial spans are
recorded, never lost), and — when BOOJUM_TPU_JAX_TRACE points at a
directory — a `jax.profiler.TraceAnnotation` so device traces carry the
same names.

Recording is opt-in: with no `SpanRecorder` installed and profiling off,
`span()` is a handful of attribute reads and one `os.environ.get` — cheap
enough to leave threaded through every prover stage permanently. Stage
spans (``stage=True``) additionally feed the legacy flat stage sink
(`profiling.collect_stages`) and the per-stage stderr log line, so
`bench.py`'s stage split keeps working unchanged.

Scoping (ISSUE 9): the ACTIVE recorder is resolved contextvar-first —
`install_scoped_recorder` binds a recorder to the current execution
context (one packed proving-service request on its pool thread), while
`install_recorder` keeps setting the process-global DEFAULT context that
bench/CLI flows rely on. Concurrent scoped contexts record into disjoint
trees; code that never scopes sees exactly the old process-global
behavior.

Explicit device sync points: `sync_point(x, label)` calls
`jax.block_until_ready` when an installed recorder asks for synced spans,
charging asynchronously-dispatched device work to the stage that issued it
instead of whichever later stage first touches the result.

Trace context (ISSUE 17): every recorder owns a Dapper-style trace —
a 32-hex `trace_id` minted at construction (or adopted from an inbound
context bound via `set_inbound_trace` / the BOOJUM_TPU_TRACE env var),
and every span opened under it carries a fresh 16-hex `span_id` plus a
`parent_span_id` (the enclosing span's id; for roots, the inbound
parent — e.g. the gateway's admission span). The ids are what
`prove_report.py --timeline` stitches cross-host artifacts on.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import secrets
import threading
import time

from . import profiling as _prof

# Dapper-mold id formats (BASELINE.md "Trace protocol"): trace ids are
# 128-bit, span ids 64-bit, both lowercase hex — the same widths the
# W3C traceparent header uses, so external drivers can mint compatible
# ids without knowing anything about this codebase.
TRACE_ID_HEX = 32
SPAN_ID_HEX = 16


def new_trace_id() -> str:
    return secrets.token_hex(TRACE_ID_HEX // 2)


def new_span_id() -> str:
    return secrets.token_hex(SPAN_ID_HEX // 2)


def _is_hex_id(s, width: int) -> bool:
    return (
        isinstance(s, str)
        and len(s) == width
        and all(c in "0123456789abcdef" for c in s)
    )


def valid_trace_id(s) -> bool:
    return _is_hex_id(s, TRACE_ID_HEX)


def valid_span_id(s) -> bool:
    return _is_hex_id(s, SPAN_ID_HEX)


# inbound trace context: bound to the current execution context by
# whoever dispatches work on behalf of an already-minted trace (the
# proving service serving a gateway-admitted request). A SpanRecorder
# constructed while a context is bound ADOPTS it instead of minting a
# fresh trace — that is the whole propagation mechanism; nothing else
# needs to know where the recorder came from.
_INBOUND_TRACE: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "boojum_tpu.inbound_trace", default=None
)


def set_inbound_trace(ctx: dict | None):
    """Bind an inbound trace context ({"trace_id": ..,
    "parent_span_id": ..?}) to the CURRENT execution context; returns a
    token for reset_inbound_trace. A malformed context is treated as
    absent (recorders mint a fresh trace) rather than poisoning ids."""
    if not (isinstance(ctx, dict) and valid_trace_id(ctx.get("trace_id"))):
        ctx = None
    return _INBOUND_TRACE.set(ctx)


def reset_inbound_trace(token):
    _INBOUND_TRACE.reset(token)


def inbound_trace() -> dict | None:
    """The trace context a new recorder should adopt: contextvar first,
    then the BOOJUM_TPU_TRACE env var ("<trace_id>[:<parent_span_id>]")
    — the latter lets an external driver hand a trace to a bare
    `prove()` CLI/bench process without touching its code."""
    ctx = _INBOUND_TRACE.get()
    if ctx is not None:
        return ctx
    env = os.environ.get("BOOJUM_TPU_TRACE")
    if env:
        tid, _, psid = env.partition(":")
        if valid_trace_id(tid):
            out = {"trace_id": tid}
            if valid_span_id(psid):
                out["parent_span_id"] = psid
            return out
    return None


class SpanRecorder:
    """Collects a span tree. Spans opened on the installing thread nest via
    a per-thread stack; spans opened from other threads (e.g. the
    precompile pool) become additional roots of that thread's own tree and
    are merged into `roots` on close."""

    def __init__(self, sync: bool = True):
        self.t0 = time.perf_counter()
        self.roots: list[dict] = []
        self.sync = sync
        self._tls = threading.local()
        self._lock = threading.Lock()
        # trace context: adopt the inbound one when the constructing
        # context carries it (the scoped-collector path — one gateway
        # request on its pool thread), else mint a fresh root trace
        ctx = inbound_trace()
        if ctx is not None:
            self.trace_id = ctx["trace_id"]
            psid = ctx.get("parent_span_id")
            self.parent_span_id = psid if valid_span_id(psid) else None
        else:
            self.trace_id = new_trace_id()
            self.parent_span_id = None

    def adopt_trace(self, trace_id: str, parent_span_id: str | None = None):
        """Rebind this recorder (and any roots already opened) to an
        externally-minted trace — for callers that learn the context
        only after constructing the recorder."""
        if not valid_trace_id(trace_id):
            return
        self.trace_id = trace_id
        self.parent_span_id = (
            parent_span_id if valid_span_id(parent_span_id) else None
        )
        with self._lock:
            for r in self.roots:
                r["trace_id"] = trace_id
                if self.parent_span_id:
                    r["parent_span_id"] = self.parent_span_id

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> dict | None:
        st = self._stack()
        return st[-1] if st else None

    def open(self, name: str, start_at: float | None = None, **attrs) -> dict:
        """Open a span. `start_at` (a time.perf_counter stamp) backdates
        the span to an instant BEFORE open() ran — how the queue.wait
        span covers the admission→dispatch gap even though the request's
        scoped recorder is only constructed at dispatch. A backdated
        span that predates the recorder itself carries a negative
        start_s and a `backdated` marker so validation can tell it from
        a corrupt clock."""
        now = time.perf_counter()
        t0 = start_at if (start_at is not None and start_at <= now) else now
        sp: dict = {
            "name": name,
            "start_s": round(t0 - self.t0, 6),
            "wall_s": None,
            "span_id": new_span_id(),
            "children": [],
        }
        if t0 < self.t0:
            sp["backdated"] = True
        if attrs:
            sp["attrs"] = dict(attrs)
        st = self._stack()
        if st:
            sp["parent_span_id"] = st[-1]["span_id"]
            st[-1]["children"].append(sp)
        else:
            sp["trace_id"] = self.trace_id
            if self.parent_span_id:
                sp["parent_span_id"] = self.parent_span_id
            with self._lock:
                self.roots.append(sp)
        st.append(sp)
        sp["_t0"] = t0
        return sp

    def close(self, sp: dict, error: str | None = None):
        now = time.perf_counter()
        sp["wall_s"] = round(now - sp.pop("_t0", now), 6)
        if error is not None:
            sp["error"] = error
        st = self._stack()
        # an exception can unwind past child spans whose cms have not run
        # their own close yet in start/stop (non-with) usage — drop them
        while st and st[-1] is not sp:
            st.pop()
        if st:
            st.pop()

    def add_sync(self, seconds: float):
        sp = self.current()
        if sp is not None:
            sp["sync_s"] = round(sp.get("sync_s", 0.0) + seconds, 6)

    def add_overlap(self, seconds: float):
        """Charge time an async transfer batch spent in flight WHILE the
        host kept dispatching (utils/transfer.py) — the counterpart of
        `sync_s` (blocked time): together they make the overlap win
        visible per span in every ProveReport."""
        sp = self.current()
        if sp is not None:
            sp["overlap_s"] = round(sp.get("overlap_s", 0.0) + seconds, 6)

    def tree(self) -> list[dict]:
        """The recorded roots, sanitized (no open-span bookkeeping keys)."""

        def _clean(sp: dict) -> dict:
            d = {k: v for k, v in sp.items() if k != "_t0"}
            if "_t0" in sp and d.get("wall_s") is None:
                d["error"] = d.get("error") or "unclosed"
                d["wall_s"] = round(time.perf_counter() - sp["_t0"], 6)
            d["children"] = [_clean(c) for c in sp["children"]]
            return d

        with self._lock:
            return [_clean(r) for r in self.roots]


# process-global DEFAULT context (bench/CLI posture: one recorder owns
# the whole process) — immutable None or a SpanRecorder reference; all
# mutable collector state lives inside recorder instances
_RECORDER: SpanRecorder | None = None
# contextvar override: a scoped recorder bound to one execution context
# (e.g. one packed proving-service request on its pool thread). Threads
# start with an EMPTY context, so a freshly spawned worker falls back to
# the process-global default unless it scopes its own recorder.
_RECORDER_CTX: contextvars.ContextVar[SpanRecorder | None] = (
    contextvars.ContextVar("boojum_tpu.span_recorder", default=None)
)


def current_recorder() -> SpanRecorder | None:
    """The ACTIVE recorder: context-scoped when one is bound, else the
    process-global default."""
    rec = _RECORDER_CTX.get()
    return rec if rec is not None else _RECORDER


def install_recorder(rec: SpanRecorder | None) -> SpanRecorder | None:
    """Swap the process-wide DEFAULT recorder; returns the previous one.
    Scoped recorders (install_scoped_recorder) override this within
    their context."""
    global _RECORDER
    prev = _RECORDER
    _RECORDER = rec
    return prev


def install_scoped_recorder(rec: SpanRecorder | None):
    """Bind `rec` to the CURRENT execution context only (this thread /
    task); returns a token for reset_scoped_recorder. Other contexts —
    including the process-global default — are untouched, so concurrent
    packed requests each record into their own tree."""
    return _RECORDER_CTX.set(rec)


def reset_scoped_recorder(token):
    _RECORDER_CTX.reset(token)


def start_recording(sync: bool = True) -> SpanRecorder:
    rec = SpanRecorder(sync=sync)
    install_recorder(rec)
    return rec


def stop_recording() -> SpanRecorder | None:
    return install_recorder(None)


def span_attr(name: str, value):
    """Attach an attribute to the CURRENTLY OPEN span (no-op when nothing
    records) — for call sites that learn something mid-span worth auditing
    per report, e.g. which axis shard_cols actually sharded."""
    rec = current_recorder()
    if rec is None:
        return
    sp = rec.current()
    if sp is not None:
        sp.setdefault("attrs", {})[name] = value


def recording() -> bool:
    """Whether a span opened now would land anywhere: a recorder, the
    profiler's annotations (BOOJUM_TPU_JAX_TRACE), the stage log or the
    stage sink. `span()`'s own test, for call sites that open several
    spans at once and want ONE check when nothing listens
    (utils/transfer.py's upload and sync sites)."""
    return (
        current_recorder() is not None
        or os.environ.get("BOOJUM_TPU_JAX_TRACE") is not None
        or _prof.profiling_enabled()
        or _prof._STAGE_SINK is not None
    )


@contextlib.contextmanager
def span(name: str, stage: bool = False, **attrs):
    """Record one span. Yields the span dict (or None when not recording).

    ``stage=True`` marks a top-level prover stage: on close it also feeds
    the flat stage sink and the per-stage log line (the pre-flight-recorder
    observable surface). Exception-safe: a raising body still records the
    span, with an ``error`` field (ISSUE 2 satellite: the old stage_timer
    lost the timing line entirely)."""
    from . import blackbox as _bb

    # any span open is Python-level forward motion: reset the blackbox
    # stall clock even on the cheap not-recording path
    _bb.tick()
    if not recording():
        yield None
        return
    rec = current_recorder()
    trace_dir = os.environ.get("BOOJUM_TPU_JAX_TRACE")
    ctx = contextlib.nullcontext()
    if trace_dir:
        import jax

        ctx = jax.profiler.TraceAnnotation(name)
    sp = rec.open(name, **attrs) if rec is not None else None
    t0 = time.perf_counter()
    err: BaseException | None = None
    try:
        with ctx:
            yield sp
    except BaseException as e:
        err = e
        raise
    finally:
        dt = time.perf_counter() - t0
        error_s = None
        if err is not None:
            error_s = f"{type(err).__name__}: {err}"[:200]
        if rec is not None:
            rec.close(sp, error=error_s)
        if stage:
            sink = _prof._STAGE_SINK
            if sink is not None:
                sink.append((name, dt))
            _prof.log(
                f"{name}: {dt:.3f}s"
                + (f" [error: {error_s}]" if error_s else "")
            )


def sync_point(x, label: str | None = None):
    """Block on `x` (jax.block_until_ready) when the installed recorder
    wants synced spans, charging the wait to the current span as `sync_s`.
    Passes `x` through unchanged; a no-op without a recorder."""
    rec = current_recorder()
    if rec is None or not rec.sync or x is None:
        return x
    import jax

    t0 = time.perf_counter()
    try:
        jax.block_until_ready(x)
    except Exception:
        return x
    rec.add_sync(time.perf_counter() - t0)
    if label:
        sp = rec.current()
        if sp is not None:
            sp.setdefault("sync_points", []).append(label)
    return x
