#!/usr/bin/env python3
"""Every idle gap of a kept trace, put down to what the host was doing.

    python3 benchmark/tools/idle_by_span.py <trace dir or .xplane.pb> [--min-ms 1]

`run.py --trace 1 --keep-trace <dir>` keeps the profiler's trace. The
benchmark's own breakdown names the FIVE longest idle gaps; this reads the
same trace through reduce_trace's loaders and owns every gap: each part of
the traced window in which no operation ran on the first device goes to the
innermost program span open on the host at its middle (`no_annotation`
where none is). Printed: the idle milliseconds a prove by span, in gaps of
at least --min-ms and in shorter ones, whose sum is the window's idle time;
every gap of at least --min-ms with the whole host path at its middle (the
runtime's own events included); and the program's spans a prove by count
and host time. Not run by the driver.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace as R  # noqa: E402

NO_ANNOTATION = "no_annotation"
# the runtime's own host events whose names reduce_trace.SPAN_NAME takes for
# a program span (plain lower-case identifiers): they stay in a long gap's
# printed path and never own a gap (`jnp.asarray(<numpy>)` opens
# `shard_args` inside the upload site's `h2d.<site>` span)
RUNTIME_EVENTS = frozenset({"shard_args"})
# spans that say where in the prove a gap fell but not what the host did
# there: a stage with no child open, the prove, the harness's annotation
UNOWNED = re.compile(r"^(round\d_\w+|queries|prove|bench\.prove|no_annotation)$")


def window(planes):
    """(lo, hi, proves, host events, busy intervals of the first device):
    the window `reduce_trace.reduce` takes when the clocks line up, the
    proves' annotations from the first's start to the last's end."""
    devices = [p for p in planes if R.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("trace has no device plane")
    host = R.host_annotation_line(planes)
    if host is None:
        raise ValueError(
            f"no host line carries {R.WINDOW_ANNOTATION!r}: nothing to own a gap"
        )
    proves = sorted(
        (e for e in host.events if e.name == R.WINDOW_ANNOTATION),
        key=lambda e: e.start_ns,
    )
    lo = min(e.start_ns for e in proves)
    hi = max(e.end_ns for e in proves)
    busy_line = R._line(devices[0], R.OPS_LINE)
    if busy_line is None or not busy_line.events:
        busy_line = R._line(devices[0], R.MODULES_LINE)
    busy = R._clip(busy_line.events, lo, hi) if busy_line is not None else []
    if not busy:
        raise ValueError(
            "no operation of the first device lies inside the proves' "
            "annotations: nothing ran, or host and device clocks do not line up"
        )
    return lo, hi, proves, host.events, busy


def program_spans(host_events, lo, hi):
    """The program's spans and the harness's annotation inside the window
    (reduce_trace.SPAN_NAME tells them from the runtime's own events)."""
    return [
        e for e in host_events
        if R.SPAN_NAME.match(e.name) and e.name not in RUNTIME_EVENTS
        and e.end_ns > lo and e.start_ns < hi
    ]


def innermost_at(spans, times):
    """For each of `times` (ascending), the name of the innermost of
    `spans` open then. One pass: spans nest on one thread, so the open ones
    are a stack."""
    order = sorted(spans, key=lambda e: (e.start_ns, -e.duration_ns))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i].start_ns <= t:
            stack.append(order[i])
            i += 1
        # an ended span may lie under one that is still open only if the
        # two did not nest; dropping every ended one keeps the stack right
        stack = [e for e in stack if e.end_ns > t]
        out.append(stack[-1].name if stack else NO_ANNOTATION)
    return out


def table(planes, min_ms: float = 1.0) -> dict:
    lo, hi, proves, host_events, busy = window(planes)
    idle = R.gaps(busy, lo, hi)
    spans = program_spans(host_events, lo, hi)
    owners = innermost_at(spans, [(s + e) / 2 for s, e in idle])
    rows: dict[str, list[float]] = {}
    long_gaps = []
    for (s, e), owner in zip(idle, owners):
        ms = (e - s) / 1e6
        row = rows.setdefault(owner, [0.0, 0.0])
        row[0 if ms >= min_ms else 1] += ms
        if ms >= min_ms:
            mid = (s + e) / 2
            k = max(
                (i for i, p in enumerate(proves) if p.start_ns <= mid), default=0
            )
            long_gaps.append({
                "prove": k,
                "at_ms": (s - proves[k].start_ns) / 1e6,
                "ms": ms,
                "path": "/".join(R.span_path_at(host_events, mid)) or NO_ANNOTATION,
            })
    by_span: dict[str, list[float]] = {}
    for e in spans:
        if e.name == R.WINDOW_ANNOTATION:
            continue
        row = by_span.setdefault(e.name, [0, 0.0])
        row[0] += 1
        row[1] += e.duration_ns / 1e6
    return {
        "proves": len(proves),
        "window_ms": (hi - lo) / 1e6,
        # what reduce_trace calls idle: the window less the busy union
        "idle_ms": (hi - lo) / 1e6 - 1e3 * R.union_seconds(busy),
        "gaps": len(idle),
        "rows": rows,
        "long_gaps": long_gaps,
        "spans": by_span,
    }


def unowned_share(rows: dict) -> float:
    """Of the idle time in gaps of at least --min-ms, the share that falls
    to a span that names no host activity."""
    total = sum(r[0] for r in rows.values())
    loose = sum(r[0] for name, r in rows.items() if UNOWNED.match(name))
    return loose / total if total else 0.0


def render(t: dict, min_ms: float) -> str:
    n = t["proves"]
    out = [
        f"{n} proves, window {t['window_ms']:.3f} ms, idle {t['idle_ms']:.3f} ms in "
        f"{t['gaps']} gaps: {t['idle_ms'] / n:.3f} ms a prove "
        f"({100 * t['idle_ms'] / t['window_ms']:.2f} %)",
        "",
        f"idle ms a prove by innermost program span (in gaps >= {min_ms:g} ms | "
        "in shorter gaps | both)",
    ]
    rows = sorted(t["rows"].items(), key=lambda kv: -(kv[1][0] + kv[1][1]))
    for name, (a, b) in rows:
        out.append(f"  {name:<34}{a / n:>10.3f}{b / n:>10.3f}{(a + b) / n:>10.3f}")
    a = sum(r[0] for r in t["rows"].values())
    b = sum(r[1] for r in t["rows"].values())
    out.append(f"  {'sum':<34}{a / n:>10.3f}{b / n:>10.3f}{(a + b) / n:>10.3f}")
    out.append(
        f"  sum less the window's idle time: {(a + b - t['idle_ms']) / n:+.6f} ms a prove"
    )
    out.append(
        f"  of the idle time in gaps >= {min_ms:g} ms, {100 * unowned_share(t['rows']):.1f} % "
        "falls to a stage span with no child, prove, bench.prove or no_annotation"
    )
    out += ["", f"every gap of at least {min_ms:g} ms (prove, ms into it, length, "
            "path at its middle)"]
    for g in t["long_gaps"]:
        out.append(
            f"  #{g['prove']} +{g['at_ms']:>9.3f} {g['ms']:>9.3f} ms  {g['path']}"
        )
    out += ["", "program spans a prove (count, host ms)"]
    for name, (count, ms) in sorted(t["spans"].items(), key=lambda kv: -kv[1][1]):
        out.append(f"  {name:<34}{count / n:>9.2f}{ms / n:>11.3f}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a directory holding an .xplane.pb, or the file")
    ap.add_argument("--min-ms", type=float, default=1.0)
    opts = ap.parse_args(argv)
    path = opts.trace if os.path.isfile(opts.trace) else R.find_trace_file(opts.trace)
    print(render(table(R.load_xplane(path), opts.min_ms), opts.min_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
