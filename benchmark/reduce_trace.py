"""Reduce a profiler trace (.xplane.pb) to what the per-layer metrics read:
device busy and idle time, device time by module, launches, and the longest
idle gaps named by what the host was doing.

The arithmetic works on plain `Plane`/`Line`/`Event` records, so that tests
can hand it a trace built by hand; `load_xplane` fills them from a file
through `jax.profiler.ProfileData`, with nothing but JAX.

What a TPU trace looks like (seen by hand, PERF.md has the notes): one plane
`/device:TPU:<i>` per chip with a line "XLA Modules" (one event per
execution of a compiled program, named `jit_<function>(<program id>)`) and a
line "XLA Ops" (one event per operation inside them), and one plane
`/host:CPU` with a line per host thread, on which
`jax.profiler.TraceAnnotation`s appear under their own names, nested by
time.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = re.compile(r"^/host:")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "bench.prove"
PROGRAM_ID = re.compile(r"\(\d+\)$")
# the program's spans and the benchmark's annotations are plain lower-case
# identifiers; the runtime's own host events are not (PjitFunction(fn),
# TpuExecute, Foo::Bar)
SPAN_NAME = re.compile(r"^[a-z0-9_.]+$")


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def find_trace_file(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> list[Plane]:
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            events = []
            for e in ln.events:
                stats = {}
                try:
                    for k, v in e.stats:
                        if k in ("run_id", "program_id", "hlo_module", "hlo_op"):
                            stats[k] = v
                except Exception:  # noqa: BLE001 — a stat that cannot be read
                    pass
                events.append(
                    Event(e.name, float(e.start_ns), float(e.duration_ns), stats)
                )
            lines.append(Line(ln.name, events))
        planes.append(Plane(pl.name, lines))
    return planes


def load_families(path: str) -> list[dict]:
    with open(path) as f:
        rows = json.load(f)["families"]
    return [
        {
            "family": r["family"],
            "module": re.compile(r["module"]),
            "span": re.compile(r["span"]) if r.get("span") else None,
        }
        for r in rows
    ]


def module_key(name: str) -> str:
    """`jit_fn(1234)` -> `jit_fn`: the name without its program id."""
    return PROGRAM_ID.sub("", name)


def classify(name: str, span_path: str, families: list[dict]) -> str:
    key = module_key(name)
    for row in families:
        if not row["module"].search(key):
            continue
        if row["span"] is not None and not row["span"].search(span_path):
            continue
        return row["family"]
    return "other"


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def _line(plane: Plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def host_annotation_line(planes: list[Plane]):
    """The host thread that drove the proves: the line that carries the
    window annotation."""
    for pl in planes:
        if not HOST_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            if any(e.name == WINDOW_ANNOTATION for e in ln.events):
                return ln
    return None


def span_path_at(host_events, t_ns: float) -> list[str]:
    """Names of the host events open at `t_ns`, outermost first."""
    open_ = [e for e in host_events if e.start_ns <= t_ns < e.end_ns]
    open_.sort(key=lambda e: (e.start_ns, -e.duration_ns))
    return [e.name for e in open_]


def gap_name(path: list[str]) -> str:
    """The innermost span annotation open on the host, with the runtime's
    innermost event beside it where there is one."""
    spans = [p for p in path if SPAN_NAME.match(p) and p != WINDOW_ANNOTATION]
    runtime = [p for p in path if not SPAN_NAME.match(p)]
    name = spans[-1] if spans else (WINDOW_ANNOTATION if path else "no_annotation")
    if runtime:
        name += f" [{runtime[-1][:60]}]"
    return name


def reduce(planes: list[Plane], families: list[dict]) -> dict:
    """The whole reduction. Raises ValueError where the trace holds no
    device plane or no module ran: a traced run with nothing on the device
    is an error, not a zero."""
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(
            "trace has no device plane: " + ", ".join(p.name for p in planes)
        )
    host = host_annotation_line(planes)
    host_events = host.events if host is not None else []
    proves = [e for e in host_events if e.name == WINDOW_ANNOTATION]
    device_events = [
        e for d in devices for ln in d.lines
        if ln.name in (MODULES_LINE, OPS_LINE) for e in ln.events
    ]
    if not device_events:
        raise ValueError("no operation ran on the device in the traced window")
    dev_lo = min(e.start_ns for e in device_events)
    dev_hi = max(e.end_ns for e in device_events)
    aligned = False
    if proves:
        lo = min(e.start_ns for e in proves)
        hi = max(e.end_ns for e in proves)
        inside = sum(1 for e in device_events if lo <= e.start_ns < hi)
        aligned = inside * 2 >= len(device_events)
    if not aligned:
        # host and device clocks do not line up (or no annotation reached
        # the trace): the window is what the device saw
        lo, hi = dev_lo, dev_hi
    num_proves = max(1, len(proves))

    # where the runtime stamps launches and executions with one run_id, a
    # module is looked up at its launch; otherwise at its start on the device
    launch_at = {}
    for e in host_events:
        rid = e.stats.get("run_id")
        if rid is not None and rid not in launch_at:
            launch_at[rid] = e.start_ns

    busy, per_key, launches = [], {}, 0
    for d in devices:
        mods = _line(d, MODULES_LINE)
        ops = _line(d, OPS_LINE)
        busy_line = ops if (ops is not None and ops.events) else mods
        busy.append(union_seconds(_clip(busy_line.events, lo, hi)) if busy_line else 0.0)
        for e in (mods.events if mods is not None else []):
            if not (lo <= e.start_ns < hi):
                continue
            launches += 1
            at = launch_at.get(e.stats.get("run_id"), e.start_ns) if aligned else None
            path = "/".join(span_path_at(host_events, at)) if at is not None else ""
            fam = classify(e.name, path, families)
            row = per_key.setdefault(
                (e.name, fam), {"name": e.name, "family": fam, "count": 0, "seconds": 0.0}
            )
            row["count"] += 1
            row["seconds"] += e.duration_ns / 1e9
    chips = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / chips
    modules = sorted(per_key.values(), key=lambda r: -r["seconds"])
    family_seconds = {}
    for r in modules:
        family_seconds[r["family"]] = family_seconds.get(r["family"], 0.0) + r["seconds"]

    # idle gaps of the first device, named by the host
    d0 = devices[0]
    busy_line = _line(d0, OPS_LINE)
    if busy_line is None or not busy_line.events:
        busy_line = _line(d0, MODULES_LINE)
    idle = gaps(_clip(busy_line.events, lo, hi), lo, hi) if busy_line else []
    idle.sort(key=lambda g: g[0] - g[1])
    idle_gaps = []
    for s, e in idle[:5]:
        path = span_path_at(host_events, (s + e) / 2) if aligned else []
        idle_gaps.append([gap_name(path), (e - s) / 1e9])

    return {
        "chips": chips,
        "proves": num_proves,
        "clock_aligned": aligned,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
        "launches": launches / chips,
        "modules": modules,
        # per chip, whole traced window
        "family_seconds": {k: v / chips for k, v in family_seconds.items()},
        "device_ops": [[r["name"], r["seconds"] / chips] for r in modules[:10]],
        "idle_gaps": idle_gaps,
        "host_events": host_events,
    }


def module_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Device seconds and executions, per chip and over the traced window,
    of the modules whose name matches `pattern`."""
    rx = re.compile(pattern)
    rows = [r for r in reduced["modules"] if rx.search(module_key(r["name"]))]
    chips = reduced["chips"]
    return sum(r["seconds"] for r in rows) / chips, sum(r["count"] for r in rows) // chips


def span_seconds(reduced: dict, name: str) -> float | None:
    """Host seconds the annotation `name` was open, over the traced window."""
    evs = [e for e in reduced["host_events"] if e.name == name]
    return sum(e.duration_ns for e in evs) / 1e9 if evs else None
