"""Readers of the per-layer metrics. Each metric is one data file,
`layer_metrics/<name>.json`, whose `source.kind` names one of the readers
below; a reader that finds nothing to read returns None and the harness
leaves the metric out of the line.

The context a reader gets:
  trace     reduce_trace.reduce()'s result for the traced proves
  counters  the program's flight-recorder counters of one recorded prove
  timers    the harness's own set-up timers, seconds by name
  shapes    costs.shapes.prove_shapes() of the cell
  peaks     the peaks.json row of this device_kind
"""

from __future__ import annotations

import importlib
import json
import os

from . import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def load_metric(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "layer_metrics", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{path}: name {spec.get('name')!r} is not {name!r}")
    return spec


def _trace_modules(src, ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    proves = tr["proves"]
    red = src.get("reduction", "device_ms_per_prove")
    if red == "launches_per_prove":
        return tr["launches"] / proves
    if "family" in src:
        if src["family"] not in tr["family_seconds"] and src["family"] != "other":
            return None
        seconds = tr["family_seconds"].get(src["family"], 0.0)
    else:
        seconds, count = reduce_trace.module_seconds(tr, src["module"])
        if not count:
            return None
    if red == "device_ms_per_prove":
        return 1e3 * seconds / proves
    raise ValueError(f"unknown reduction {red!r}")


def _trace_device(src, ctx):
    tr = ctx.get("trace")
    return None if tr is None else tr.get(src["field"])


def _counter(src, ctx):
    counters = ctx.get("counters") or {}
    v = counters.get(src["name"])
    return None if v is None else float(v)


def _harness_timer(src, ctx):
    v = (ctx.get("timers") or {}).get(src["name"])
    return None if v is None else float(v)


def _span(src, ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    s = reduce_trace.span_seconds(tr, src["name"])
    return None if s is None else 1e3 * s / tr["proves"]


def _shape_cost(src, ctx):
    """Operations or compulsory bytes from the cell's shapes (a function of
    costs/<cost>.py) over the device time of the modules that do the work."""
    tr, shapes = ctx.get("trace"), ctx.get("shapes")
    if tr is None or shapes is None:
        return None
    seconds, count = reduce_trace.module_seconds(tr, src["module"])
    if not count or seconds <= 0:
        return None
    cost = importlib.import_module(f"benchmark.costs.{src['cost']}").cost(shapes)
    per_prove_s = seconds / tr["proves"]
    red = src["reduction"]
    if red == "share_of_peak_pct":
        # the least time the chip could take over the time it took; the
        # peak comes from peaks.json and an unknown device is an error
        peak = ctx["peaks"][src["peak"]]
        return 100.0 * (cost[src["quantity"]] / peak) / per_prove_s
    if red == "per_second":
        return cost[src["quantity"]] / per_prove_s
    raise ValueError(f"unknown reduction {red!r}")


READERS = {
    "trace_modules": _trace_modules,
    "trace_device": _trace_device,
    "counter": _counter,
    "harness_timer": _harness_timer,
    "span": _span,
    "shape_cost": _shape_cost,
}


def read_metric(spec: dict, ctx: dict):
    kind = spec["source"]["kind"]
    if kind not in READERS:
        raise ValueError(f"{spec['name']}: unknown source kind {kind!r}")
    return READERS[kind](spec["source"], ctx)
