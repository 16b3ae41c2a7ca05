#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads BENCHMARK.json at the root of the checkout, finds the cell's
configuration, traffic mix and per-layer metrics by name under benchmark/,
sets the system up (synthesis from the seed, kernel library, generate_setup,
warm-up proves), then

  --trace 0  proves in a closed loop for --seconds and reports the cell's
             end-to-end metrics from the host clock;
  --trace 1  traces three proves with jax.profiler and reports the cell's
             per-layer metrics from the trace, the program's counters and the
             harness's own timers, with a breakdown.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device (and breakdown with --trace 1). Off a TPU, or with
fewer chips than the cell asks for, it prints no result and exits nonzero:
there is no CPU fallback.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import layer_metrics, reduce_trace  # noqa: E402
from benchmark.costs.shapes import prove_shapes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
REQUIRED_PLATFORM = "tpu"
WARMUP_PROVES = 2
TRACED_PROVES = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class BenchFailure(Exception):
    """No result can be printed: wrong device, unknown cell, broken set-up."""


def log(msg: str):
    print(f"[bench +{time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def say(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything that belongs to one cell, from BENCHMARK.json and the
    files it names. Nothing here knows a cell, a configuration, a mix or a
    metric by name."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(
            f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchFailure(f"workload {workload!r} names no known configuration")
    config = _load(os.path.join(root, configs[cell["config"]]["file"]))
    bdir = os.path.join(root, bench["paths"][0])
    traffic = _load(os.path.join(bdir, "traffic", f"{cell['traffic']}.json"))

    def in_cell(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "bench_dir": bdir,
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def load_peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    table = _load(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchFailure(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}): a device without published peaks is an error"
        )
    return table[device_kind]


def require_devices(devices, chips: int):
    if not devices or devices[0].platform != REQUIRED_PLATFORM:
        got = devices[0].platform if devices else "none"
        raise BenchFailure(
            f"needs a {REQUIRED_PLATFORM} device, jax reports {got!r}"
        )
    if len(devices) < chips:
        raise BenchFailure(
            f"the cell needs {chips} chips, jax reports {len(devices)}"
        )


# ---------------------------------------------------------------------------
# Compilations, counted by the harness itself
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts jax.monitoring's compile events. A compile request (cache hit
    or miss) inside the window means a shape was not warmed up."""

    def __init__(self):
        self.counts: dict[str, int] = {}

    def install(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: self._tick(ev)
        )
        monitoring.register_event_listener(lambda ev, **kw: self._tick(ev))
        return self

    def _tick(self, ev: str):
        self.counts[ev] = self.counts.get(ev, 0) + 1

    def compiles(self) -> int:
        return self.counts.get(COMPILE_EVENT, 0)

    def misses(self) -> int:
        return self.counts.get(CACHE_MISS_EVENT, 0)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


class Timers(dict):
    """Seconds by step; a step timed twice adds up."""

    @contextlib.contextmanager
    def time(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - t0
            log(f"{name}: {self[name]:.3f} s")


def set_up(system, cell, opts, timers: Timers, compiles: CompileCounter,
           devices) -> dict:
    """Synthesis from the seed, the kernel library, the setup and the
    warm-up proves. Only this cell's shapes are compiled.

    A checkout's first run of a cell generates the setup (verification key,
    setup oracle and its tree) and keeps it beside the compile cache; later
    runs load it, as a prover worker loads its setup data: it depends on the
    circuit, not on the witness. The file is named by what it depends on
    (`setup_key`), so a changed configuration or program generates anew."""
    with timers.time("synthesis"):
        trace_len = system.synthesize(cell, opts.seed)
    log(f"trace_len {trace_len}")
    kept = os.path.join(
        system.cache_dir,
        f"bench.{cell['name']}.{system.setup_key(cell, trace_len)}.setup.pkl",
    )
    have_setup = os.path.exists(kept)
    with timers.time("library"):
        # compiled on a first run, loaded from the cache afterwards, on a
        # pool both times: left to the proves' own dispatch a fresh process
        # took longer than a whole run may last (PERF.md, PR 23)
        with timers.time("warm_library"):
            errors = system.warm_library(
                max(8, os.cpu_count() or 8), skip_setup=have_setup
            )
        if errors:
            raise BenchFailure("kernels failed to compile: " + "; ".join(errors))
        if have_setup:
            with timers.time("load_setup"):
                system.load_setup(kept)
        else:
            with timers.time("generate_setup"):
                system.generate_setup()
            os.makedirs(system.cache_dir, exist_ok=True)
            with timers.time("save_setup"):
                system.save_setup(kept)
    # the device's peak counter belongs to the process and cannot be reset:
    # what set-up reached before the first prove is printed beside the
    # window's reading, so a run shows whether that is the proves' own
    peak_before_proves = system.peak_bytes(devices)
    with timers.time("warmup"):
        for _ in range(WARMUP_PROVES - 1):
            system.prove()
            system.drain()
        # the last warm-up prove runs under the program's flight recorder:
        # its counters say which kernel path ran
        _proof, counters = system.recorded_prove()
    log(f"compile requests in set-up {compiles.compiles()}, cache misses "
        f"{compiles.misses()}")
    return {"trace_len": trace_len, "counters": counters,
            "peak_before_proves": peak_before_proves}


# ---------------------------------------------------------------------------
# The measured window and the traced proves
# ---------------------------------------------------------------------------


def one_prove(system):
    """A request: ends in proof bytes on the host and a drained device."""
    t0 = time.perf_counter()
    proof = system.prove()
    blob = system.proof_bytes(proof)
    system.drain()
    return time.perf_counter() - t0, blob


def closed_loop(system, seconds: float):
    """One client: the next prove starts when the last has drained."""
    walls, blobs = [], []
    t0 = time.perf_counter()
    while True:
        wall, blob = one_prove(system)
        walls.append(wall)
        blobs.append(blob)
        now = time.perf_counter()
        if now - t0 >= seconds:
            return walls, blobs, now - t0


def traced_proves(system, n: int, keep: str | None):
    """`n` proves under jax.profiler, the program's spans annotated.
    Returns (walls, blobs, planes)."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        system.annotate_spans(trace_dir)
        walls, blobs = [], []
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(n):
                with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_ANNOTATION):
                    wall, blob = one_prove(system)
                walls.append(wall)
                blobs.append(blob)
        finally:
            jax.profiler.stop_trace()
            system.annotate_spans(None)
        path = reduce_trace.find_trace_file(trace_dir)
        log(f"trace {os.path.getsize(path)} bytes")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, keep)
        return walls, blobs, reduce_trace.load_xplane(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------


def check(system, blobs, counters, compiles_in_window, label="check") -> dict:
    """What the timed path produced, held to the configuration's
    guarantees. Every number compared is printed beside its limit."""
    from benchmark.system import native_path_problems

    differing = sum(1 for b in blobs if b != blobs[0])
    verified = {}
    for which, blob in (("first", blobs[0]), ("last", blobs[-1])):
        if which == "last" and blob == blobs[0]:
            verified[which] = verified["first"]  # the same bytes
            continue
        try:
            verified[which] = bool(system.verify(system.proof_from_bytes(blob)))
        except Exception as e:  # noqa: BLE001 — a proof the verifier cannot read
            log(f"verify({which}) raised {e!r}")
            verified[which] = False
    problems = native_path_problems(counters)
    rows = [
        ("proofs_differing_from_first", differing, 0),
        ("verify_first", verified["first"], True),
        ("verify_last", verified["last"], True),
        ("native_path_problems", len(problems), 0),
        ("compile_requests_in_window", compiles_in_window, 0),
    ]
    for name, got, limit in rows:
        say(f"{label} {name}: {json.dumps(got)} (limit {json.dumps(limit)})")
    for p in problems:
        say(f"{label} native path: {p}")
    # a proof that differs from the first has failed; if the first does not
    # verify, so have all that equal it
    failed = differing if verified["first"] else len(blobs)
    return {
        "correct": all(got == limit for _n, got, limit in rows),
        "attempted": len(blobs),
        "failed": failed,
    }


def check_with_control(system, blobs, counters, compiles_in_window, control):
    """The run's own check and, where a control is asked for, the same check
    handed the same proofs damaged: it has to come out not correct. Its
    verdict goes on a line of its own, before the run's result."""
    say(f"proof sha256 {hashlib.sha256(blobs[0]).hexdigest()} bytes {len(blobs[0])}")
    verdict = check(system, blobs, counters, compiles_in_window)
    if control:
        damaged = [system.damage(b) for b in blobs]
        got = check(system, damaged, counters, compiles_in_window, label="control")
        say("control " + json.dumps({"control": control, **got}))
        if got["correct"]:
            raise BenchFailure(f"the control {control!r} came out correct")
    return verdict


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def device_record(devices, system) -> dict:
    d = devices[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
        "memory_peak_bytes": system.peak_bytes(devices),
    }


def run_cell(cell, opts, system, devices) -> dict:
    """Set-up, window or traced proves, check: the result line as a dict."""
    peaks = load_peaks(devices[0].device_kind, cell["bench_dir"])
    compiles = CompileCounter().install()
    timers = Timers()
    timers["import"] = time.perf_counter() - T_START
    # set-up traces and lowers the whole kernel library on one thread and
    # allocates millions of objects that stay: the cyclic collector only
    # slows that down. From before the window on it runs as in any process,
    # over what the proves allocate: what set-up left is frozen, so that no
    # full collection over the library's objects falls into one prove's wall.
    used = devices[: cell["chips"]]
    gc.disable()
    try:
        facts = set_up(system, cell, opts, timers, compiles, used)
    finally:
        gc.enable()
        gc.collect()
        gc.freeze()
    unit = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
    metrics: dict = {}

    def put(name, value):
        if value is not None and name in unit:
            metrics[name] = {"value": value, "unit": unit[name]}

    say("set-up by step: " + json.dumps({k: round(v, 3) for k, v in timers.items()}))
    c0 = compiles.compiles()
    setup_s = time.perf_counter() - T_START
    if not opts.trace:
        walls, blobs, window_s = closed_loop(system, opts.seconds)
        in_window = compiles.compiles() - c0
        log("window closed")
        say(f"window: {len(walls)} proves in {window_s:.3f} s; walls min "
            f"{min(walls):.4f} max {max(walls):.4f} s")
        say(f"device peak bytes: before the first prove "
            f"{facts['peak_before_proves']}, after the window "
            f"{system.peak_bytes(used)}")
        verdict = check_with_control(system, blobs, facts["counters"], in_window,
                                     opts.control)
        done = verdict["attempted"] - verdict["failed"]
        put("prove_s.p50", float(np.percentile(walls, 50)))
        put("prove_s.p90", float(np.percentile(walls, 90)))
        put("proofs_per_s", done / window_s)
        device = device_record(used, system)
        put("hbm_peak_gib", device["memory_peak_bytes"] / 2**30)
        put("setup_s", setup_s)
        log("checked")
        return {**verdict, "metrics": metrics, "device": device}

    walls, blobs, planes = traced_proves(system, TRACED_PROVES, opts.keep_trace)
    in_window = compiles.compiles() - c0
    families = reduce_trace.load_families(
        os.path.join(cell["bench_dir"], "families.json")
    )
    trace = reduce_trace.reduce(planes, families)
    say(f"traced: {len(walls)} proves, walls {[round(w, 4) for w in walls]} s; "
        f"window {trace['window_s']:.4f} s busy {trace['busy_s']:.4f} s; "
        f"clock aligned {trace['clock_aligned']}")
    verdict = check_with_control(system, blobs, facts["counters"], in_window,
                                     opts.control)
    ctx = {
        "trace": trace,
        "counters": facts["counters"],
        "timers": dict(timers),
        "shapes": prove_shapes(cell["config"], facts["trace_len"]),
        "peaks": peaks,
    }
    for m in cell["per_layer"]:
        spec = layer_metrics.load_metric(m["name"], cell["bench_dir"])
        put(m["name"], layer_metrics.read_metric(spec, ctx))
    device = device_record(used, system)
    device["busy_s"] = trace["busy_s"]
    device["window_s"] = trace["window_s"]
    say("modules: " + json.dumps(
        [[r["name"], r["family"], r["count"], round(r["seconds"], 6)]
         for r in trace["modules"]]
    ))
    return {
        **verdict,
        "metrics": metrics,
        "device": device,
        "breakdown": {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        },
    }


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # not used by the driver: the control that must come out not correct
    # (checked after the run's own check, on the same proofs), and a place
    # to keep the trace
    ap.add_argument("--control", choices=("truncate_opening",), default=None)
    ap.add_argument("--keep-trace", default=None)
    return ap.parse_args(argv)


def main(argv=None, system=None, root: str = ROOT) -> int:
    opts = parse_args(argv)
    try:
        cell = load_cell(opts.workload, root)
        if system is None:
            from benchmark.system import BoojumSystem

            system = BoojumSystem()
        devices = system.start()
        require_devices(devices, cell["chips"])
        log(f"device {devices[0].device_kind} x{len(devices)}; cache "
            f"{getattr(system, 'cache_dir', None)}")
        line = run_cell(cell, opts, system, devices)
    except Exception as e:  # noqa: BLE001 — the boundary: no result line on a failure
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"correct": False, "error": repr(e)}), file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # the result is out: leave without the interpreter's and the TPU
    # runtime's teardown, which took 10 s after a warm run and 17 s after a
    # first one (PERF.md, section 5) of the 360 s a run may last. The
    # process starts no other and holds no file open for writing.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
