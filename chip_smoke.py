#!/usr/bin/env python3
"""First proof on the chip: the quickest evidence that the system still
starts on a TPU, proves through the native kernel path, and says so loudly
when it cannot.

    python chip_smoke.py                  one chip, SHA-256 1 kB (2^14 rows),
                                          main phase only: fits a cold 1200 s
    python chip_smoke.py --sha-bytes 8192 the real size (2^16 rows): parity
                                          phase, then main
    python chip_smoke.py --chips 4        ONLY the four-chip mesh phase

One process, no child, no platform set in code, no CPU fallback, no
interpret mode, no retry, no watchdog. Phases (one JSON line each):

  parity  a 2^14-row fma circuit proved on the native path and again under
          `force_xla()`: proof bytes must be equal and both must verify.
          Runs first — the cheap fault-finder before the large compile.
  main    upstream's SHA-256 bench circuit at its own widths through
          precompile -> generate_setup -> cold prove -> verify -> two warm
          proves; the last warm prove's flight-recorder counters must show
          the limb-resident Pallas kernels ran (a prove that verified on
          the XLA u64 path is a fallback that hid the kernels).
  mesh    (--chips 4 only) one meshless prove on device 0 against one
          shard_map prove over a 2x2 mesh: equal proof bytes, collectives
          counted, memory in use on all four devices.

The last stdout line is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and the exit code 0; any failure gives {"ok": false, "error": ...} and a
nonzero exit. The compile cache follows the package's one rule
(boojum_tpu/compile_cache.py).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import sys
import time
import traceback

# upstream's bench configuration (reference README "For curions in
# benchmarks"): LDE 8, cap 16, 50 queries, PoW 0, final degree 16
PROOF_CONFIG = dict(
    fri_lde_factor=8,
    merkle_tree_cap_size=16,
    num_queries=50,
    pow_bits=0,
    fri_final_degree=16,
)
REAL_SHA_BYTES = 8192
# What a run with no arguments proves. A cold run must end inside the
# driver's 1200 s, compiling included, and the v5e host compiles about as
# fast as four cores of the sandbox: the 1 kB circuit's kernel library took
# 539 s there, the parity phase's two libraries would take longer still,
# and the 8 kB library longer than either (CHANGES.md PR 22 has the
# seconds; ROADMAP.md S1 is the compile bill). So the default is upstream's
# smallest trace — every message up to 1 kB gives 2^14 rows (the lookup
# tables set the floor) at the same widths — without the parity phase, and
# the main phase's line says both under "reduced". `--sha-bytes 8192` is
# the real size, parity phase first.
DEFAULT_SHA_BYTES = 1024
# The parity circuit: a 2^14-row fma chain at LDE 4 — the smallest trace at
# which the MXU NTT (MIN_LOG_N = 14), the Pallas sponges and the limb sweep
# all dispatch. SHA-256 at 1 kB has lookups and would be the better probe,
# but its second (u64 XLA) kernel set took 795 s to compile for the v5e on
# eight cores and 39 GB of host memory, on a 40 GiB host (PR 22).
PARITY_LOG_N = 14
PARITY_LDE = 4
REQUIRED_PLATFORM = "tpu"
# the compile ledger must name these (substring match) for the main
# circuit's shape bucket, and none of their u64 twins
RESIDENT_KERNELS = (
    "coset_sweep_terms_limbres",
    ":lde_mxu_limbres_",
    ":leaf_digests_limbres",
    "node_layers_limbres",
)


class SmokeFailure(Exception):
    pass


def emit(obj: dict):
    print(json.dumps(obj), flush=True)


def device_record(devices) -> dict:
    d = devices[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
    }


def final_line(ok: bool, devices, error: str | None = None) -> dict:
    """The contract's last line. A passing line carries `ok` and `device`
    and nothing more."""
    line: dict = {"ok": bool(ok)}
    if devices:
        line["device"] = device_record(devices)
    if not ok:
        line["error"] = error or "failed"
    return line


def require_devices(devices, chips: int):
    if not devices or devices[0].platform != REQUIRED_PLATFORM:
        got = devices[0].platform if devices else "none"
        raise SmokeFailure(
            f"needs a {REQUIRED_PLATFORM} device, jax reports {got!r}"
        )
    if len(devices) < chips:
        raise SmokeFailure(
            f"--chips {chips} needs {chips} devices, jax reports "
            f"{len(devices)}"
        )


def plan(chips: int, sha_bytes: int) -> list[str]:
    """Which phases a run makes. The four-chip option runs the mesh phase
    (with its own single-chip comparison) and nothing else. On one chip
    the parity phase goes first at the real size, as the fault-finder
    before the large compile; the reduced default leaves it out, because
    its two kernel sets alone compile for most of the 1200 s a cold run
    has (DEFAULT_SHA_BYTES has the story), and says so under "reduced"."""
    if chips == 4:
        return ["mesh"]
    return ["parity", "main"] if sha_bytes >= REAL_SHA_BYTES else ["main"]


# ---------------------------------------------------------------------------
# Checks: pure functions of a phase's result line -> list of problems
# ---------------------------------------------------------------------------


def native_path_problems(counters: dict) -> list[str]:
    c = lambda k: int(counters.get(k, 0) or 0)  # noqa: E731
    problems = []
    if not (c("quotient.resident_coset_sweeps") == c("quotient.coset_sweeps") > 0):
        problems.append(
            "quotient.resident_coset_sweeps "
            f"{c('quotient.resident_coset_sweeps')} != quotient.coset_sweeps "
            f"{c('quotient.coset_sweeps')} (or both zero)"
        )
    if not (c("fri.resident_folds") == c("fri.folds") > 0):
        problems.append(
            f"fri.resident_folds {c('fri.resident_folds')} != fri.folds "
            f"{c('fri.folds')} (or both zero)"
        )
    for k in ("ntt.resident_transforms", "merkle.resident_commits",
              "deep.resident_codewords"):
        if c(k) < 1:
            problems.append(f"{k} is {c(k)}: the resident kernels did not run")
    for k in ("limb.splits", "limb.joins"):
        if c(k) > 0:
            problems.append(f"interior {k} = {c(k)} on a resident prove")
    return problems


def precompile_problems(res: dict) -> list[str]:
    """A kernel that failed to lower or compile is a failure of the smoke,
    not a log line (precompile() itself records it and carries on)."""
    problems = []
    for k in ("precompile.lower_errors", "precompile.compile_errors"):
        if int(res.get("precompile_counters", {}).get(k, 0) or 0):
            problems.append(f"{k} = {res['precompile_counters'][k]}")
    for e in res.get("ledger_errors", []):
        problems.append(f"kernel {e.get('name')} failed: {e.get('error')}")
    return problems


def kernel_name_problems(res: dict) -> list[str]:
    problems = []
    names = res.get("ledger_kernels", [])
    for want in RESIDENT_KERNELS:
        if not any(want in n for n in names):
            problems.append(f"compile ledger names no {want!r} kernel")
        # a u64 twin carries the same name without the suffix
        twin = want.replace("_limbres", "")
        hits = [n for n in names if twin in n and "_limbres" not in n]
        if hits:
            problems.append(f"compile ledger names u64 twin(s) {hits[:3]}")
    return problems


def check_parity(res: dict) -> list[str]:
    problems = precompile_problems(res)
    if res.get("verify_native") is not True:
        problems.append("native proof did not verify")
    if res.get("verify_xla") is not True:
        problems.append("force_xla proof did not verify")
    if res.get("proofs_equal") is not True:
        problems.append("native and force_xla proof bytes differ")
    problems += native_path_problems(res.get("native_counters", {}))
    xc = res.get("xla_counters", {})
    if int(xc.get("quotient.resident_coset_sweeps", 0) or 0):
        problems.append("force_xla prove dispatched resident kernels")
    return problems


def check_main(res: dict) -> list[str]:
    problems = precompile_problems(res) + kernel_name_problems(res)
    if res.get("verify") is not True:
        problems.append("proof did not verify")
    if res.get("warm_equals_cold") is not True:
        problems.append("warm proof bytes differ from the cold proof's")
    problems += native_path_problems(res.get("warm_counters", {}))
    if int(res.get("last_warm_cache_misses", -1)) != 0:
        problems.append(
            f"last warm prove compiled: {res.get('last_warm_cache_misses')} "
            "cache misses"
        )
    if not int(res.get("peak_bytes_in_use", 0) or 0) > 0:
        problems.append("device reports no peak_bytes_in_use")
    return problems


def check_mesh(res: dict) -> list[str]:
    problems = precompile_problems(res)
    if res.get("verify_single") is not True:
        problems.append("meshless proof did not verify")
    if res.get("verify_mesh") is not True:
        problems.append("mesh proof did not verify")
    if res.get("proofs_equal") is not True:
        problems.append("meshless and mesh proof bytes differ")
    if not float(res.get("mesh_gauges", {}).get("ici.all_to_all_bytes", 0)) > 0:
        problems.append("ici.all_to_all_bytes is zero: no pivot crossed chips")
    mesh_counters = res.get("mesh_counters", {})
    for name in ("merkle.resident_commits", "merkle.sm_commits"):
        if not int(mesh_counters.get(name, 0)) > 0:
            problems.append(f"{name} is zero on the mesh prove")
    peaks = res.get("peak_bytes_in_use_per_device", [])
    if len(peaks) != 4 or not all(int(p or 0) > 0 for p in peaks):
        problems.append(f"not every chip held data: peak bytes {peaks}")
    return problems


CHECKS = {"parity": check_parity, "main": check_main, "mesh": check_mesh}


# ---------------------------------------------------------------------------
# Phases: each returns its result line (a dict)
# ---------------------------------------------------------------------------


_T0 = time.perf_counter()


@contextlib.contextmanager
def _timed(seconds: dict, name: str):
    """Time one step into `seconds`, and say so on stderr as it ends: a
    call that is cut still shows where its time went."""
    t0 = time.perf_counter()
    raised = " (raised)"
    try:
        yield
        raised = ""
    finally:
        now = time.perf_counter()
        seconds[name] = round(now - t0, 3)
        print(
            f"[smoke +{now - _T0:7.1f}s] {name}: {seconds[name]} s{raised}",
            file=sys.stderr, flush=True,
        )


def _drain():
    """A prove returns host data, but dispatch is asynchronous: wait for
    whatever the device still holds in flight before stopping a clock."""
    import jax

    jax.block_until_ready(jax.live_arrays())


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def _recorded_prove(label, fn):
    """Run one prove under the flight recorder; return (proof, metrics)."""
    from boojum_tpu.utils import report as _report

    with _report.flight_recording(label=label, sync=False) as rec:
        proof = fn()
        _drain()
    return proof, rec.metrics.to_dict()


def _synthesize(build, seconds: dict):
    with _timed(seconds, "synthesis"):
        asm = build().into_assembly()
    from boojum_tpu.dag.resolver import NativeTapeResolver

    lp = asm.lookup_params
    facts = {
        "trace_len": int(asm.trace_len),
        "log_n": int(asm.trace_len).bit_length() - 1,
        "copy_columns": int(asm.geometry.num_columns_under_copy_permutation),
        "constant_columns": int(asm.geometry.num_constant_columns),
        "lookup_width": int(lp.width),
        "lookup_args": int(lp.num_repetitions),
        # native/__init__.get_lib falls back to the Python resolver in
        # silence: say which one synthesized this witness
        "resolver": (
            "native" if isinstance(asm.resolver, NativeTapeResolver)
            else "python"
        ),
    }
    return asm, facts


def _ledger_errors(ledger) -> list[dict]:
    return [
        {"name": e["name"], "error": e["error"]}
        for e in ledger.to_dict()["entries"] if e.get("error")
    ]


def _ledger_facts(ledger, shape: str) -> dict:
    entries = [e for e in ledger.to_dict()["entries"] if e.get("shape") == shape]
    s = ledger.summary()
    return {
        "ledger_kernels": sorted({e["name"] for e in entries}),
        "ledger_errors": _ledger_errors(ledger),
        "ledger": {
            "kernels": s["num_kernels"],
            "cache_hits": s["cache_hits"],
            "cache_misses": s["cache_misses"],
            "worst_graph": s["worst_graph"],
            "precompile_total_s": s["precompile_total_s"],
            "backend_compile_total_s": s["backend_compile_total_s"],
            "num_dispatch_compiles": s["num_dispatch_compiles"],
            "dispatch_compile_total_s": s["dispatch_compile_total_s"],
        },
    }


def _precompile(asm, cfg, ledger, workers, seconds, key, **kw):
    """The kernel library on the pool, under a recorder so the sweep's
    error counters are readable afterwards."""
    from boojum_tpu.prover import precompile
    from boojum_tpu.utils import report as _report

    with _report.flight_recording(label=key, sync=False) as rec:
        with _timed(seconds, key):
            precompile(asm, cfg, max_workers=workers, ledger=ledger, **kw)
    return {
        k: v for k, v in rec.metrics.to_dict()["counters"].items()
        if k.startswith("precompile.")
    }


def _sum_counters(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0) + b.get(k, 0) for k in sorted({*a, *b})}


def _slowest(*ledgers, n=6):
    """The longest compiles, pooled (ledger entries) and at dispatch."""
    rows = []
    for d in (led.to_dict() for led in ledgers):
        rows += d["entries"] + d["dispatch_compiles"]
    rows = sorted(rows, key=lambda e: -e["compile_s"])[:n]
    return [{"name": e["name"], "compile_s": round(e["compile_s"], 1)}
            for e in rows]


def phase_parity(build, circuit: str, process_ledger, workers: int) -> dict:
    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify
    from boojum_tpu.utils.pallas_util import force_xla
    from boojum_tpu.utils.profiling import CompileLedger

    cfg = ProofConfig(**{**PROOF_CONFIG, "fri_lde_factor": PARITY_LDE})
    # this phase's two kernel sets are recorded apart from the main
    # ledger, which must name no u64 twin (at --sha-bytes 1024 the two
    # phases share a shape bucket)
    ledger = CompileLedger()
    seconds: dict = {}
    asm, facts = _synthesize(build, seconds)
    pre = _precompile(asm, cfg, ledger, workers, seconds, "precompile_native")
    with force_xla():
        pre_x = _precompile(
            asm, cfg, ledger, workers, seconds, "precompile_xla"
        )
    with _timed(seconds, "setup"):
        setup = generate_setup(asm, cfg)
    with _timed(seconds, "prove_native"):
        p_native, m_native = _recorded_prove(
            "parity_native", lambda: prove(asm, setup, cfg)
        )
    with force_xla():
        with _timed(seconds, "prove_xla"):
            p_xla, m_xla = _recorded_prove(
                "parity_xla", lambda: prove(asm, setup, cfg)
            )
    with _timed(seconds, "verify"):
        v_native = bool(verify(setup.vk, p_native, asm.gates))
        v_xla = bool(verify(setup.vk, p_xla, asm.gates))
    return {
        "phase": "parity",
        "circuit": circuit,
        **facts,
        "lde": PARITY_LDE,
        "seconds": seconds,
        "verify_native": v_native,
        "verify_xla": v_xla,
        "proofs_equal": p_native.to_json() == p_xla.to_json(),
        "proof_bytes": len(p_native.to_json()),
        "precompile_counters": _sum_counters(pre, pre_x),
        "ledger_errors": _ledger_errors(ledger),
        "native_counters": _pick(m_native["counters"]),
        "xla_counters": _pick(m_xla["counters"]),
        "slowest_compiles": _slowest(ledger, process_ledger),
    }


_COUNTER_PREFIXES = (
    "quotient.", "fri.", "ntt.", "merkle.", "deep.", "limb.", "ici.",
)


def _pick(d: dict) -> dict:
    return {k: v for k, v in d.items() if k.startswith(_COUNTER_PREFIXES)}


def phase_main(build, circuit: str, ledger, workers: int, devices,
               reduced=None) -> dict:
    from boojum_tpu.prover import (
        ProofConfig, bucket_key, generate_setup, prove, verify,
    )

    cfg = ProofConfig(**PROOF_CONFIG)
    seconds: dict = {}
    asm, facts = _synthesize(build, seconds)
    pre_counters = _precompile(
        asm, cfg, ledger, workers, seconds, "precompile"
    )
    with _timed(seconds, "setup"):
        setup = generate_setup(asm, cfg)
    with _timed(seconds, "cold_prove"):
        proof = prove(asm, setup, cfg)
        _drain()
    with _timed(seconds, "verify"):
        verified = bool(verify(setup.vk, proof, asm.gates))
    with _timed(seconds, "warm_prove_1"):
        prove(asm, setup, cfg)
        _drain()
    misses0 = ledger.summary()["cache_misses"]
    with _timed(seconds, "warm_prove_2"):
        warm, metrics = _recorded_prove(
            "warm_prove_2", lambda: prove(asm, setup, cfg)
        )
    misses1 = ledger.summary()["cache_misses"]
    import jax

    return {
        "phase": "main",
        "circuit": circuit,
        **({"reduced": reduced} if reduced else {}),
        **facts,
        "proof_config": dict(PROOF_CONFIG),
        "seconds": seconds,
        "verify": verified,
        "warm_equals_cold": warm.to_json() == proof.to_json(),
        "proof_bytes": len(proof.to_json()),
        "precompile_counters": pre_counters,
        **_ledger_facts(ledger, bucket_key(asm, cfg)),
        "slowest_compiles": _slowest(ledger),
        "last_warm_cache_misses": misses1 - misses0,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "peak_bytes_in_use": _peak_bytes(devices[0]),
        "warm_counters": _pick(metrics["counters"]),
    }


def phase_mesh(build, circuit: str, ledger, workers: int, devices) -> dict:
    from boojum_tpu.parallel.sharding import make_mesh
    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify

    cfg = ProofConfig(**PROOF_CONFIG)
    seconds: dict = {}
    asm, facts = _synthesize(build, seconds)
    mesh = make_mesh(devices[:4])
    pre = _precompile(asm, cfg, ledger, workers, seconds, "precompile_single")
    pre_m = _precompile(
        asm, cfg, ledger, workers, seconds, "precompile_mesh",
        mesh_shape=mesh,
    )
    with _timed(seconds, "setup"):
        setup = generate_setup(asm, cfg)
    with _timed(seconds, "prove_single"):
        p_single, _m = _recorded_prove(
            "single", lambda: prove(asm, setup, cfg)
        )
    with _timed(seconds, "prove_mesh"):
        p_mesh, m_mesh = _recorded_prove(
            "mesh", lambda: prove(asm, setup, cfg, mesh=mesh)
        )
    with _timed(seconds, "verify"):
        v_single = bool(verify(setup.vk, p_single, asm.gates))
        v_mesh = bool(verify(setup.vk, p_mesh, asm.gates))
    return {
        "phase": "mesh",
        "circuit": circuit,
        **facts,
        "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
        "seconds": seconds,
        "verify_single": v_single,
        "verify_mesh": v_mesh,
        "proofs_equal": p_single.to_json() == p_mesh.to_json(),
        "precompile_counters": _sum_counters(pre, pre_m),
        "ledger_errors": _ledger_errors(ledger),
        "mesh_counters": _pick(m_mesh["counters"]),
        "mesh_gauges": _pick(m_mesh["gauges"]),
        "peak_bytes_in_use_per_device": [
            _peak_bytes(d) for d in devices[:4]
        ],
        "slowest_compiles": _slowest(ledger),
    }


def default_phases(opts, devices, build_main=None, build_parity=None) -> dict:
    """The real phases as zero-argument callables, keyed by plan() name.
    `build_main`/`build_parity` swap the circuits (the CPU rehearsal proves
    a small example circuit); the default is upstream's SHA-256 bench."""
    from boojum_tpu.examples import (
        build_fma_bench_circuit,
        build_sha256_bench_circuit,
    )
    from boojum_tpu.utils.profiling import start_compile_ledger

    ledger = start_compile_ledger()
    # per-graph names for what compiles at dispatch (setup, the query
    # phase: graphs the enumeration leaves out): jax logs them at DEBUG on
    # these two loggers, where the ledger's handler sits; they stop
    # propagating so that stderr keeps to this script's progress lines
    for name in ("jax._src.dispatch", "jax._src.interpreters.pxla"):
        logging.getLogger(name).setLevel(logging.DEBUG)
        logging.getLogger(name).propagate = False
    workers = max(8, os.cpu_count() or 8)
    sha = f"sha256_{opts.sha_bytes}B"
    main = build_main or (lambda: build_sha256_bench_circuit(opts.sha_bytes))
    parity = build_parity or (lambda: build_fma_bench_circuit(PARITY_LOG_N))
    reduced = None
    if opts.sha_bytes < REAL_SHA_BYTES:
        reduced = {
            "sha_bytes": [REAL_SHA_BYTES, opts.sha_bytes],
            "parity_phase": "not run below the real size",
        }
    return {
        "parity": lambda: phase_parity(
            parity, f"fma_2^{PARITY_LOG_N}", ledger, workers
        ),
        "main": lambda: phase_main(
            main, sha, ledger, workers, devices, reduced=reduced
        ),
        "mesh": lambda: phase_mesh(main, sha, ledger, workers, devices),
    }


def _failure_line(e: Exception, devices) -> dict:
    traceback.print_exc(file=sys.stderr)
    msg = str(e) if isinstance(e, SmokeFailure) else repr(e)
    return final_line(False, devices, msg)


def run(opts, devices, phases: dict) -> dict:
    """Drive the planned phases; print one line per phase; return the
    final line. Never raises: every failure becomes `ok: false`."""
    try:
        require_devices(devices, opts.chips)
        for name in plan(opts.chips, opts.sha_bytes):
            res = phases[name]()
            emit(res)
            problems = CHECKS[name](res)
            if problems:
                raise SmokeFailure(f"{name}: " + "; ".join(problems))
    except Exception as e:  # noqa: BLE001 — where every failure lands
        return _failure_line(e, devices)
    return final_line(True, devices)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--sha-bytes", type=int, default=None,
        help=f"message size of the SHA-256 circuit (default "
        f"{DEFAULT_SHA_BYTES}; with --chips 4, {REAL_SHA_BYTES})",
    )
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)
    if opts.sha_bytes is None:
        # the mesh phase exists for the 2^16 trace; one host with four chips
        # and thirty cores compiles it inside a call
        opts.sha_bytes = REAL_SHA_BYTES if opts.chips == 4 else DEFAULT_SHA_BYTES
    return opts


def main(argv=None) -> int:
    opts = parse_args(argv)
    devices = None
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        # the package first: it sets x64, the one compile-cache rule and the
        # TPU compiler's stack size, which libtpu reads as the backend starts
        import boojum_tpu  # noqa: F401
        import jax

        devices = jax.devices()
        require_devices(devices, opts.chips)

        emit({
            "phase": "start",
            "device": device_record(devices),
            "jax": jax.__version__,
            "cache_dir": jax.config.jax_compilation_cache_dir,
            "cpu_count": os.cpu_count(),
            "plan": plan(opts.chips, opts.sha_bytes),
        })
        phases = default_phases(opts, devices)
    except Exception as e:  # noqa: BLE001 — no JAX, no TPU, no package
        emit(_failure_line(e, devices))
        return 1
    line = run(opts, devices, phases)
    emit(line)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
