"""Headline benchmark. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ...}

Headline circuit: the reference's SHA-256 bench (8 kB message through the
lookup-table SHA-256 gadget; reference src/gadgets/sha256/mod.rs:269 and
README "For curions in benchmarks": 60 copy columns, 8 width-4 lookup
sub-arguments, LDE factor 8, cap 16; the reference trace is 2^16 rows — the
2^17 passed to the CS below is a CAPACITY bound, pad_and_shrink rounds the
actual trace to the smallest power of two that fits and the bench prints the
realized trace length on stderr). The timed quantity is the proving
wall-clock with warm compile caches (the reference's "Proving is done,
taken ..." line measures the same region).

Robustness: a cold cache costs minutes of compiling per big fused graph.
A watchdog thread guarantees the JSON line is printed within BENCH_BUDGET_S
seconds no matter what: if the full protocol hasn't finished by then, the
line carries whatever was measured so far plus a "status" field, and the
process exits 1. A completed run reports status "ok".

Cold-start posture (ISSUE 1): before the first prove the bench runs the
parallel PRECOMPILE sweep (boojum_tpu/prover/precompile.py) — the split
prover kernel library compiles concurrently through a thread pool instead
of serially at first dispatch, and lands in the persistent cache below. A
compile LEDGER (per-kernel trace/compile seconds, cache hit/miss counts)
rides along on every JSON line and is written to BENCH_LEDGER_JSON, so a
timeout is diagnosable from the JSON alone and compile-bill regressions
are visible across rounds.

AOT artifacts (ISSUE 8): with BOOJUM_TPU_AOT_DIR set the bench consults
the artifact store (boojum_tpu/prover/aot.py) before anything traces —
a matching pre-built bundle replaces the precompile sweep outright, the
warm phase becomes O(deserialization), and the ledger attributes it via
aot_hits/aot_deserialize_s instead of compile seconds. Build the bundle
once per (circuit, config, platform) with `--build-artifacts` (or
scripts/build_artifacts.py) and every later cold process skips the
compile bill entirely.

Usage: python bench.py [--precompile-only] [--no-precompile] [--service]
                       [--build-artifacts]
  --precompile-only runs synthesis + the parallel precompile, emits the
  ledger JSON line and exits — a cache-warming step to run before a bench
  or a multihost round.
  --build-artifacts runs synthesis + the full AOT bundle build (kernel
  library + setup + one capture prove, persistent cache redirected into
  the bundle) under BOOJUM_TPU_AOT_DIR (default ./aot_artifacts), emits
  the ledger line and exits.
  --no-precompile skips the pre-prove parallel precompile sweep (the
  sweep runs BY DEFAULT before the warm-up prove: round 4's watchdog
  burned the whole budget on serial cold compiles, so BENCH lines never
  measured a prove; equivalent to BENCH_PRECOMPILE=0).
  --service measures THROUGHPUT instead of single-proof wall: after the
  warm-up prove, BENCH_SERVICE_REQS requests (default 4) of the bench
  circuit drain through the boojum_tpu/service/ scheduler
  (shape-bucketed queue, device-resident caches, shard- vs
  proof-parallel placement) and the JSON line's metric becomes
  <circuit>_service_proofs_per_sec with the service summary (placements,
  queue, cache hits/evictions) attached — so BENCH rounds can track
  proofs/sec, not just prove wall. BOOJUM_TPU_SERVICE_* flags apply.

Environment knobs:
  BENCH_CIRCUIT = sha256 (default) | fma
  BENCH_SHA_BYTES = message size (default 8192)
  BENCH_LOG_N = fma-mode trace log2 size (default 10)
  BENCH_REPS = timed repetitions (default 3)
  BENCH_BUDGET_S = hard wall-clock budget before the watchdog reports
      (default 1500)
  BENCH_LDE = FRI commit rate override (default 8 sha / 4 fma; the
      quotient still evaluates at the degree-derived rate — BENCH_LDE=2 is
      the Era main-VM golden-proof commit rate and what 2^20-row traces
      use to stay inside HBM)
  BENCH_QUERIES = FRI query count (default 50; the reference's LDE-2
      golden proof uses 100)
  BENCH_SKIP_NTT = 1 skips the NTT-throughput side metric
  BENCH_PRECOMPILE = 0 skips the pre-prove parallel precompile sweep
      (same as --no-precompile; the sweep is ON by default)
  BENCH_PRECOMPILE_WORKERS = thread-pool width for it (default 8)
  BENCH_LEDGER_JSON = compile-ledger artifact path (default
      compile_ledger.json next to this file)
  BENCH_LOG_COMPILES = 0 disables jax_log_compiles (on by default so the
      ledger can attribute dispatch-time compiles to graph names)
  BOOJUM_TPU_BLACKBOX / BOOJUM_TPU_STALL_S arm the black-box recorder
      (boojum_tpu/utils/blackbox.py): crash-safe heartbeat sidecar +
      stall/SIGTERM stack dumps into the report artifact (ISSUE 15)
  BENCH_SETUP_DEADLINE_S / BENCH_WARMUP_DEADLINE_S / BENCH_REP_DEADLINE_S
      per-phase blackbox deadline alarms (defaults 300/600/60; =0
      disables one; no-ops when the blackbox is not armed)
  BOOJUM_TPU_REPORT = <path.jsonl> records every prove (warm-up + reps)
      through the flight recorder and appends one labeled ProveReport
      JSONL line each: hierarchical span tree, metrics (device memory,
      transfer bytes, NTT/Merkle/FRI counts), Fiat–Shamir digest
      checkpoints, compile-ledger summary. Inspect/diff with
      scripts/prove_report.py (see BASELINE.md "Observability protocol").

JSON line schema 2: adds "schema", promotes the per-stage split to every
line (warm-up split until the first timed rep lands, so even a watchdog
line carries one) and "peak_mem" (device high-water where the backend
exposes memory_stats, live-buffer census bytes, host max RSS). Non-"ok"
lines additionally carry "span_tree": the partial flight-recorder span
tree of the prove in flight (open spans annotated "unclosed"), so a
watchdog timeout localizes to the exact sub-stage instead of `{}`.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_T0 = time.perf_counter()


def _log(msg):
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _enable_compile_cache():
    """The package's one cache rule (boojum_tpu/compile_cache.py), with
    the bench's persist-everything threshold: the 2^16 prove traces ~500
    distinct graphs, and at the 1.0 s default ~400 of them would recompile
    in every fresh process."""
    from boojum_tpu import compile_cache

    compile_cache.enable(min_compile_time_secs=0.0)


_enable_compile_cache()


def _start_ledger():
    """Process-wide compile ledger + per-graph compile logging. Runs after
    the cache dir is pinned (importing boojum_tpu configures jax)."""
    try:
        import jax

        if os.environ.get("BENCH_LOG_COMPILES", "").strip() != "0":
            jax.config.update("jax_log_compiles", True)
        from boojum_tpu.utils.profiling import start_compile_ledger

        return start_compile_ledger()
    except Exception as e:
        _log(f"compile ledger unavailable: {e!r}")
        return None


_LEDGER = _start_ledger()

# ---------------------------------------------------------------------------
# Watchdog: the driver kills the bench (rc=124, no JSON parsed) if it runs
# past its timeout. A long compile blocks the main thread inside C++ where
# Python signals never fire, so a daemon THREAD prints the best-known
# result and hard-exits (nonzero) while the main thread is still blocked.
# ---------------------------------------------------------------------------

_STATE = {
    "metric": None,
    "unit": "s",
    "phase": "import",
    "reps": [],           # completed timed rep walls (service mode:
                          # the single proofs/sec figure)
    "service": None,      # --service: the service drain summary
    "warm_wall": None,    # warm-up (first, compile-laden) prove wall
    "stages": {},         # per-stage split of the reported rep (the warm-up
                          # split until the first timed rep lands, so EVERY
                          # line — including the watchdog's — carries one)
    "peak_mem": {},       # device/host memory high water, updated per prove
    "ntt_eps": None,
    "done": False,
}
_EMIT_LOCK = threading.Lock()

# bench JSON line schema version. 2: stage split and peak_mem promoted to
# every line (previously only present when the stage sink happened to be
# installed), schema field added.
_LINE_SCHEMA = 2

# the LIVE stage sink of the prove currently in flight: the watchdog reads
# it when _STATE["stages"] has no completed-prove split yet, so a line
# fired MID-prove (the stuck-compile case schema 2 exists to diagnose)
# still shows which stages finished before the stall
_LIVE_SINK = {"sink": None}

# the LIVE span recorder of the prove in flight (the PR 2 flight
# recorder's time axis): a watchdog line fired mid-phase carries the
# PARTIAL hierarchical span tree — open spans annotated "unclosed" with
# their elapsed wall — instead of an empty stage split, so a timeout
# localizes to the exact sub-stage that stalled (BENCH_r04 gave
# `"stages": {}` and no localization at all). _prove_recorded installs a
# recorder for EVERY prove, with or without BOOJUM_TPU_REPORT. "bench"
# holds the BENCH-LIFETIME recorder main() installs before the first
# phase: a watchdog line fired OUTSIDE a prove (precompile / AOT load /
# setup — exactly where BENCH_r03/r04 burned their budgets) falls back
# to it, so those phases' spans (precompile_compile_pool, aot_load,
# aot_warm, setup stages) localize the stall too.
_LIVE_REC = {"rec": None, "bench": None, "flight": None}


def _set_phase(name):
    """One phase transition: the bench JSON line's `phase` field and the
    blackbox heartbeat stream (utils/blackbox.py) must never disagree
    about where the budget went."""
    _STATE["phase"] = name
    try:
        from boojum_tpu.utils import blackbox as _bb

        _bb.set_phase(name)
    except Exception:
        pass


def _phase_deadline(name, env, default_s):
    """A blackbox deadline alarm for one phase ("setup may take 300 s, a
    rep may take 60 s") — expiry produces a LOCALIZED stack dump instead
    of a silent global watchdog line. A no-op nullcontext when no
    blackbox is armed or the env var disables it (=0)."""
    import contextlib

    try:
        from boojum_tpu.utils import blackbox as _bb

        bb = _bb.current_blackbox()
        if bb is None:
            return contextlib.nullcontext()
        budget = float(os.environ.get(env, "") or default_s)
        if budget <= 0:
            return contextlib.nullcontext()
        return bb.deadline(name, budget)
    except Exception:
        return contextlib.nullcontext()


def _partial_span_tree():
    rec = _LIVE_REC["rec"] or _LIVE_REC["bench"]
    if rec is None:
        return None
    try:
        tree = rec.tree()
        return tree or None
    except Exception:
        return None


def _update_peak_mem():
    """Fold current device/host memory high-water marks into _STATE
    (best-effort: XLA:CPU exposes no device stats; ru_maxrss always
    works on linux)."""
    pm = dict(_STATE["peak_mem"])
    try:
        from boojum_tpu.utils import metrics as _metrics

        dm = _metrics.device_memory_stats()
        if dm:
            for k in ("bytes_in_use", "peak_bytes_in_use"):
                if k in dm:
                    pm[f"device_{k}"] = max(pm.get(f"device_{k}", 0), dm[k])
        census = _metrics.live_buffer_census()
        if census is not None:
            pm["live_buffer_bytes"] = max(
                pm.get("live_buffer_bytes", 0), census[1]
            )
    except Exception:
        pass
    try:
        import resource

        pm["host_max_rss_kb"] = resource.getrusage(
            resource.RUSAGE_SELF
        ).ru_maxrss
    except Exception:
        pass
    with _EMIT_LOCK:
        if not _STATE["done"]:
            _STATE["peak_mem"] = pm


def _prove_recorded(label, fn):
    """Run one prove; with BOOJUM_TPU_REPORT set, record it as a labeled
    ProveReport JSONL line (span tree + metrics + digest checkpoints +
    compile-ledger summary — utils/report.py). WITHOUT the env var a bare
    SpanRecorder still runs so a watchdog line fired mid-prove can carry
    the partial span tree (nothing is written anywhere in that mode)."""
    path = os.environ.get("BOOJUM_TPU_REPORT")
    if not path:
        from boojum_tpu.utils import spans as _spans

        rec = _spans.SpanRecorder(sync=False)
        _LIVE_REC["rec"] = rec
        prev = _spans.install_recorder(rec)
        try:
            out = fn()
            # success: drop the ref so a later stall OUTSIDE a prove never
            # reports this finished tree as "the prove in flight" (a prove
            # that RAISED keeps it — its partial tree is the diagnosis)
            _LIVE_REC["rec"] = None
        finally:
            _spans.install_recorder(prev)
            _update_peak_mem()
        return out
    from boojum_tpu.utils import report as _report

    with _report.flight_recording(label=label) as rec:
        _LIVE_REC["rec"] = rec.spans
        # the watchdog flushes THIS recorder's partial line if the prove
        # is still in flight when the budget dies (os._exit skips the
        # finally below — exactly how r03/r04 lost their artifacts)
        _LIVE_REC["flight"] = rec
        try:
            out = fn()
            _LIVE_REC["rec"] = None
        finally:
            # a failed prove still leaves its (partial, error-annotated)
            # report line — that is the diagnosable-timeout posture the
            # watchdog/ledger already follow
            _update_peak_mem()
            try:
                _report.append_jsonl(path, _report.build_report(rec))
                _log(f"ProveReport line ({label}) appended to {path}")
                _LIVE_REC["flight"] = None
            except Exception as e:  # recorder must never sink the bench
                _log(f"ProveReport write failed: {e!r}")
    return out


def _live_stage_split():
    """Snapshot the in-flight prove's completed stages (empty when no
    prove has started)."""
    sink = _LIVE_SINK["sink"]
    if not sink:
        return {}
    return {name: round(dt, 3) for name, dt in list(sink)}


def _emit(status):
    """Print the one JSON line (exactly once) and return it."""
    with _EMIT_LOCK:
        if _STATE["done"]:
            return
        _STATE["done"] = True
        reps = sorted(_STATE["reps"])
        if reps:
            value = reps[len(reps) // 2]
        elif _STATE["warm_wall"] is not None:
            # no clean rep, but the protocol DID complete once (compile
            # time included) — report that wall, flagged
            value = _STATE["warm_wall"]
            status = status + "+warm_only"
        else:
            # nothing completed: report elapsed as a lower bound
            value = round(time.perf_counter() - _T0, 1)
            status = status + "+no_prove"
        out = {
            "metric": _STATE["metric"] or "sha256_8192B_prove_wall",
            "value": round(value, 4),
            "unit": _STATE["unit"],
            "schema": _LINE_SCHEMA,
            "status": status,
            "phase": _STATE["phase"],
            "reps": [round(r, 4) for r in _STATE["reps"]],
            "stages": _STATE["stages"] or _live_stage_split(),
            "peak_mem": _STATE["peak_mem"],
        }
        # which field backend ran (ISSUE 20): a babybear line moves half
        # the bytes of the same goldilocks geometry, so --trend /--slo
        # must split series by field straight from the line
        try:
            from boojum_tpu.field.spec import active_field

            out["field"] = active_field()
        except Exception:
            pass
        if _STATE["service"] is not None:
            out["service"] = _STATE["service"]
        if status != "ok":
            # a watchdog/failure line localizes the stall: the partial
            # hierarchical span tree of the prove in flight (open spans
            # carry error="unclosed" + elapsed wall), not just the flat
            # stage split
            tree = _partial_span_tree()
            if tree is not None:
                out["span_tree"] = tree
        if _STATE["ntt_eps"] is not None:
            out["ntt_goldilocks_elems_per_s"] = _STATE["ntt_eps"]
        # which on-device representation ran (ISSUE 10): BENCH_r05+ can
        # attribute any wall-clock delta to the limb-resident pipeline
        # (or its absence) straight from the line
        try:
            from boojum_tpu.utils.pallas_util import resolve_variant

            out["limb_resident"] = resolve_variant().planes
        except Exception:
            pass
        # machine/software identity (ISSUE 12): the same block the AOT
        # manifest validates on, so --trend groups this line with the
        # right machine's history
        try:
            from boojum_tpu.prover.aot import platform_info

            out["host"] = platform_info()
        except Exception:
            pass
        # the roofline cost record of the last completed prove (ISSUE
        # 12): per-stage achieved GFLOP/s & GB/s vs peak — the "which
        # kernel left perf on the table" axis BENCH_r05+ lines carry
        # (the kernel list is the analytic sheet's coverage; it rides
        # the report artifact, not this line)
        try:
            from boojum_tpu.utils import costmodel as _costmodel

            rec_cost = _costmodel.last_cost_record()
            if rec_cost:
                out["cost"] = {
                    k: v for k, v in rec_cost.items()
                    if k not in ("kernels", "attributed_kernels")
                }
        except Exception:
            pass
        # live-telemetry time series (queue-less in bench, but device
        # memory + live-buffer census over the whole run): the same
        # `telemetry` record the service's report lines carry, so a
        # watchdog line shows WHEN memory climbed, not just the peak
        try:
            from boojum_tpu.utils import telemetry as _telemetry

            sampler = _telemetry.current_sampler()
            if sampler is not None:
                out["telemetry"] = sampler.snapshot()
        except Exception:
            pass
        # the compile-ledger summary rides on EVERY line (including the
        # watchdog's) so a timeout is diagnosable from the JSON alone:
        # which graph compiled longest, how much the cache saved, whether
        # the process was still paying compile when the budget ran out
        if _LEDGER is not None:
            try:
                out["compile_ledger"] = _LEDGER.summary()
                ledger_path = os.environ.get(
                    "BENCH_LEDGER_JSON",
                    os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "compile_ledger.json",
                    ),
                )
                _LEDGER.dump_json(ledger_path)
                out["compile_ledger"]["artifact"] = ledger_path
            except Exception:
                pass
        print(json.dumps(out), flush=True)


def _flush_report_artifact():
    """ISSUE 15 satellite: make the BOOJUM_TPU_REPORT artifact durable
    BEFORE the timeout JSON line prints. The r03/r04 rounds left NO
    partial JSONL because the in-flight prove's report line is appended
    in a finally that os._exit never reaches — so the watchdog appends
    that partial line itself, then fsyncs the artifact."""
    path = os.environ.get("BOOJUM_TPU_REPORT")
    if not path:
        return
    flight = _LIVE_REC.get("flight")
    if flight is not None:
        try:
            from boojum_tpu.utils import report as _report

            _report.append_jsonl(path, _report.build_report(flight))
            _log(f"partial ProveReport line flushed to {path}")
        except Exception as e:
            _log(f"partial ProveReport flush failed: {e!r}")
    try:
        with open(path, "a") as f:
            f.flush()
            os.fsync(f.fileno())
    except Exception:
        pass


def _watchdog(budget_s):
    deadline = _T0 + budget_s
    while True:
        now = time.perf_counter()
        if _STATE["done"]:
            return
        if now >= deadline:
            _log(f"watchdog fired in phase {_STATE['phase']!r}")
            # forensics BEFORE the JSON line: an armed blackbox dumps
            # all-thread stacks + span tree into the sidecar/artifact,
            # and the report artifact is flushed+fsynced — the timeout
            # line is the LAST thing this process says, never the only
            try:
                from boojum_tpu.utils import blackbox as _bb

                bb = _bb.current_blackbox()
                if bb is not None:
                    bb.dump("watchdog", budget_s=budget_s)
            except Exception:
                pass
            _flush_report_artifact()
            _emit("timeout")
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)
        time.sleep(min(5.0, deadline - now))


from boojum_tpu.examples import (  # noqa: E402
    build_fma_bench_circuit as build_fma,
    build_sha256_bench_circuit as build_sha256,
)


def _measure_ntt():
    """NTT throughput (BASELINE.md tracked metric): Goldilocks elems/s for a
    batched forward+inverse pair at bench scale, warm."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from boojum_tpu.ntt import (
            fft_natural_to_bitreversed,
            ifft_bitreversed_to_natural,
        )

        cols, log_n = 64, 16
        rng = np.random.default_rng(0)
        from boojum_tpu.field import gl

        a = jnp.asarray(
            rng.integers(0, gl.P, size=(cols, 1 << log_n), dtype=np.uint64)
        )
        ntt_reps = 8

        # chain the reps ON DEVICE (one dispatch), so the reading is the
        # chip's and not the per-launch host overhead
        @jax.jit
        def _ntt_chain(x):
            def body(_, v):
                return ifft_bitreversed_to_natural(
                    fft_natural_to_bitreversed(v)
                )

            return jax.lax.fori_loop(0, ntt_reps, body, x)

        jax.block_until_ready(_ntt_chain(a))  # compile
        t1 = time.perf_counter()
        jax.block_until_ready(_ntt_chain(a))
        dt = time.perf_counter() - t1
        _STATE["ntt_eps"] = int(2 * ntt_reps * cols * (1 << log_n) / dt)
    except Exception as e:
        _log(f"ntt side metric failed: {e!r}")


def main():
    budget = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    threading.Thread(target=_watchdog, args=(budget,), daemon=True).start()
    # black-box recorder (ISSUE 15): with BOOJUM_TPU_BLACKBOX /
    # BOOJUM_TPU_STALL_S armed, a heartbeat thread stamps a crash-safe
    # sidecar (phase, open span, compile deltas, rss) and stall /
    # deadline / SIGTERM dumps land in the report artifact — the layer
    # that turns the next rc=124 into a stack trace
    try:
        from boojum_tpu.utils import blackbox as _bb

        _bb.ensure_started(label="bench")
    except Exception as e:
        _log(f"blackbox failed to start: {e!r}")

    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify
    from boojum_tpu.utils.profiling import collect_stages, stop_collecting_stages
    from boojum_tpu.utils import spans as _spans

    # bench-lifetime span recorder: the per-prove recorders of
    # _prove_recorded install OVER it (and restore it after), so a
    # watchdog line fired in ANY phase — precompile, AOT load, setup —
    # carries a span tree instead of "stages": {}
    bench_rec = _spans.SpanRecorder(sync=False)
    _LIVE_REC["bench"] = bench_rec
    _spans.install_recorder(bench_rec)

    # bench-lifetime telemetry sampler (BOOJUM_TPU_TELEMETRY_INTERVAL
    # cadence, =0 is rejected by the parser — there is no off switch
    # because a 1 Hz census costs microseconds): every ProveReport line
    # and the final bench JSON line carry its time series
    try:
        from boojum_tpu.utils import telemetry as _telemetry

        sampler = _telemetry.TelemetrySampler()
        _telemetry.install_sampler(sampler)
        sampler.start()
    except Exception as e:
        _log(f"telemetry sampler failed to start: {e!r}")

    circuit = os.environ.get("BENCH_CIRCUIT", "sha256")
    reps = int(os.environ.get("BENCH_REPS", "3"))
    lde = int(
        os.environ.get("BENCH_LDE", "8" if circuit == "sha256" else "4")
    )
    config = ProofConfig(
        fri_lde_factor=lde,
        merkle_tree_cap_size=16,
        num_queries=int(os.environ.get("BENCH_QUERIES", "50")),
        pow_bits=0,
        fri_final_degree=16,
    )
    _set_phase("synthesis")
    if circuit == "sha256":
        num_bytes = int(os.environ.get("BENCH_SHA_BYTES", "8192"))
        cs = build_sha256(num_bytes)
        _STATE["metric"] = f"sha256_{num_bytes}B_prove_wall"
    else:
        log_n = int(os.environ.get("BENCH_LOG_N", "10"))
        cs = build_fma(log_n)
        _STATE["metric"] = f"fma_2^{log_n}_prove_wall"

    asm = cs.into_assembly()
    print(f"trace_len={asm.trace_len}", file=sys.stderr, flush=True)
    if "--build-artifacts" in sys.argv:
        # AOT build step: compile the whole dispatch surface (kernel
        # library + setup + one full prove) into a deployment bundle
        # under BOOJUM_TPU_AOT_DIR (default ./aot_artifacts), emit the
        # ledger line and exit — after this, a cold process proves with
        # zero XLA compiles (see BASELINE.md "AOT artifact protocol")
        _set_phase("build_artifacts")
        from boojum_tpu.prover import aot as _aot

        out_root = _aot.aot_dir() or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "aot_artifacts"
        )
        workers = int(os.environ.get("BENCH_PRECOMPILE_WORKERS", "8"))
        _log(f"building AOT artifact bundle under {out_root}")
        manifest = _aot.build_bundle(
            asm, config, out_root, ledger=_LEDGER, max_workers=workers
        )
        _log(
            f"bundle {manifest['dir']}: {manifest['num_kernels']} kernels"
            f" ({manifest['num_exports']} exported), "
            f"{manifest['cache_bytes'] / 2**20:.1f} MiB cache"
        )
        _emit("build_artifacts")
        return

    precompile_only = "--precompile-only" in sys.argv
    no_precompile = (
        "--no-precompile" in sys.argv
        or os.environ.get("BENCH_PRECOMPILE", "").strip() == "0"
    )
    aot_warmed = False
    if os.environ.get("BOOJUM_TPU_AOT_DIR", "").strip():
        # artifact store first: a bundle hit replaces the precompile
        # sweep outright — the warm phase becomes O(deserialization) and
        # each kernel's ledger entry carries aot_hit, so the warm-up
        # wall on this run's JSON line is attributed to deserialization
        # rather than compilation
        _set_phase("aot_load")
        from boojum_tpu.prover import aot as _aot

        try:
            stats = _aot.load_and_warm(
                _aot.aot_dir(), asm, config, ledger=_LEDGER
            )
        except _aot.AotBundleError:
            # BOOJUM_TPU_AOT_REQUIRE: a missing/stale bundle is a hard
            # failure, not a silent fall-through to the compile bill
            raise
        except Exception as e:  # noqa: BLE001 — an unexpected loader
            # bug must degrade to the precompile sweep, not kill the run
            _log(f"aot load failed (continuing to precompile): {e!r}")
            stats = None
        if stats is not None and not stats.get("aborted"):
            aot_warmed = True
            _log(f"aot warm done: {json.dumps(stats)}")
        else:
            _log("no usable AOT bundle; falling back to precompile sweep")
    if (precompile_only or not no_precompile) and not aot_warmed:
        # compile the kernel library on the pool BEFORE the first dispatch
        # pays for it serially; everything lands in the persistent cache
        _set_phase("precompile")
        workers = int(os.environ.get("BENCH_PRECOMPILE_WORKERS", "8"))
        _log(f"parallel precompile of the kernel library ({workers} workers)")
        try:
            from boojum_tpu.prover.precompile import precompile

            led = precompile(
                asm, config, max_workers=workers, ledger=_LEDGER
            )
            _log(
                "precompile done: "
                f"{json.dumps(led.summary())}"
            )
        except Exception as e:
            if precompile_only:
                raise
            _log(f"precompile failed (continuing to prove): {e!r}")
    if precompile_only:
        _emit("precompile_only")
        return

    _set_phase("setup")
    _log("generating setup (compiles on a cold cache)")
    with _phase_deadline("setup", "BENCH_SETUP_DEADLINE_S", 300.0):
        setup = generate_setup(asm, config)

    # warm-up (compiles) then timed runs; report the MEDIAN rep and its
    # per-stage wall-clock split (a one-chip machine shares its host's
    # cores, so a single rep is not a number of record). The stage sink runs from the
    # warm-up on, so every emitted line — including a watchdog line fired
    # mid-warm-up — carries a stage split (schema 2).
    _set_phase("warmup_prove")
    _log("warm-up prove (compiles on a cold cache)")
    sink = collect_stages()
    _LIVE_SINK["sink"] = sink
    t0 = time.perf_counter()
    with _phase_deadline("warmup_prove", "BENCH_WARMUP_DEADLINE_S", 600.0):
        proof = _prove_recorded("warmup", lambda: prove(asm, setup, config))
    _STATE["warm_wall"] = round(time.perf_counter() - t0, 4)
    with _EMIT_LOCK:
        if not _STATE["done"]:
            _STATE["stages"] = {name: round(dt, 3) for name, dt in sink}
    _log(f"warm-up prove done in {_STATE['warm_wall']}s; verifying")
    _set_phase("verify")
    assert verify(setup.vk, proof, asm.gates)

    if "--service" in sys.argv:
        # throughput mode: drain BENCH_SERVICE_REQS requests through the
        # proving service (shape-bucketed queue, device-resident caches,
        # scheduler-picked placement) and report proofs/sec — the number
        # BENCH rounds need once single-proof wall stops being the
        # bottleneck. The warm-up prove above already validated parity
        # and warmed the caches the service will hit.
        _set_phase("service_drain")
        from boojum_tpu.service import ProvingService, ServiceConfig

        scfg = ServiceConfig.from_env()
        if not os.environ.get("BOOJUM_TPU_SERVICE_PRECOMPILE", "").strip():
            # the bench's own precompile sweep already filled the cache
            # for the variant a meshless/proof-parallel drain dispatches
            scfg.precompile = "off"
        svc = ProvingService(scfg)
        nreq = int(os.environ.get("BENCH_SERVICE_REQS", "4"))
        _log(
            f"service drain: {nreq} requests, "
            f"mesh={None if svc.mesh is None else dict(svc.mesh.shape)}"
        )
        if os.environ.get("BENCH_SERVICE_GATEWAY", "").strip() in (
            "1", "true", "on", "yes"
        ):
            # ISSUE 11: admit over the real loopback HTTP front door so
            # the measured proofs/sec includes the network admission
            # plane (auth, quota check, DRR queue) — two equal-weight
            # tenants split the request stream
            import urllib.request

            from boojum_tpu.service import (
                Gateway, GatewayConfig, TenantSpec,
            )

            gw = Gateway(
                svc,
                GatewayConfig(tenants=[
                    TenantSpec(id="bench-a", token="bench-a"),
                    TenantSpec(id="bench-b", token="bench-b"),
                ]),
                resolver=lambda spec: (asm, setup, config),
            )
            port = gw.start()
            _log(f"service drain: gateway admission on :{port}")
            drain_t0 = time.perf_counter()
            jobs = []
            for i in range(nreq):
                r = urllib.request.Request(
                    gw.url("/prove"),
                    data=json.dumps({
                        "priority": (
                            "interactive" if i == nreq - 1 else "batch"
                        ),
                    }).encode(),
                    headers={
                        "Authorization":
                            f"Bearer bench-{'ab'[i % 2]}",
                        "Content-Type": "application/json",
                    },
                    method="POST",
                )
                with urllib.request.urlopen(r, timeout=30) as resp:
                    jobs.append(json.loads(resp.read())["job"])
            # worker drains in the gateway's background thread
            requests = gw.wait_jobs(jobs, timeout_s=3600)
            drain_wall = time.perf_counter() - drain_t0
            gw.stop()
            summary = svc.summary(wall_s=drain_wall)
            summary["gateway_admitted"] = len(jobs)
        else:
            requests = [
                svc.submit(
                    asm, setup, config,
                    priority="interactive" if i == nreq - 1 else "batch",
                )
                for i in range(nreq)
            ]
            summary = svc.run_worker()
        assert summary["failed"] == 0, summary
        for r in requests:
            r.result(timeout=1.0)
        pps = summary.get("proofs_per_sec") or 0.0
        _log(f"service drain done: {json.dumps(summary)}")
        with _EMIT_LOCK:
            if not _STATE["done"]:
                base = (_STATE["metric"] or "prove_wall").replace(
                    "_prove_wall", ""
                )
                _STATE["metric"] = f"{base}_service_proofs_per_sec"
                _STATE["unit"] = "proofs/s"
                _STATE["reps"] = [pps]
                _STATE["service"] = summary
        stop_collecting_stages()
        if not os.environ.get("BENCH_SKIP_NTT"):
            _set_phase("ntt_metric")
            _measure_ntt()
        _emit("ok")
        return

    _set_phase("timed_reps")
    rep_stages = []
    for i in range(reps):
        sink = collect_stages()
        _LIVE_SINK["sink"] = sink
        t0 = time.perf_counter()
        with _phase_deadline(f"rep{i + 1}", "BENCH_REP_DEADLINE_S", 60.0):
            proof = _prove_recorded(
                f"rep{i + 1}", lambda: prove(asm, setup, config)
            )
        rep_wall = time.perf_counter() - t0
        rep_stages.append({name: round(dt, 3) for name, dt in sink})
        # update reps + the matching median split atomically wrt the
        # watchdog's _emit (same lock), so the reported stage split always
        # belongs to the rep whose wall is the reported median
        with _EMIT_LOCK:
            _STATE["reps"].append(rep_wall)
            order = sorted(range(len(_STATE["reps"])),
                           key=lambda j: _STATE["reps"][j])
            _STATE["stages"] = rep_stages[order[len(order) // 2]]
        _log(f"rep {i + 1}/{reps}: {rep_wall:.3f}s")
    stop_collecting_stages()

    if not os.environ.get("BENCH_SKIP_NTT"):
        _set_phase("ntt_metric")
        _measure_ntt()
    _emit("ok")


if __name__ == "__main__":
    main()
