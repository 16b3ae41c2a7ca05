"""Kernel cost model + roofline attribution + perf-trend gate (ISSUE 12).

The tentpole built the cost-attribution plane: an analytic per-kernel /
per-stage cost sheet (utils/costmodel.py) cross-checked against XLA's
own compile-time `cost_analysis()` actuals (captured into CompileLedger
entries by prover/precompile.py), joined with measured span walls into a
validated `cost` record on every ProveReport line, rendered by
`prove_report.py --roofline`, and a `--trend --gate` perf-regression
gate over report artifacts + the repo's BENCH_*.json history. These
tests pin:

- the analytic sheet covers every kernel `enumerate_kernels` emits (u64
  AND limb-resident variants) with no fallback-family holes;
- a 2^10 CPU prove emits a `cost` record that passes `--check`,
  renders under `--roofline`, exports `cost.*` gauges, and whose
  analytic model agrees with the XLA actuals within the documented
  tolerance band (BASELINE.md "Cost model & trend protocol": family
  aggregates within 4x, totals within 2.5x);
- the `--check` gate REJECTS fabricated records: negative efficiency,
  efficiency over a zero denominator (no wall / zero peak), and
  actuals attributed to kernels the compile ledger never recorded;
- `--diff` reports per-stage efficiency deltas;
- `--trend` ingests the real BENCH_*.json history plus synthetic
  report artifacts and `--gate` exits nonzero exactly on the regressed
  stage (the CI smoke), with machine-identity grouping and
  higher-is-better gating for throughput metrics;
- every registry counter family in use renders under `boojum_tpu_*` on
  /metrics, including the prove-side families the sampler registry
  never carried before the merge.
"""

import copy
import functools
import json
import os
import subprocess
import sys

from boojum_tpu.utils import report
from boojum_tpu.utils import costmodel as cm
from proving import fma_assembly, small_config, small_parts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGES = cm.STAGE_NAMES


def _fma_cfg_asm():
    return fma_assembly(), small_config()


@functools.lru_cache(maxsize=1)
def _proved_with_costs():
    """ONE precompile sweep (capturing per-kernel XLA actuals into a
    process-wide ledger) + ONE recorded 2^10 prove — the shared e2e
    artifact most tests here read, on the shared circuit and config.
    It is a prove of its own and not the shared baseline: the precompile
    ledger has to be live around it."""
    from boojum_tpu.prover import prove
    from boojum_tpu.prover.precompile import enumerate_kernels, precompile
    from boojum_tpu.utils.profiling import (
        start_compile_ledger,
        stop_compile_ledger,
    )

    asm, setup, config = small_parts()
    led = start_compile_ledger()
    specs = enumerate_kernels(asm, config)
    precompile(asm, config, ledger=led, max_workers=2, specs=specs)
    try:
        with report.flight_recording(label="cost_e2e") as rec:
            proof = prove(asm, setup, config)
        line = report.build_report(rec)
    finally:
        stop_compile_ledger()
    assert proof is not None
    return asm, config, [s.name for s in specs], led, line


# ---------------------------------------------------------------------------
# Analytic sheet coverage
# ---------------------------------------------------------------------------


def test_cost_sheet_covers_u64_enumeration():
    from boojum_tpu.prover.precompile import enumerate_kernels

    asm, cfg = _fma_cfg_asm()
    specs = enumerate_kernels(asm, cfg)
    sheet = cm.cost_sheet(specs)
    assert set(sheet) == {s.name for s in specs}
    for name, ent in sheet.items():
        assert ent["flops"] >= 0, name
        assert ent["hbm_bytes"] > 0, name
        assert ent["ici_bytes"] == 0, name  # meshless: no ICI
        assert ent["family"] not in ("fallback", "error"), (
            f"{name} fell out of every modeled family"
        )


def test_cost_sheet_covers_limb_resident_enumeration(monkeypatch):
    from boojum_tpu.prover.precompile import enumerate_kernels

    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "1")
    asm, cfg = _fma_cfg_asm()
    specs = enumerate_kernels(asm, cfg)
    names = {s.name for s in specs}
    assert "coset_sweep_terms_limbres" in names
    sheet = cm.cost_sheet(specs)
    assert set(sheet) == names
    for name, ent in sheet.items():
        assert ent["hbm_bytes"] > 0, name
        assert ent["family"] not in ("fallback", "error"), name
    # plane pairs carry the same field-element payload: the resident
    # sweep must not price bytes wildly differently from the u64 one
    monkeypatch.setenv("BOOJUM_TPU_LIMB_RESIDENT", "0")
    sheet_u64 = cm.cost_sheet(enumerate_kernels(asm, cfg))
    a = sheet["coset_sweep_terms_limbres"]["hbm_bytes"]
    b = sheet_u64["coset_sweep_terms"]["hbm_bytes"]
    assert 0.2 <= a / b <= 5.0


def test_plane_pair_args_price_like_u64():
    """A (lo, hi) u32 plane pair is ONE logical argument: E-keyed
    kernels (binv, stage2, deep, fri) must price the resident variant
    identically to the u64 one — not at half, which _flatten_args-based
    sizing once produced by measuring a single plane."""
    import jax
    import jax.numpy as jnp

    n = 1 << 11
    u64 = jax.ShapeDtypeStruct((4, n), jnp.uint64)
    u32 = jax.ShapeDtypeStruct((4, n), jnp.uint32)
    pair = (u32, u32)
    base = cm.kernel_cost("ext_binv", [u64])
    res = cm.kernel_cost("ext_binv_limbres", [pair])
    assert res["flops"] == base["flops"]
    assert res["hbm_bytes"] == base["hbm_bytes"]
    # a bare u32 array is still its own (half-size) payload
    assert cm.kernel_cost("ext_binv", [u32])["flops"] == base["flops"] / 2
    # a general list of arrays is NOT a pair: largest single array wins
    assert cm.kernel_cost("ext_binv", [[u64, u64, u64]])["flops"] == (
        base["flops"]
    )


def test_stage_costs_positive_and_scale_with_trace():
    from boojum_tpu.prover.shape_key import shape_bucket

    asm, cfg = _fma_cfg_asm()
    sb = shape_bucket(asm, cfg)
    stages = cm.stage_costs(sb, cfg)
    assert set(stages) == set(STAGES)
    for name, ent in stages.items():
        assert ent["flops"] > 0, name
        assert ent["hbm_bytes"] > 0, name
        assert ent["ici_bytes"] == 0, name
    # a mesh adds ICI traffic to the commit stages
    stages_mesh = cm.stage_costs(sb, cfg, mesh_devices=8)
    assert stages_mesh["round1_witness_commit"]["ici_bytes"] > 0
    assert (
        stages_mesh["round1_witness_commit"]["flops"]
        == stages["round1_witness_commit"]["flops"]
    )


# ---------------------------------------------------------------------------
# Record assembly (synthetic — no jax work)
# ---------------------------------------------------------------------------


def _synthetic_tree(walls: dict) -> list:
    children = [
        {"name": nm, "start_s": float(i), "wall_s": w, "children": []}
        for i, (nm, w) in enumerate(walls.items())
    ]
    return [{
        "name": "prove", "start_s": 0.0,
        "wall_s": sum(walls.values()), "children": children,
    }]


def test_build_cost_record_from_synthetic_spans():
    from boojum_tpu.prover.shape_key import shape_bucket

    asm, cfg = _fma_cfg_asm()
    sb = shape_bucket(asm, cfg)
    walls = {nm: 0.5 for nm in STAGES}
    peaks = {
        "kind": "test", "peak_gflops": 100.0, "peak_hbm_gbps": 50.0,
        "peak_ici_gbps": 0.0, "source": "env",
    }
    rec = cm.build_cost_record(
        sb, cfg, _synthetic_tree(walls), {}, peaks=peaks
    )
    assert set(rec["stages"]) == set(STAGES)
    for nm, ent in rec["stages"].items():
        assert ent["wall_s"] == 0.5
        assert ent["achieved_gflops"] > 0, nm
        assert ent["regime"] in ("compute", "memory"), nm
        assert 0 <= ent["efficiency"], nm
    total = rec["total"]
    assert total["wall_s"] == round(0.5 * len(STAGES), 6)
    assert total["achieved_gflops"] > 0
    # a stage whose wall never landed gets NO achieved/efficiency
    # (the zero-denominator rule the validator enforces)
    rec2 = cm.build_cost_record(
        sb, cfg, _synthetic_tree({"round3_quotient": 0.5}), {},
        peaks=peaks,
    )
    r1 = rec2["stages"]["round1_witness_commit"]
    assert r1["wall_s"] is None
    assert "achieved_gflops" not in r1
    assert "efficiency" not in r1


def test_roofline_zero_wall_claims_nothing():
    peaks = {"peak_gflops": 10.0, "peak_hbm_gbps": 10.0}
    out = cm.roofline({"flops": 100.0, "hbm_bytes": 10.0}, 0.0, peaks)
    assert "achieved_gflops" not in out
    assert "efficiency" not in out
    out = cm.roofline({"flops": 100.0, "hbm_bytes": 10.0}, 2.0, peaks)
    assert out["achieved_gflops"] > 0
    assert out["efficiency"] > 0


def test_roofline_submicrosecond_wall_rounds_to_consistent_record():
    """A positive wall below the 6-decimal rounding floor must not
    produce wall_s=0.0 alongside achieved fields — the validator
    rightly rejects efficiency claimed over a zero wall, so the
    producer must gate on the SAME rounded value it records."""
    peaks = {"peak_gflops": 10.0, "peak_hbm_gbps": 10.0}
    out = cm.roofline({"flops": 1000.0, "hbm_bytes": 10.0}, 2e-7, peaks)
    assert out["wall_s"] == 0.0
    assert "achieved_gflops" not in out
    assert "efficiency" not in out


def test_stage_walls_takes_last_prove_span():
    """A long-lived recorder (bench/CLI bare-SpanRecorder path) can
    hold several prove roots — the cost record must join the walls of
    the prove that just FINISHED, not the first one."""
    tree = (
        _synthetic_tree({"round3_quotient": 1.0})
        + _synthetic_tree({"round3_quotient": 7.0})
    )
    walls = report.stage_walls(tree, names=report.PROVE_STAGES)
    assert walls == {"round3_quotient": 7.0}


def test_span_coverage_shares_stage_walls_root():
    """One report line's coverage= and stage numbers must describe the
    SAME prove: span_coverage reuses stage_walls' root selection (last
    prove span, found anywhere in the tree)."""
    # multi-prove recorder: first prove 50% covered, last 100%
    first = _synthetic_tree({"round3_quotient": 1.0})
    first[0]["wall_s"] = 2.0
    last = _synthetic_tree({"round3_quotient": 4.0})
    cov = report.span_coverage({"spans": first + last})
    assert cov == 1.0
    # service line: prove nested under the service_request root
    nested = [{
        "name": "service_request", "start_s": 0.0, "wall_s": 100.0,
        "children": _synthetic_tree({"round3_quotient": 3.0}),
    }]
    assert report.span_coverage({"spans": nested}) == 1.0


def test_kernel_costs_filter_by_shape_key():
    """The compile ledger is process-global and kernel names are not
    shape-qualified — a multi-bucket process must get ITS bucket's XLA
    actuals, never another bucket's (a 2^12 sweep's flops attributed to
    a 2^10 prove would skew its cost record ~4x)."""
    from boojum_tpu.utils.profiling import CompileLedger

    led = CompileLedger()
    led.record("coset_sweep_terms", 0.1, 1.0, shape_key="bucket_a",
               xla_cost={"flops": 100.0})
    led.record("coset_sweep_terms", 0.1, 1.0, shape_key="bucket_b",
               xla_cost={"flops": 400.0})
    assert led.kernel_costs(shape_key="bucket_a") == {
        "coset_sweep_terms": {"flops": 100.0}
    }
    assert led.kernel_costs(shape_key="bucket_b") == {
        "coset_sweep_terms": {"flops": 400.0}
    }
    # unfiltered keeps the legacy last-wins union
    assert led.kernel_costs() == {
        "coset_sweep_terms": {"flops": 400.0}
    }


def test_platform_info_memoized_and_copy_safe():
    """platform_info rides every report/bench line — it must probe the
    stack once per process and hand out copies a caller can't poison."""
    from boojum_tpu.prover.aot import platform_info

    a = platform_info()
    b = platform_info()
    assert a == b and a is not b
    a["jax"] = "poisoned"
    assert platform_info()["jax"] != "poisoned"


# ---------------------------------------------------------------------------
# E2E: the 2^10 CPU prove's cost record (acceptance)
# ---------------------------------------------------------------------------


def test_e2e_prove_emits_valid_cost_record():
    _asm, _cfg, spec_names, _led, line = _proved_with_costs()
    cost = line.get("cost")
    assert isinstance(cost, dict), "prove emitted no cost record"
    assert line["schema"] == report.REPORT_SCHEMA
    problems = report.validate_report(line)
    assert problems == [], problems
    # every prover stage measured and attributed
    for nm in STAGES:
        ent = cost["stages"][nm]
        assert ent["wall_s"] > 0, nm
        assert ent["achieved_gflops"] >= 0, nm
        assert ent["regime"] in ("compute", "memory"), nm
    assert cost["total"]["achieved_gflops"] > 0
    # the sheet covers exactly the dispatched enumeration
    assert cost["kernels"] == sorted(spec_names)
    # ledger actuals attributed, and only to recorded kernels
    ledger = line["compile_ledger"]
    assert ledger["cost_kernels"] > 0
    assert set(cost["attributed_kernels"]) <= set(ledger["kernel_names"])
    # cost.* gauges rode the line's metrics (and therefore /metrics)
    gauges = line["metrics"]["gauges"]
    assert gauges.get("cost.total.achieved_gflops", 0) > 0
    assert any(k.startswith("cost.round3_quotient.") for k in gauges)


def test_roofline_cli_and_check_cli(tmp_path):
    _asm, _cfg, _names, _led, line = _proved_with_costs()
    path = tmp_path / "cost.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(line) + "\n")
    script = os.path.join(REPO, "scripts", "prove_report.py")
    chk = subprocess.run(
        [sys.executable, script, "--check", str(path)],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, chk.stdout + chk.stderr
    roof = subprocess.run(
        [sys.executable, script, "--roofline", str(path)],
        capture_output=True, text=True, timeout=120,
    )
    assert roof.returncode == 0, roof.stdout + roof.stderr
    assert "round3_quotient" in roof.stdout
    assert "GFLOP/s" in roof.stdout
    assert "TOTAL" in roof.stdout


# ---------------------------------------------------------------------------
# --check gate: fabricated cost records FAIL
# ---------------------------------------------------------------------------


def test_check_rejects_negative_efficiency():
    *_, line = _proved_with_costs()
    bad = copy.deepcopy(line)
    bad["cost"]["stages"]["round3_quotient"]["efficiency"] = -0.5
    probs = report.validate_report(bad)
    assert any("efficiency invalid" in p for p in probs), probs


def test_check_rejects_zero_denominator_efficiency():
    *_, line = _proved_with_costs()
    # claimed over a zero wall
    bad = copy.deepcopy(line)
    bad["cost"]["stages"]["round3_quotient"]["wall_s"] = 0
    probs = report.validate_report(bad)
    assert any("zero/absent wall" in p for p in probs), probs
    # claimed over a zero peak
    bad = copy.deepcopy(line)
    bad["cost"]["device"]["peak_gflops"] = 0
    probs = report.validate_report(bad)
    assert any("zero/absent" in p and "peak" in p for p in probs), probs


def test_check_rejects_kernels_absent_from_ledger():
    *_, line = _proved_with_costs()
    bad = copy.deepcopy(line)
    bad["cost"]["attributed_kernels"] = list(
        bad["cost"].get("attributed_kernels") or []
    ) + ["bogus_kernel_nobody_compiled"]
    probs = report.validate_report(bad)
    assert any("absent from the compile ledger" in p for p in probs), probs
    # and the pristine line still passes
    assert report.validate_report(line) == []


def test_diff_reports_cost_efficiency_deltas():
    *_, line = _proved_with_costs()
    other = copy.deepcopy(line)
    st = other["cost"]["stages"]["round3_quotient"]
    if isinstance(st.get("efficiency"), (int, float)):
        st["efficiency"] = st["efficiency"] / 2
    diff = report.diff_reports(line, other)
    assert "round3_quotient" in diff["cost_deltas"]
    ent = diff["cost_deltas"]["round3_quotient"]
    assert ent["efficiency_delta"] is not None
    assert "cost (roofline) deltas" in report.render_diff(diff)


def test_slo_summary_carries_roofline():
    *_, line = _proved_with_costs()
    summary = report.slo_summary([line, line])
    roof = summary["roofline"]
    assert roof["lines"] == 2
    assert "round3_quotient" in roof["stages"]
    assert roof["stages"]["round3_quotient"]["mean_efficiency"] >= 0
    assert "roofline" in report.render_slo(summary)


# ---------------------------------------------------------------------------
# Trend + gate
# ---------------------------------------------------------------------------


def _report_artifact(path, total, stage_walls, label):
    line = {
        "kind": report.REPORT_KIND,
        "schema": report.REPORT_SCHEMA,
        "label": label,
        "unix_ts": 0,
        "wall_s": total,
        "spans": _synthetic_tree(stage_walls),
        "metrics": {"counters": {}, "gauges": {}, "boundaries": []},
        "checkpoints": [],
    }
    with open(path, "w") as f:
        f.write(json.dumps(line) + "\n")
    return path


def _bench_history_paths(tmp_path):
    """The five-point history the old chip rounds left (the record files
    went in PR 22; their figures live on in ROADMAP.md): a bare baseline
    line, two good driver wrappers, one rc=124 wrapper with no line, and
    one watchdog line that never reached a prove."""
    def line(value, **kw):
        return dict(
            {"metric": "sha256_8192B_prove_wall", "value": value,
             "unit": "s"}, **kw
        )

    def wrapper(n, rc, parsed):
        tail = "trace_len=65536\n"
        if parsed is not None:
            tail += json.dumps(parsed) + "\n"
        return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": tail,
                "parsed": parsed}

    docs = {
        "BENCH_BASELINE.json": line(35.6178),
        "BENCH_r01.json": wrapper(1, 0, line(21.6716)),
        "BENCH_r02.json": wrapper(2, 0, line(19.7891)),
        "BENCH_r03.json": wrapper(3, 124, None),
        "BENCH_r04.json": wrapper(4, 0, line(
            1500.0, status="timeout+no_prove", phase="warmup_prove",
            reps=[],
        )),
    }
    paths = []
    for name, doc in docs.items():
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    return paths


def test_trend_gate_fires_exactly_on_regressed_stage(tmp_path):
    """Acceptance: BENCH_*.json history + synthetic report artifacts —
    the gate exits nonzero exactly on the regressed stage: round3 blew
    up 3x, every other series (including the totals fed by the
    BENCH history and round5) stays quiet."""
    prev = _report_artifact(
        tmp_path / "prev.jsonl", 20.0,
        {"round3_quotient": 1.0, "round5_deep_fri": 2.0}, "prev",
    )
    last = _report_artifact(
        tmp_path / "last.jsonl", 20.3,
        {"round3_quotient": 3.0, "round5_deep_fri": 2.05}, "last",
    )
    points, notes = report.load_trend_points(
        _bench_history_paths(tmp_path) + [str(prev), str(last)]
    )
    # r03 (rc=124, parsed null) and r04 (timeout+no_prove) are skipped
    assert sum("BENCH_r03" in n for n in notes) == 1, notes
    assert sum("BENCH_r04" in n for n in notes) == 1, notes
    assert len(points) == 5  # BASELINE, r01, r02, prev, last
    series = report.trend_series(points)
    regressions = report.trend_gate(series)
    assert len(regressions) == 1, regressions
    assert regressions[0]["series"] == "stage:round3_quotient"
    assert regressions[0]["ratio"] == 3.0
    rendered = report.render_trend(series, regressions)
    assert "REGRESSED" in rendered
    assert "stage:round3_quotient" in rendered
    # without the regressed artifact, the gate stays green
    assert report.trend_gate(
        report.trend_series(points[:-1])
    ) == []


def test_trend_skips_trailing_reject_lines(tmp_path):
    """A gateway 429/shed reject line (wall_s=0.0, no spans) trailing
    an artifact must not become its trend point — the last line holding
    a real prove span does; an artifact of ONLY reject lines is
    skipped entirely."""
    reject = {
        "kind": report.REPORT_KIND, "schema": report.REPORT_SCHEMA,
        "label": "gateway:throttled", "unix_ts": 0, "wall_s": 0.0,
        "spans": [],
        "metrics": {
            "counters": {"service.gateway.throttled": 1}, "gauges": {},
        },
        "checkpoints": [],
    }
    p = tmp_path / "mixed.jsonl"
    _report_artifact(p, 10.0, {"round3_quotient": 1.0}, "rep")
    with open(p, "a") as f:
        f.write(json.dumps(reject) + "\n")
    points, _ = report.load_trend_points([str(p)])
    assert len(points) == 1
    assert points[0]["values"]["total_wall"]["value"] == 10.0
    only = tmp_path / "only_rejects.jsonl"
    with open(only, "w") as f:
        f.write(json.dumps(reject) + "\n")
    points, notes = report.load_trend_points([str(only)])
    assert points == []
    assert any("only_rejects" in n for n in notes)


def test_attach_subtracts_measured_traffic_baseline():
    """On a long-lived registry (bench multi-rep) the ici./transfer.
    families are cumulative — the prove-start baseline makes the cost
    record carry per-PROVE bytes, not the running total."""
    from boojum_tpu.utils import metrics as _metrics

    reg = _metrics.MetricsRegistry()
    reg.gauge_add("ici.all_to_all_bytes", 1000.0)
    reg.count("transfer.h2d_bytes", 600)
    tok = _metrics.install_scoped_registry(reg)
    try:
        base = cm.measured_baseline()
    finally:
        _metrics.reset_scoped_registry(tok)
    assert base["gauges"]["ici.all_to_all_bytes"] == 1000.0
    assert base["counters"]["transfer.h2d_bytes"] == 600.0
    # this prove adds 250 ICI + 100 h2d on top of the running totals
    reg.gauge_add("ici.all_to_all_bytes", 250.0)
    reg.count("transfer.h2d_bytes", 100)
    snap = cm._subtract_baseline(reg.to_dict(), base)
    assert snap["gauges"]["ici.all_to_all_bytes"] == 250.0
    assert snap["counters"]["transfer.h2d_bytes"] == 100.0
    # a registry swapped mid-prove (fresh, below baseline) clamps at 0
    fresh = _metrics.MetricsRegistry()
    fresh.gauge_add("ici.all_to_all_bytes", 10.0)
    snap = cm._subtract_baseline(fresh.to_dict(), base)
    assert snap["gauges"]["ici.all_to_all_bytes"] == 0.0


def test_trend_total_series_spans_bench_and_reports(tmp_path):
    prev = _report_artifact(
        tmp_path / "prev.jsonl", 20.0, {"round3_quotient": 1.0}, "prev"
    )
    points, _ = report.load_trend_points(
        _bench_history_paths(tmp_path) + [str(prev)]
    )
    series = report.trend_series(points)
    totals = series[("", "total_wall")]["points"]
    assert [round(v, 2) for _l, v in totals] == [35.62, 21.67, 19.79, 20.0]


def test_trend_skips_warm_only_bench_lines(tmp_path):
    """A watchdog line whose status carries +warm_only measured the
    compile-laden warm-up wall, not steady state — it must feed no
    trend series (same rule as +no_prove)."""
    p = tmp_path / "warm.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({
            "metric": "fma_2p10_prove_wall", "value": 280.0, "unit": "s",
            "status": "timeout+warm_only",
        }) + "\n")
        f.write(json.dumps({
            "metric": "fma_2p10_prove_wall", "value": 11.0, "unit": "s",
            "status": "ok",
        }) + "\n")
    points, _ = report.load_trend_points([str(p)])
    vals = [
        pt["values"]["total_wall"]["value"]
        for pt in points if "total_wall" in pt["values"]
    ]
    assert vals == [11.0]


def test_report_line_host_identity_feeds_trend_grouping():
    """ProveReport lines must carry the SAME five-field identity block
    bench/bench_micro stamp — an empty _trend_identity would collapse
    report artifacts from two machines into one gated series."""
    *_, line = _proved_with_costs()
    h = line.get("host") or {}
    for k in ("host_fp", "device_kind", "backend", "jax", "jaxlib"):
        assert h.get(k), f"host block missing {k}"
    assert report._trend_identity(line) != ""


def test_prime_sheet_skips_reenumeration(monkeypatch):
    """precompile primes the assembly's sheet cache from its own
    enumeration — the first recorded prove's cost seam must then hit
    the cache, never re-walking enumerate_kernels inside its span."""
    import importlib

    # the package re-exports the precompile FUNCTION under the same
    # name as the submodule — resolve the module itself
    pc = importlib.import_module("boojum_tpu.prover.precompile")

    asm, cfg = _fma_cfg_asm()
    specs = pc.enumerate_kernels(asm, cfg)
    cm.prime_sheet(asm, cfg, specs)

    def _boom(*a, **k):
        raise AssertionError("cost seam re-enumerated the kernel library")

    monkeypatch.setattr(pc, "enumerate_kernels", _boom)
    sheet = cm._cached_sheet(asm, cfg)
    assert set(sheet) == {s.name for s in specs}


def test_trend_legacy_history_adopts_sole_real_identity(tmp_path):
    """Pre-identity BENCH history (identity "") must keep gating new
    identity-stamped runs: with exactly one real identity in play the
    legacy points join its series; with two they stay split."""
    ident_a = {"host_fp": "aaaa", "device_kind": "cpu", "backend": "cpu",
               "jax": "0.4.37", "jaxlib": "0.4.36"}
    legacy = tmp_path / "legacy.jsonl"
    with open(legacy, "w") as f:
        for v in (10.0, 10.2):
            f.write(json.dumps({
                "metric": "fma_2p10_prove_wall", "value": v, "unit": "s",
            }) + "\n")
    new = tmp_path / "new.jsonl"
    with open(new, "w") as f:
        f.write(json.dumps({
            "metric": "fma_2p10_prove_wall", "value": 30.0, "unit": "s",
            "host": ident_a,
        }) + "\n")
    points, _ = report.load_trend_points([str(legacy), str(new)])
    series = report.trend_series(points)
    assert len(series) == 1  # merged under ident_a
    regs = report.trend_gate(series)
    assert len(regs) == 1 and regs[0]["last"] == 30.0
    # a SECOND real identity makes legacy attribution ambiguous: split
    ident_b = dict(ident_a, host_fp="bbbb")
    other = tmp_path / "other.jsonl"
    with open(other, "w") as f:
        f.write(json.dumps({
            "metric": "fma_2p10_prove_wall", "value": 9.0, "unit": "s",
            "host": ident_b,
        }) + "\n")
    points, _ = report.load_trend_points(
        [str(legacy), str(new), str(other)]
    )
    series = report.trend_series(points)
    assert len(series) == 3  # legacy "", ident_a, ident_b — no adoption
    assert report.trend_gate(series) == []


def test_deep_codeword_ici_matches_stage_convention():
    """The per-kernel deep_codeword ICI and the round5 stage total both
    price the SAME col->row plane re-layout: global payload with
    (D-1)/D crossing chips — they may never disagree by a factor of D."""
    import numpy as np

    class _Sds:
        def __init__(self, *shape):
            self.shape = shape
            self.dtype = np.dtype(np.uint32)

    N, D = 2048.0, 8
    ent = cm.kernel_cost(
        "deep_codeword_sm", [_Sds(16, int(N))], mesh_devices=D
    )
    assert ent["family"] == "deep"
    assert ent["ici_bytes"] == N * 8 * 2 * (D - 1) / D


def test_trend_identity_separates_backend_and_jaxlib():
    """The documented grouping contract is host_fp / device_kind /
    backend / jax / jaxlib — two jaxlib builds (or backends) on the
    same machine must never share a gated series."""
    base = {"host_fp": "aaaa", "device_kind": "cpu", "jax": "0.4.37"}
    a = report._trend_identity(
        {"host": {**base, "backend": "cpu", "jaxlib": "0.4.37"}}
    )
    b = report._trend_identity(
        {"host": {**base, "backend": "cpu", "jaxlib": "0.4.38"}}
    )
    c = report._trend_identity(
        {"host": {**base, "backend": "tpu", "jaxlib": "0.4.37"}}
    )
    assert len({a, b, c}) == 3


def test_trend_gates_throughput_drop_and_groups_identity(tmp_path):
    a = tmp_path / "micro_a.jsonl"
    b = tmp_path / "micro_b.jsonl"
    ident = {"host_fp": "aaaa", "device_kind": "cpu", "jax": "0.4.37"}
    other = {"host_fp": "bbbb", "device_kind": "tpu", "jax": "0.4.37"}
    with open(a, "w") as f:
        f.write(json.dumps({
            "metric": "ntt_pair_elems_per_s", "value": 1000,
            "unit": "elems/s", "host": ident,
        }) + "\n")
    with open(b, "w") as f:
        f.write(json.dumps({
            "metric": "ntt_pair_elems_per_s", "value": 400,
            "unit": "elems/s", "host": ident,
        }) + "\n")
    points, _ = report.load_trend_points([str(a), str(b)])
    regs = report.trend_gate(report.trend_series(points))
    assert len(regs) == 1 and regs[0]["direction"] == "higher"
    # a different machine's line starts its own series: no gate fires
    # across identities even with a "worse" number
    with open(b, "w") as f:
        f.write(json.dumps({
            "metric": "ntt_pair_elems_per_s", "value": 400,
            "unit": "elems/s", "host": other,
        }) + "\n")
    points, _ = report.load_trend_points([str(a), str(b)])
    assert report.trend_gate(report.trend_series(points)) == []


def test_stage_walls_finds_prove_nested_under_service_root():
    """Service-mode lines nest `prove` under the `service_request` root
    span: the shared extraction must find it anywhere in the tree, or
    every packed-service cost record silently loses its stage walls."""
    nested = [{
        "name": "service_request", "start_s": 0.0, "wall_s": 3.0,
        "children": _synthetic_tree({"round3_quotient": 1.5}),
    }]
    walls = report.stage_walls(nested, names=report.PROVE_STAGES)
    assert walls == {"round3_quotient": 1.5}
    # and costmodel's view is the same extraction
    assert cm.STAGE_NAMES == report.PROVE_STAGES


def test_trend_stage_series_exclude_cache_state_spans(tmp_path):
    """aot_load/aot_warm land under `prove` but are artifact-store
    temperature, not prover stages — gating them would fail CI on a
    cold cache. Only PROVE_STAGES become stage:<name> series."""
    walls = {"round3_quotient": 1.0, "aot_warm": 30.0}
    p = _report_artifact(tmp_path / "a.jsonl", 31.0, walls, "a")
    points, _ = report.load_trend_points([str(p)])
    series = report.trend_series(points)
    names = {name for _i, name in series}
    assert "stage:round3_quotient" in names
    assert "stage:aot_warm" not in names


def test_trend_total_wall_excludes_cache_state_spans(tmp_path):
    """A cold-cache artifact's wall is dominated by aot_load/aot_warm
    (compile/deserialize). The total_wall trend point subtracts those
    spans so the gate fires on prover performance, never on
    artifact-store temperature — cold head vs warm history stays
    green, and a cold baseline can't mask a warm-head regression."""
    warm = _report_artifact(
        tmp_path / "warm.jsonl", 10.0, {"round3_quotient": 9.0}, "warm"
    )
    cold = _report_artifact(
        tmp_path / "cold.jsonl", 41.0,
        {"aot_load": 1.0, "aot_warm": 30.0, "round3_quotient": 9.5},
        "cold",
    )
    points, _ = report.load_trend_points([str(warm), str(cold)])
    totals = {
        p["label"]: p["values"]["total_wall"]["value"] for p in points
    }
    assert totals["warm.jsonl"] == 10.0
    assert totals["cold.jsonl"] == 10.0  # 41.0 minus the 31s of cache
    assert report.trend_gate(report.trend_series(points)) == []


def test_trend_duplicate_labels_and_column_order(tmp_path):
    (tmp_path / "runA").mkdir()
    (tmp_path / "runB").mkdir()
    a = _report_artifact(
        tmp_path / "runA" / "report.jsonl", 10.0,
        {"round3_quotient": 1.0}, "x",
    )
    b = _report_artifact(
        tmp_path / "runB" / "report.jsonl", 12.0,
        {"round3_quotient": 1.1}, "x",
    )
    points, _ = report.load_trend_points([str(a), str(b)])
    labels = [p["label"] for p in points]
    assert labels == ["runA/report.jsonl", "runB/report.jsonl"]
    series = report.trend_series(points)
    rendered = report.render_trend(series, [], labels=labels)
    # both columns present, in artifact order
    assert rendered.index("runA/report.jsonl") < rendered.index(
        "runB/report.jsonl"
    )
    assert "10 " in rendered or "10\n" in rendered or "10 |" in rendered


def test_trend_gate_cli_smoke(tmp_path):
    """CI satellite: the fast CPU smoke — `--trend --gate` over two
    synthetic report artifacts exits 1 on the regression, 0 without."""
    prev = _report_artifact(
        tmp_path / "prev.jsonl", 10.0, {"round3_quotient": 1.0}, "prev"
    )
    last = _report_artifact(
        tmp_path / "last.jsonl", 10.1, {"round3_quotient": 2.4}, "last"
    )
    ok = _report_artifact(
        tmp_path / "ok.jsonl", 10.0, {"round3_quotient": 1.02}, "ok"
    )
    script = os.path.join(REPO, "scripts", "prove_report.py")
    bad = subprocess.run(
        [sys.executable, script, "--trend", str(prev), str(last),
         "--gate"],
        capture_output=True, text=True, timeout=120,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "GATE" in bad.stdout and "round3_quotient" in bad.stdout
    good = subprocess.run(
        [sys.executable, script, "--trend", str(prev), str(ok), "--gate"],
        capture_output=True, text=True, timeout=120,
    )
    assert good.returncode == 0, good.stdout + good.stderr
    assert "GATE: ok" in good.stdout


# ---------------------------------------------------------------------------
# /metrics Prometheus audit (satellite)
# ---------------------------------------------------------------------------


def test_prometheus_renders_every_family():
    from boojum_tpu.service.http_metrics import prometheus_text
    from boojum_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    fams = (
        "ici", "limb", "aot", "quotient", "fri", "transfer", "service",
        "cost",
    )
    for fam in fams:
        reg.count(f"{fam}.things", 3)
        reg.gauge_set(f"{fam}.level", 1.5)
    text = prometheus_text(reg.to_dict())
    for fam in fams:
        assert f"boojum_tpu_{fam}_things 3" in text, (fam, text)
        assert f"boojum_tpu_{fam}_level 1.5" in text, (fam, text)


def test_metrics_plane_merges_prove_registry():
    from boojum_tpu.service.http_metrics import MetricsPlane
    from boojum_tpu.utils import metrics as _metrics
    from boojum_tpu.utils.telemetry import TelemetrySampler

    sampler = TelemetrySampler(interval_s=60.0)
    sampler.registry.gauge_set("telemetry.canary", 7.0)
    reg = _metrics.MetricsRegistry()
    reg.count("fri.folds", 3)
    reg.gauge_set("cost.total.efficiency", 0.25)
    plane = MetricsPlane(sampler)
    prev = _metrics.install_registry(reg)
    try:
        text = plane.render_metrics()
    finally:
        _metrics.install_registry(prev)
    assert "boojum_tpu_fri_folds 3" in text
    assert "boojum_tpu_cost_total_efficiency 0.25" in text
    assert "boojum_tpu_telemetry_canary 7.0" in text
    # without the global registry, the sampler view still renders
    text = plane.render_metrics()
    assert "boojum_tpu_telemetry_canary 7.0" in text


def test_post_prove_registry_snapshot_fully_exported():
    """Satellite: pin the exported set against a REAL post-prove
    registry snapshot — every counter/gauge family the 2^10 prove
    recorded renders under boojum_tpu_*."""
    from boojum_tpu.service.http_metrics import _prom_name, prometheus_text

    *_, line = _proved_with_costs()
    metrics = line["metrics"]
    text = prometheus_text(metrics)
    keys = list(metrics["counters"]) + list(metrics["gauges"])
    assert keys, "prove recorded no metrics"
    for k in keys:
        assert f"{_prom_name(k)} " in text, k
    families = {k.split(".")[0] for k in keys}
    assert {"prover", "transfer", "cost"} <= families, families


# ---------------------------------------------------------------------------
# Identity block (satellite)
# ---------------------------------------------------------------------------


def test_bench_micro_lines_carry_identity(capsys):
    sys.path.insert(0, REPO)
    try:
        import bench_micro
    finally:
        sys.path.remove(REPO)
    ident = bench_micro.host_identity()
    for key in ("host_fp", "device_kind", "jax", "jaxlib", "backend"):
        assert key in ident, ident
    bench_micro.emit("canary_metric", 1, "s")
    out = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(out)
    assert line["metric"] == "canary_metric"
    assert line["host"]["host_fp"] == ident["host_fp"]
    # the identity matches what the AOT bundle manifests validate on
    from boojum_tpu.prover.aot import platform_info

    assert ident == platform_info()


def test_cost_telemetry_provider_flattens_last_record():
    _asm, _cfg, _names, _led, line = _proved_with_costs()
    assert cm.last_cost_record() is not None
    flat = cm.telemetry_provider()
    assert flat, "provider returned nothing after a costed prove"
    for k, v in flat.items():
        assert isinstance(v, (int, float)) and v >= 0, (k, v)
    assert any(k.startswith("round") for k in flat)
