"""AOT executable artifact store (ISSUE 8).

The tentpole made compilation a build step: `prover/aot.py` serializes
the compiled executables of the whole dispatch surface (persistent-cache
bundle + jax.export StableHLO artifacts, manifest with integrity
hashes), and a cold process loads them instead of compiling. These tests
pin the acceptance criteria at 2^10 on CPU:

- artifact roundtrip across REAL process boundaries: one subprocess
  builds the bundle, a second FRESH subprocess (empty persistent cache)
  loads it and proves — proof bytes AND digest-checkpoint stream are
  bit-identical to an in-process JIT prove, the CompileLedger records
  ZERO cache misses / dispatch compiles, and every enumerated kernel is
  an `aot_hit`;
- the serve process's ProveReport line passes `validate_report`
  (aot.* gauge schema), and a line whose ledger claims all-aot_hit
  kernels while counting cache misses FAILS it;
- a stale bundle (wrong jaxlib in the manifest) degrades to JIT with a
  logged warning — and raises under BOOJUM_TPU_AOT_REQUIRE;
- a corrupt cache entry is skipped (counted, not fatal);
- jax.export artifacts in the bundle deserialize and name the build
  platform;
- bench.py's size-capped cache prune never evicts entries touched by
  the current run or installed from a loaded bundle.

The build/serve circuit is the same 2^10 fma circuit + smallest-honest
config as the rest of tier-1 (tests/proving.py); the in-process reference
is the shared baseline prove.
"""

import functools
import json
import logging
import os
import subprocess
import sys
import tempfile
import textwrap

import pytest

from boojum_tpu.utils import report
from proving import baseline, small_parts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the shared circuit and config (tests/proving.py), as the first lines of
# both subprocess drivers — synthesis only, no jit dispatch before
# build_bundle redirects the cache
_CIRCUIT_SRC = textwrap.dedent(
    '''
    from proving import fma_assembly, small_config

    asm, config = fma_assembly(), small_config()
    '''
)

_BUILD_SRC = (
    _CIRCUIT_SRC
    + textwrap.dedent(
        '''
    import json, sys

    from boojum_tpu.prover.aot import build_bundle
    from boojum_tpu.utils.profiling import start_compile_ledger

    led = start_compile_ledger()
    manifest = build_bundle(asm, config, OUT_ROOT, ledger=led)
    json.dump(
        {
            "dir": manifest["dir"],
            "bucket": manifest["bucket"],
            "num_kernels": manifest["num_kernels"],
            "num_exports": manifest["num_exports"],
            "kernels": manifest["kernels"],
            "num_cache_entries": len(manifest["cache_entries"]),
        },
        open(OUT_JSON, "w"),
    )
    '''
    )
)

_SERVE_SRC = (
    _CIRCUIT_SRC
    + textwrap.dedent(
        '''
    import json, sys

    from boojum_tpu.prover import generate_setup, prove
    from boojum_tpu.prover import aot as _aot
    from boojum_tpu.utils import report as _report
    from boojum_tpu.utils.profiling import start_compile_ledger

    led = start_compile_ledger()
    # ONE recording over load + warm + setup + prove, so the report
    # line carries the aot.* counters/gauges the validator checks
    with _report.flight_recording(label="aot_serve") as rec:
        stats = _aot.maybe_load_for_prove(asm, config)
        setup = generate_setup(asm, config)
        proof = prove(asm, setup, config)
    line = _report.build_report(rec)
    json.dump(
        {
            "proof": proof.to_json(),
            "checkpoints": [
                (e["seq"], e["round"], e["label"], e["digest"])
                for e in line["checkpoints"]
            ],
            "report_line": line,
            "stats": stats,
            "summary": led.summary(),
            "aot_entries": {
                e["name"]: e["aot_hit"]
                for e in led.entries
                if "aot_hit" in e
            },
        },
        open(OUT_JSON, "w"),
    )
    '''
    )
)


def _run_driver(src: str, tmp: str, name: str, env_extra: dict) -> dict:
    """Write `src` (prefixed with OUT_* constants) as a driver script and
    run it in a FRESH python process; returns the JSON it wrote."""
    out_json = os.path.join(tmp, f"{name}.json")
    path = os.path.join(tmp, f"{name}.py")
    with open(path, "w") as f:
        f.write(
            "import sys\n"
            f"sys.path[:0] = [{REPO!r}, {os.path.join(REPO, 'tests')!r}]\n"
            f"OUT_ROOT = {os.path.join(tmp, 'bundles')!r}\n"
            f"OUT_JSON = {out_json!r}\n"
        )
        f.write(src)
    env = dict(os.environ)
    for k in (
        "BOOJUM_TPU_REPORT", "BOOJUM_TPU_AOT_DIR",
        "BOOJUM_TPU_AOT_REQUIRE", "BOOJUM_TPU_PROFILE",
    ):
        env.pop(k, None)
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, path],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"{name} driver failed:\n{proc.stdout}\n{proc.stderr[-4000:]}"
    )
    with open(out_json) as f:
        return json.load(f)


@functools.lru_cache(maxsize=1)
def _roundtrip():
    """Build the bundle in one subprocess, serve from it in a second
    FRESH subprocess whose persistent cache starts EMPTY."""
    tmp = tempfile.mkdtemp(prefix="boojum_aot_")
    build = _run_driver(
        _BUILD_SRC, tmp, "build",
        {"JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "build_cache")},
    )
    serve = _run_driver(
        _SERVE_SRC, tmp, "serve",
        {
            "BOOJUM_TPU_AOT_DIR": os.path.join(tmp, "bundles"),
            # an EMPTY cache dir: the only warm state is the bundle
            "JAX_COMPILATION_CACHE_DIR": os.path.join(tmp, "fresh_cache"),
        },
    )
    return tmp, build, serve


def test_roundtrip_zero_compile_bit_parity():
    """Acceptance: with a pre-built bundle, a cold process records ZERO
    XLA compiles (no cache misses, no dispatch compiles), every
    enumerated kernel is an aot_hit, and proof bytes + checkpoint
    stream are bit-identical to the JIT path."""
    _tmp, build, serve = _roundtrip()
    summary = serve["summary"]
    assert summary["cache_misses"] == 0, summary
    assert summary["num_dispatch_compiles"] == 0, summary
    assert summary["aot_misses"] == 0, summary
    assert summary["aot_hits"] == build["num_kernels"], summary
    assert summary["aot_deserialize_s"] > 0.0
    # every enumerated kernel present and hit
    assert len(serve["aot_entries"]) == build["num_kernels"]
    misses = [k for k, v in serve["aot_entries"].items() if not v]
    assert not misses, f"kernels that escaped the artifact store: {misses}"

    ref_proof, ref_line = baseline()
    assert serve["proof"] == ref_proof.to_json()
    ref_ckpts = [
        (e["seq"], e["round"], e["label"], e["digest"])
        for e in ref_line["checkpoints"]
    ]
    assert ref_ckpts, "reference recorded no checkpoints"
    assert [tuple(c) for c in serve["checkpoints"]] == ref_ckpts


def test_serve_report_line_validates_aot_schema():
    """The serve line carries aot.* counters/gauges and passes --check;
    tampered variants (missing deserialize gauge, negative counter,
    all-hit claim with nonzero compile count) FAIL it."""
    _tmp, _build, serve = _roundtrip()
    line = serve["report_line"]
    problems = report.validate_report(line)
    assert problems == [], problems
    counters = line["metrics"]["counters"]
    assert counters.get("aot.hits", 0) > 0
    assert "aot.deserialize_s" in line["metrics"]["gauges"]

    # missing deserialize gauge
    bad = json.loads(json.dumps(line))
    bad["metrics"]["gauges"].pop("aot.deserialize_s")
    assert any(
        "aot.deserialize_s" in p for p in report.validate_report(bad)
    )
    # malformed negative counter
    bad = json.loads(json.dumps(line))
    bad["metrics"]["counters"]["aot.hits"] = -3
    assert any(
        "aot metric aot.hits" in p for p in report.validate_report(bad)
    )
    # the lying line: all-aot_hit ledger with a nonzero compile count
    bad = json.loads(json.dumps(line))
    bad["compile_ledger"]["cache_misses"] = 7
    probs = report.validate_report(bad)
    assert any("cache misses" in p for p in probs), probs


def test_cold_process_carries_cost_actuals():
    """ISSUE 12 acceptance: the AOT bundle manifest persists per-kernel
    XLA cost actuals captured at BUILD time, and the zero-compile cold
    serve process still stamps a fully-attributed `cost` record — no
    recompilation needed to attribute flops/bytes."""
    _tmp, build, serve = _roundtrip()
    with_cost = [k for k in build["kernels"] if k.get("cost")]
    assert len(with_cost) >= 0.8 * build["num_kernels"], (
        f"only {len(with_cost)}/{build['num_kernels']} manifest kernels "
        f"carry cost actuals"
    )
    assert all(
        isinstance(k["cost"].get("bytes_accessed"), (int, float))
        for k in with_cost
    )
    line = serve["report_line"]
    cost = line.get("cost")
    assert isinstance(cost, dict), "cold serve line missing cost record"
    assert (
        len(cost.get("attributed_kernels") or [])
        >= 0.8 * build["num_kernels"]
    ), cost.get("attributed_kernels")
    ledger = line["compile_ledger"]
    assert set(cost.get("attributed_kernels") or []) <= set(
        ledger["kernel_names"]
    )
    # still a zero-compile process — the actuals came from the warm
    # pass / manifest, not from fresh compiles
    assert serve["summary"]["cache_misses"] == 0


def test_slo_view_surfaces_artifact_hit_rate():
    _tmp, build, serve = _roundtrip()
    summary = report.slo_summary([serve["report_line"]])
    assert summary["aot_kernels_warmed"] == build["num_kernels"]
    assert summary["aot_hit_rate"] == 1.0
    assert "aot artifacts" in report.render_slo(summary)


def test_export_artifacts_deserialize():
    """The jax.export half of the bundle: every kernel recorded as
    kind=export round-trips through jax.export.deserialize and names
    the build platform."""
    import jax
    from jax import export as jexport

    tmp, build, _serve = _roundtrip()
    exported = [k for k in build["kernels"] if k.get("kind") == "export"]
    assert exported, "no kernels were exported"
    ent = exported[0]
    with open(os.path.join(build["dir"], ent["file"]), "rb") as f:
        data = f.read()
    assert len(data) == ent["bytes"]
    rt = jexport.deserialize(data)
    assert jax.default_backend() in rt.platforms


def _stale_root(tmp_path, asm, config, jaxlib_version="0.0.0-stale"):
    """A bundle dir for (asm, config) whose manifest claims a different
    jaxlib — the canonical stale artifact."""
    from boojum_tpu.prover import aot

    root = str(tmp_path)
    bdir = aot.bundle_dir_for(root, asm, config)
    os.makedirs(bdir, exist_ok=True)
    plat = aot.platform_info()
    plat["jaxlib"] = jaxlib_version
    manifest = {
        "kind": aot.AOT_KIND,
        "schema": aot.AOT_SCHEMA,
        "platform": plat,
        "cache_entries": [],
        "kernels": [],
    }
    with open(os.path.join(bdir, aot.MANIFEST_NAME), "w") as f:
        json.dump(manifest, f)
    return root


def test_stale_bundle_graceful_jit_fallback(tmp_path):
    """Wrong jaxlib version in the manifest: load_bundle warns and
    returns None (counted as aot.stale_bundles), and prove() under
    BOOJUM_TPU_AOT_DIR still proves bit-identically via JIT."""
    from boojum_tpu.prover import aot, prove
    from boojum_tpu.utils import metrics as _metrics

    asm, setup, config = small_parts()
    root = _stale_root(tmp_path, asm, config)

    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec.getMessage())
    lg = logging.getLogger("boojum_tpu")
    lg.addHandler(handler)
    reg = _metrics.start_metrics()
    try:
        out = aot.load_bundle(root, asm, config, require=False)
    finally:
        lg.removeHandler(handler)
        _metrics.stop_metrics()
    assert out is None
    assert reg.counters.get("aot.stale_bundles") == 1
    stale_msgs = [m for m in records if "stale bundle" in m]
    assert stale_msgs and "jaxlib" in stale_msgs[0], records

    # the prove-side consult degrades to JIT, not a crash
    ref_proof, _ = baseline()
    prev = os.environ.get("BOOJUM_TPU_AOT_DIR")
    os.environ["BOOJUM_TPU_AOT_DIR"] = root
    try:
        proof = prove(asm, setup, config)
    finally:
        if prev is None:
            os.environ.pop("BOOJUM_TPU_AOT_DIR", None)
        else:
            os.environ["BOOJUM_TPU_AOT_DIR"] = prev
    assert proof.to_json() == ref_proof.to_json()


def test_stale_bundle_raises_under_require(tmp_path, monkeypatch):
    from boojum_tpu.prover import aot

    asm, _setup, config = small_parts()
    root = _stale_root(tmp_path, asm, config)
    monkeypatch.setenv("BOOJUM_TPU_AOT_REQUIRE", "1")
    with pytest.raises(aot.AotBundleError, match="stale bundle"):
        aot.load_bundle(root, asm, config)
    # missing bundle entirely is also a hard error under REQUIRE
    with pytest.raises(aot.AotBundleError, match="no artifact bundle"):
        aot.load_bundle(str(tmp_path / "empty"), asm, config)


def test_corrupt_entry_skipped(tmp_path):
    """A flipped byte in one cache entry: the entry is skipped (and
    counted), the rest of the bundle still installs."""
    import shutil

    import jax

    from boojum_tpu.prover import aot
    from boojum_tpu.utils import metrics as _metrics

    tmp, build, _serve = _roundtrip()
    bdir_src = build["dir"]
    root = str(tmp_path / "bundles")
    bdir = os.path.join(root, os.path.basename(bdir_src))
    shutil.copytree(bdir_src, bdir)
    manifest = json.load(open(os.path.join(bdir, aot.MANIFEST_NAME)))
    victim = next(
        e for e in manifest["cache_entries"] if e["file"].endswith("-cache")
    )
    vpath = os.path.join(bdir, victim["file"])
    blob = bytearray(open(vpath, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(vpath, "wb").write(bytes(blob))

    # the serve subprocesses own the sticky cache-key flip; restore this
    # process's value so later tier-1 tests keep their cache keys
    prev_flag = jax.config.jax_persistent_cache_enable_xla_caches
    asm, _setup, config = small_parts()
    reg = _metrics.start_metrics()
    try:
        out = aot.load_bundle(root, asm, config, require=False)
    finally:
        _metrics.stop_metrics()
        jax.config.update(
            "jax_persistent_cache_enable_xla_caches", prev_flag
        )
    assert out is not None
    assert out.skipped == 1
    assert reg.counters.get("aot.corrupt_entries") == 1
    assert os.path.basename(victim["file"]) not in out.cache_files
    assert len(out.cache_files) == len(manifest["cache_entries"]) - 1


def test_platform_info_does_not_memoize_failed_probe(monkeypatch):
    """A first call racing device availability (backend not up yet)
    must not pin device_kind='unknown' for the process lifetime — that
    would reject every bundle load and mis-identify every report
    line. Only a successful probe is memoized."""
    import jax

    from boojum_tpu.prover import aot

    saved = aot._PLATFORM_INFO
    try:
        aot._PLATFORM_INFO = None

        def _boom():
            raise RuntimeError("backend not initialized")

        monkeypatch.setattr(jax, "devices", _boom)
        monkeypatch.setattr(jax, "device_count", _boom)
        bad = aot.platform_info()
        assert bad["device_kind"] == "unknown"
        assert bad["num_devices"] == 0
        assert aot._PLATFORM_INFO is None  # failure NOT cached
        monkeypatch.undo()
        good = aot.platform_info()
        assert good["device_kind"] != "unknown"
        assert good["num_devices"] >= 1
        assert aot._PLATFORM_INFO is not None  # success memoized
    finally:
        aot._PLATFORM_INFO = saved
