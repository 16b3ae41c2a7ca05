"""What the host was doing inside a prove (ISSUE 37): spans at the
transcript, around every upload and every blocking pull and around the
three parts of the query phase, the counters beside them, and device
memory by stage — on the CPU backend with the 2^10 acceptance circuit.

The rule the spans were added under: with nothing recording, the program
dispatches, uploads, converts, slices and frees exactly what it did before.
Pinned here as far as the CPU can show it: the compiled-executable calls of
one prove are the same number with a recorder installed as without, the
proof bytes are the same, and a recorded span tree holds numbers and
strings, never an array.
"""

import collections
import contextlib
import glob
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu import native
from boojum_tpu import transcript as T
from boojum_tpu.prover import prove
from boojum_tpu.utils import metrics, report, spans, transfer
from proving import baseline, small_parts

MIB = 1 << 20
STAGES = [
    "round1_witness_commit", "round2_stage2_commit", "round3_quotient",
    "round4_evaluations", "round5_deep_fri", "queries",
]


def walk(tree, path=()):
    """(path of names, span) for every span of a recorded tree."""
    for sp in tree:
        here = path + (sp["name"],)
        yield here, sp
        yield from walk(sp["children"], here)


def by_name(rep):
    found = collections.defaultdict(list)
    for path, sp in walk(rep["spans"]):
        found[sp["name"]].append((path, sp))
    return found


# ---------------------------------------------------------------------------
# The shared baseline prove's span tree (u64 kernels, no mesh, pow_bits 0)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,count", [
    # the setup cap and public inputs, a cap and its challenges a round,
    # the evaluations at z, a cap a FRI oracle, the final monomials, the
    # query index draw
    ("host.transcript", 9),
    ("host.sync", 8),
    ("queries.plan", 1),
    ("query_gather", 1),
    ("queries.assemble", 1),
    ("h2d.challenges_r2", 1),
    ("h2d.coset_index", 8),
    ("h2d.z_points", 1),
    ("h2d.deep_prep", 3),
    ("h2d.deep_challenges", 2),
    ("h2d.fri_challenge", 2),
    ("h2d.query_indices", 1),
    ("h2d.query_path_indices", 6),
    ("h2d.fri_rows", 2),
    ("d2h.witness_cap", 1),
    ("d2h.stage2_cap", 1),
    ("d2h.quotient_cap", 1),
    ("d2h.round4_evals", 1),
    ("d2h.fri_cap_0", 1),
    ("d2h.fri_cap_1", 1),
    ("d2h.fri_final_monomials", 1),
    ("d2h.query_gather", 1),
])
def test_span_is_recorded_with_its_count(name, count):
    _proof, rep = baseline()
    assert len(by_name(rep)[name]) == count


@pytest.mark.parametrize("outer,prefix", [
    ("host.upload", "h2d."), ("host.sync", "d2h."),
])
def test_a_site_span_is_the_one_child_of_its_kind_span(outer, prefix):
    _proof, rep = baseline()
    found = by_name(rep)
    assert found[outer]
    for _path, sp in found[outer]:
        names = [c["name"] for c in sp["children"]]
        assert len(names) == 1 and names[0].startswith(prefix), names
    sites = [
        (path, sp) for name, rows in found.items() if name.startswith(prefix)
        for path, sp in rows
    ]
    assert len(sites) == len(found[outer])
    assert all(path[-2] == outer for path, _sp in sites)
    assert "d2h.unlabelled" not in found


@pytest.mark.parametrize("name,parents", [
    # under the round span already open, or a span of that round
    ("host.transcript", {"prove", "queries.plan"} | set(STAGES[:5])
     | {"fri_oracle_0", "fri_oracle_1"}),
    ("host.upload", {"overlap_prefetch", "round3_coset_sweeps", "queries.plan",
                     "deep_prep_overlap", "fri_oracle_0", "fri_oracle_1"}
     | set(STAGES[:5])),
    ("host.sync", set(STAGES[:5]) | {"fri_oracle_0", "fri_oracle_1",
                                     "query_gather"}),
    ("queries.plan", {"queries"}),
    ("query_gather", {"queries"}),
    ("queries.assemble", {"queries"}),
])
def test_a_span_lies_under_the_round_that_was_open(name, parents):
    _proof, rep = baseline()
    rows = by_name(rep)[name]
    assert rows and {path[-2] for path, _sp in rows} <= parents
    assert all(path[0] == "prove" for path, _sp in rows)


def test_the_query_phase_is_its_three_parts_in_order():
    _proof, rep = baseline()
    (_path, queries), = by_name(rep)["queries"]
    assert [c["name"] for c in queries["children"]] == [
        "queries.plan", "query_gather", "queries.assemble",
    ]
    # and the parts leave next to nothing of the stage unowned
    parts = sum(c["wall_s"] for c in queries["children"])
    assert parts <= queries["wall_s"] + 1e-3
    assert parts >= 0.5 * queries["wall_s"]


@pytest.mark.parametrize("counter,attr", [
    ("transfer.h2d_ops", "ops"), ("transfer.h2d_bytes", "bytes"),
])
def test_the_upload_counters_are_what_the_sites_declare(counter, attr):
    _proof, rep = baseline()
    declared = sum(
        sp["attrs"][attr] for path, sp in walk(rep["spans"])
        if sp["name"].startswith("h2d.")
    )
    assert declared > 0
    assert rep["metrics"]["counters"][counter] == declared


def test_every_blocking_sync_is_a_span():
    _proof, rep = baseline()
    assert rep["metrics"]["counters"]["host.blocking_syncs"] == len(
        by_name(rep)["host.sync"]
    )


def test_a_recorded_span_tree_holds_no_array():
    _proof, rep = baseline()
    plain = (int, float, str, bool, type(None))

    def check(v, where):
        if isinstance(v, dict):
            for k, x in v.items():
                check(x, f"{where}.{k}")
        elif isinstance(v, (list, tuple)):
            for x in v:
                check(x, where)
        else:
            assert type(v) in plain, (where, type(v))

    for path, sp in walk(rep["spans"]):
        check({k: v for k, v in sp.items() if k != "children"}, "/".join(path))
    assert report.validate_report(rep) == []


def test_serialisation_is_the_programs_own_span():
    proof, _rep = baseline()
    rec = spans.SpanRecorder(sync=False)
    prev = spans.install_recorder(rec)
    try:
        blob = proof.to_json()
    finally:
        spans.install_recorder(prev)
    assert [sp["name"] for sp in rec.tree()] == ["proof.to_json"]
    assert blob == proof.to_json()  # and without a recorder: the same bytes


# ---------------------------------------------------------------------------
# Fresh proves: the transcript's own tally, device memory by stage, the
# sweep barrier, and what a recorder changes on the device (nothing)
# ---------------------------------------------------------------------------

EXECUTE = "PjRtCpuExecutable::Execute"


def _executions(trace_dir):
    """Compiled-executable calls in a profiler trace of the CPU backend:
    the runtime's own event, one an execution (a jitted function's call and
    an eager operation alike)."""
    from jax.profiler import ProfileData

    path, = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    return sum(
        1 for pl in ProfileData.from_file(path).planes for ln in pl.lines
        for e in ln.events if e.name == EXECUTE
    )


@contextlib.contextmanager
def _profiled(counts, key):
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        counts[key] = _executions(d)


@pytest.fixture(scope="module")
def fresh():
    """Two proves of the shared circuit, one plain and one under the flight
    recorder, each under the profiler, with the transcript's permutation
    counted from outside (so on the Python engine, where the permutation is
    a function one can wrap: no native library for these two proves;
    tests/test_native_transcript.py holds the native engine's counter to
    this one) and a device that reports memory: 6 MiB free at
    every reading (round 3 gets a barrier), and in use 100 MiB and a byte,
    with 4 MiB more a stage."""
    asm, setup, config = small_parts()
    prove(asm, setup, config)  # every shape compiled, every input cached
    mp = pytest.MonkeyPatch()
    tally = collections.Counter()
    readings = []

    def room():
        reg = metrics.current_registry()
        stage = reg.stage if reg is not None else None
        k = STAGES.index(stage) if stage in STAGES else 0
        readings.append(100 * MIB + 1 + 4 * MIB * k)
        return readings[-1] + 6 * MIB, readings[-1]

    def permutation(state, _inner=T.Poseidon2Transcript._PERMUTATION):
        tally["permutations"] += 1
        return _inner(state)

    mp.setattr(metrics, "device_memory_room", room)
    mp.setattr(native, "get_lib", lambda: None)
    mp.setattr(
        T.Poseidon2Transcript, "_PERMUTATION", staticmethod(permutation)
    )
    executions = {}
    try:
        with _profiled(executions, "plain"):
            plain = prove(asm, setup, config)
        plain_perms = tally["permutations"]
        plain_readings = len(readings)
        tally.clear()
        with _profiled(executions, "recorded"):
            with report.flight_recording(label="fresh", sync=False) as rec:
                recorded = prove(asm, setup, config)
        rep = report.build_report(rec)
    finally:
        mp.undo()
    return {
        "plain": plain, "recorded": recorded, "rep": rep,
        "executions": executions, "tally": tally["permutations"],
        "plain_perms": plain_perms, "plain_readings": plain_readings,
        "readings": readings,
    }


def test_a_recorder_changes_nothing_the_device_is_asked_to_run(fresh):
    ex = fresh["executions"]
    assert ex["plain"] > 100, ex  # the probe sees the prove
    assert ex["recorded"] == ex["plain"], ex
    assert fresh["recorded"].to_json() == fresh["plain"].to_json()
    assert fresh["plain"].to_json() == baseline()[0].to_json()


def test_the_permutation_counter_is_the_transcripts_own_tally(fresh):
    counters = fresh["rep"]["metrics"]["counters"]
    assert counters["transcript.permutations"] == fresh["tally"] > 0
    assert fresh["plain_perms"] == fresh["tally"]
    # the shared baseline ran the same transcript
    assert (
        baseline()[1]["metrics"]["counters"]["transcript.permutations"]
        == fresh["tally"]
    )


def test_memory_is_sampled_by_stage_under_a_registry_only(fresh):
    counters = fresh["rep"]["metrics"]["counters"]
    mem = {
        k[len(metrics.IN_USE_PREFIX):]: v for k, v in counters.items()
        if k.startswith(metrics.IN_USE_PREFIX)
    }
    # MiB, rounded up
    assert mem == {s: 101 + 4 * k for k, s in enumerate(STAGES)}
    # without a registry the allocator is asked by the prover's own two
    # choices only (the streamed commit's threshold is kept a process; the
    # round-3 barrier), not once by a span
    assert fresh["plain_readings"] == 1
    # the first boundary's own snapshot is not a sample
    assert "mem.in_use_mib.prove" not in counters


def test_the_sweep_barrier_is_a_sync_span(fresh):
    found = by_name(fresh["rep"])
    counters = fresh["rep"]["metrics"]["counters"]
    barriers = found["d2h.sweep_barrier"]
    assert len(barriers) == counters["quotient.sweep_barriers"] > 0
    assert all(
        path[-3:-1] == ("round3_coset_sweeps", "host.sync")
        for path, _sp in barriers
    )
    assert counters["host.blocking_syncs"] == len(found["host.sync"])


# ---------------------------------------------------------------------------
# The helpers alone
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _recording(registry=True):
    rec = spans.SpanRecorder(sync=False)
    prev = spans.install_recorder(rec)
    reg = metrics.start_metrics() if registry else None
    try:
        yield rec, reg
    finally:
        if registry:
            metrics.stop_metrics()
        spans.install_recorder(prev)


def test_upload_counts_what_it_is_told_and_keeps_numbers():
    host = np.arange(6, dtype=np.uint64)
    with _recording() as (rec, reg):
        with transfer.upload("unit", host.nbytes, 2) as sp:
            dev = jnp.asarray(host), jnp.asarray(host)
        assert sp["name"] == "h2d.unit"
    (outer,) = rec.tree()
    assert outer["name"] == "host.upload"
    (inner,) = outer["children"]
    assert inner["attrs"] == {"bytes": 48, "ops": 2}
    assert reg.counters["transfer.h2d_ops"] == 2
    assert reg.counters["transfer.h2d_bytes"] == 48
    del dev


def test_uploaded_learns_its_size_from_what_was_built():
    host = np.arange(10, dtype=np.uint32)
    with _recording() as (rec, reg):
        out = transfer.uploaded(
            "pair", lambda: (jnp.asarray(host), jnp.asarray(host))
        )
    assert isinstance(out, tuple) and out[0].shape == (10,)
    (inner,) = rec.tree()[0]["children"]
    assert inner["name"] == "h2d.pair"
    assert inner["attrs"] == {"bytes": 80, "ops": 1}
    assert reg.counters["transfer.h2d_ops"] == 1
    assert reg.counters["transfer.h2d_bytes"] == 80


@pytest.mark.parametrize("label,site,expect", [
    ("given", None, "d2h.given"),
    ("given", "site", "d2h.given"),
    (None, "site", "d2h.site"),
    (None, None, "d2h.unlabelled"),
])
def test_a_pull_is_named_by_its_label_or_the_site_open(label, site, expect):
    dev = jnp.arange(5)
    with _recording() as (rec, reg):
        with transfer.pull_site(site) if site else contextlib.nullcontext():
            out = transfer.to_host(dev, label)
        transfer.to_host(np.arange(3))  # a host value waits for nothing
    np.testing.assert_array_equal(out, np.arange(5))
    (outer,) = rec.tree()
    assert outer["name"] == "host.sync"
    assert [c["name"] for c in outer["children"]] == [expect]
    assert reg.counters["host.blocking_syncs"] == 1


def test_a_fetch_batch_is_one_sync_span_under_its_label():
    arrays = [jnp.arange(4), np.arange(2), jnp.arange(9)]
    with _recording() as (rec, reg):
        got = transfer.fetch_np(*arrays, label="batch")
        transfer.fetch_np(np.arange(2), label="host_only")
    assert [g.shape for g in got] == [(4,), (2,), (9,)]
    (outer,) = rec.tree()
    assert [c["name"] for c in outer["children"]] == ["d2h.batch"]
    assert reg.counters["host.blocking_syncs"] == 1


@pytest.mark.parametrize("what", ["sync", "upload", "uploaded"])
def test_with_nothing_recording_a_site_opens_nothing(what, monkeypatch):
    """No recorder, no registry, no trace directory: one check, no span,
    no count and no question to the allocator."""
    asked = []
    monkeypatch.setattr(
        metrics, "device_memory_room", lambda: asked.append(1) or (1, 0)
    )
    assert not spans.recording() and metrics.current_registry() is None
    if what == "sync":
        with transfer.sync("x") as got:
            pass
    elif what == "upload":
        with transfer.upload("x", 8) as got:
            pass
    else:
        got = transfer.uploaded("x", lambda: None)
    assert got is None and asked == []


@pytest.mark.parametrize("registry,stage,expect", [
    (True, "round5_deep_fri", {"mem.in_use_mib.round5_deep_fri": 3}),
    (True, None, {}),       # outside a stage: nowhere to file it
    (False, "round5_deep_fri", None),  # no registry: nothing is asked
])
def test_in_use_is_folded_as_a_maximum_into_the_open_stage(
    monkeypatch, registry, stage, expect
):
    readings = iter([MIB + 1, 3 * MIB, 2 * MIB])
    asked = []

    def room():
        asked.append(1)
        return 16 * MIB, next(readings)

    monkeypatch.setattr(metrics, "device_memory_room", room)
    with _recording(registry) as (_rec, reg):
        if registry and stage:
            metrics.stage_boundary(stage)
        with transfer.sync("a"):
            pass
        with transfer.upload("b", 4):
            pass
        with transfer.sync("c"):
            pass
        if registry:
            mem = {k: v for k, v in reg.counters.items() if k.startswith("mem.")}
            assert mem == expect
            metrics.stage_closed()
            with transfer.sync("d"):
                pass
    assert len(asked) == (3 if registry and stage else 0)


@pytest.mark.parametrize("peaks,expect", [
    # the allocator's peak rose while the stage was open: the stage reached
    # it, whatever the samples at its syncs and uploads saw
    ((10 * MIB, 12 * MIB + 5), {"mem.in_use_mib.round3_quotient": 13}),
    ((10 * MIB, 10 * MIB), {"mem.in_use_mib.round3_quotient": 4}),
    ((None, 12 * MIB), {"mem.in_use_mib.round3_quotient": 4}),
])
def test_a_peak_that_rose_in_a_stage_is_folded_in_at_its_close(
    monkeypatch, peaks, expect
):
    seen = iter(peaks)

    def stats():
        peak = next(seen)
        return None if peak is None else {
            "bytes_in_use": 0, "peak_bytes_in_use": peak
        }

    monkeypatch.setattr(metrics, "device_memory_stats", stats)
    monkeypatch.setattr(metrics, "device_memory_room", lambda: (16 * MIB, 4 * MIB))
    with _recording() as (_rec, reg):
        metrics.stage_boundary("round3_quotient")
        with transfer.sync("a"):
            pass
        metrics.stage_closed()
        metrics.stage_closed()  # closed already: asks nothing
        mem = {k: v for k, v in reg.counters.items() if k.startswith("mem.")}
    assert mem == expect


def test_a_backend_that_reports_no_memory_leaves_no_counter():
    # XLA:CPU: device_memory_room() is None, and so the baseline's line
    _proof, rep = baseline()
    assert not [
        k for k in rep["metrics"]["counters"]
        if k.startswith(metrics.IN_USE_PREFIX)
    ]


def test_folding_registries_keeps_the_larger_reading_and_adds_the_rest():
    a, b = metrics.MetricsRegistry(), metrics.MetricsRegistry()
    a.count_max("mem.in_use_mib.queries", 7)
    a.count_max("mem.in_use_mib.queries", 5)
    a.count("transfer.h2d_ops", 2)
    b.count_max("mem.in_use_mib.queries", 6)
    b.count("transfer.h2d_ops", 3)
    a.fold(b)
    assert a.counters == {"mem.in_use_mib.queries": 7, "transfer.h2d_ops": 5}


def test_the_transcript_counts_each_permutation_it_runs(monkeypatch):
    calls = []
    inner = T.Poseidon2Transcript._PERMUTATION
    monkeypatch.setattr(
        T.Poseidon2Transcript, "_PERMUTATION",
        staticmethod(lambda s: calls.append(1) or inner(s)),
    )
    reg = metrics.start_metrics()
    try:
        t = T.make_transcript("poseidon2")
        t.witness_field_elements(range(1, 20))  # 19 + padding: 3 blocks
        t.get_ext_challenge()
        t.get_multiple_challenges(9)            # squeezes past the rate
        assert reg.counters["transcript.permutations"] == len(calls) == 4
    finally:
        metrics.stop_metrics()
    t.get_multiple_challenges(8)  # without a registry: runs, counts nothing
    assert len(calls) == 5


def test_the_grind_is_transcript_time():
    from boojum_tpu.prover.pow import pow_grind

    with _recording() as (rec, reg):
        t = T.make_transcript("poseidon2")
        t.witness_field_elements([1, 2, 3])
        assert pow_grind(t, 0) == 0
        nonce = pow_grind(t, 4)
    assert [sp["name"] for sp in rec.tree()] == ["host.transcript"]
    assert rec.tree()[0]["attrs"] == {"pow_bits": 4}
    assert reg.counters["transcript.permutations"] >= 1
    t2 = T.make_transcript("poseidon2")
    t2.witness_field_elements([1, 2, 3])
    assert pow_grind(t2, 4) == nonce  # and the same nonce without a recorder
