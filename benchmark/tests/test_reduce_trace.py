"""The reduction from a trace to busy/idle, module sums, families and named
gaps, on a trace built by hand, and the loader on a small recorded one."""

import os

import pytest

from benchmark import reduce_trace as R

HERE = os.path.dirname(os.path.abspath(__file__))
FAMILIES = R.load_families(os.path.join(os.path.dirname(HERE), "families.json"))
MS = 1e6  # ns


def ev(name, start_ms, dur_ms, **stats):
    return R.Event(name, start_ms * MS, dur_ms * MS, stats)


def hand_trace():
    """Two proves of 100 ms each on the host, with rounds; on the device
    modules and ops with gaps between them."""
    host = R.Line("python", [
        ev("bench.prove", 0, 100),
        ev("prove", 1, 98),
        ev("round1_witness_commit", 1, 29),
        ev("round3_quotient", 30, 30),
        ev("PjitFunction(body_p)", 31, 1),
        ev("round5_deep_fri", 60, 39),
        ev("bench.prove", 100, 100),
        ev("prove", 101, 98),
        ev("round1_witness_commit", 101, 29),
        ev("round3_quotient", 130, 30),
        ev("round5_deep_fri", 160, 39),
    ])
    modules = R.Line("XLA Modules", [
        ev("jit__lde_planes(11)", 2, 10),
        ev("jit_leaf_digests_planes(12)", 12, 8),
        ev("jit_body_p(13)", 32, 20),
        ev("jit_fn(14)", 62, 10),
        ev("jit_mystery(15)", 80, 5),
        ev("jit__lde_planes(11)", 102, 10),
        ev("jit_leaf_digests_planes(12)", 112, 8),
        ev("jit_body_p(13)", 132, 20),
        ev("jit_fn(14)", 162, 10),
        ev("jit_mystery(15)", 180, 5),
    ])
    # ops cover the modules except 2 ms inside each sweep, and overlap
    ops = R.Line("XLA Ops", [
        ev("fusion.1", 2, 10), ev("fusion.2", 12, 8),
        ev("sweep.a", 32, 9), ev("sweep.b", 40, 2), ev("sweep.c", 43, 9),
        ev("fold", 62, 10), ev("x", 80, 5),
        ev("fusion.1", 102, 10), ev("fusion.2", 112, 8),
        ev("sweep.a", 132, 9), ev("sweep.b", 140, 2), ev("sweep.c", 143, 9),
        ev("fold", 162, 10), ev("x", 180, 5),
    ])
    return [
        R.Plane("/host:metadata", []),
        R.Plane("/host:CPU", [host]),
        R.Plane("/device:TPU:0", [modules, ops]),
        R.Plane("/device:TPU:0 (SparseCore)", []),
    ]


def test_union_and_gaps():
    assert R.union_seconds([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert R.union_seconds([]) == 0.0
    assert R.gaps([(5, 10), (8, 20)], 0, 30) == [(0, 5), (20, 30)]
    assert R.gaps([], 0, 30) == [(0, 30)]
    assert R.gaps([(0, 30)], 0, 30) == []


def test_busy_idle_window_and_launches():
    red = R.reduce(hand_trace(), FAMILIES)
    assert red["chips"] == 1 and red["proves"] == 2 and red["clock_aligned"]
    assert red["window_s"] == pytest.approx(0.200)
    # per prove: 10 + 8 + (9 + 2 + 9, of which sweep.a and sweep.b overlap by
    # 1 ms) + 10 + 5 = 52 ms busy
    assert red["busy_s"] == pytest.approx(2 * 0.052)
    assert red["idle_share_pct"] == pytest.approx(100 * (1 - 0.104 / 0.200))
    assert red["launches"] == 10


def test_module_sums_and_families():
    red = R.reduce(hand_trace(), FAMILIES)
    by = {r["name"]: r for r in red["modules"]}
    assert by["jit__lde_planes(11)"]["count"] == 2
    assert by["jit__lde_planes(11)"]["seconds"] == pytest.approx(0.020)
    assert by["jit__lde_planes(11)"]["family"] == "commit"
    assert by["jit_body_p(13)"]["family"] == "sweep"
    # the program names several kernels jit_fn: the round it was launched in
    # tells them apart
    assert by["jit_fn(14)"]["family"] == "fri"
    assert by["jit_mystery(15)"]["family"] == "other"
    assert red["family_seconds"]["commit"] == pytest.approx(0.036)
    assert red["family_seconds"]["other"] == pytest.approx(0.010)
    assert red["device_ops"][0] == ["jit_body_p(13)", pytest.approx(0.040)]
    secs, count = R.module_seconds(red, "lde_planes")
    assert (secs, count) == (pytest.approx(0.020), 2)


def test_gaps_are_named_by_the_innermost_span():
    red = R.reduce(hand_trace(), FAMILIES)
    names = [g[0] for g in red["idle_gaps"]]
    assert len(red["idle_gaps"]) == 5
    # the longest gaps: 85..100 + 100..102 (17 ms, its middle in
    # round5_deep_fri), 185..200, 20..32 (its middle in round1)
    assert red["idle_gaps"][0][1] == pytest.approx(0.017)
    assert names[0] == "round5_deep_fri"
    assert "round1_witness_commit" in names
    assert R.gap_name(["bench.prove", "prove", "round3_quotient",
                       "PjitFunction(body_p)"]) == "round3_quotient [PjitFunction(body_p)]"
    assert R.gap_name([]) == "no_annotation"


def test_known_kernel_names_leave_nothing_in_other():
    """The jitted functions behind precompile.enumerate_kernels' resident
    library (PR 23's listing), each in the round that launches it."""
    known = {
        "round1_witness_commit": [
            "jit__imono_p_jit", "jit__lde_planes", "jit_leaf_digests_planes",
            "jit_node_layers_planes", "jit__concat_rows",
        ],
        "round2_stage2_commit": [
            "jit__all_chunk_num_den_p", "jit_ext_batch_inverse",
            "jit__lookup_denominators_p", "jit__z_and_partials_p", "jit_fn",
            "jit__lde_planes", "jit_leaf_digests_planes",
        ],
        "round3_quotient": [
            "jit__zshift_p", "jit__coset_eval_q_p", "jit_body_p",
            "jit__quotient_interp_p", "jit_batch_inverse",
        ],
        "round4_evaluations": ["jit__evals_p"],
        "round5_deep_fri": [
            "jit__deep_denoms_p", "jit_ext_batch_inverse", "jit__deep_block_p",
            "jit__deep_combine_p", "jit_fn", "jit__fri_final_p",
            "jit_batch_inverse",
        ],
    }
    for span, names in known.items():
        for n in names:
            fam = R.classify(f"{n}(123)", f"bench.prove/prove/{span}", FAMILIES)
            assert fam != "other", (span, n)
    assert R.classify("jit_fn(1)", "bench.prove/prove/round5_deep_fri", FAMILIES) == "fri"
    assert R.classify("jit_fn(1)", "bench.prove/prove/round2_stage2_commit", FAMILIES) == "sweep"
    assert R.classify("jit_fn(1)", "", FAMILIES) == "other"


def test_run_id_places_a_module_at_its_launch():
    planes = hand_trace()
    host = planes[1].lines[0]
    # launched in round 3, ran on the device while the host was in round 5
    host.events.append(ev("TpuExecute", 35, 1, run_id=77))
    planes[2].lines[0].events.append(ev("jit_fn(99)", 70, 1, run_id=77))
    red = R.reduce(planes, FAMILIES)
    by = {r["name"]: r for r in red["modules"]}
    assert by["jit_fn(99)"]["family"] == "sweep"


def test_a_trace_with_nothing_on_the_device_is_an_error():
    planes = hand_trace()
    with pytest.raises(ValueError):
        R.reduce(planes[:2], FAMILIES)
    planes[2].lines = [R.Line("XLA Modules", []), R.Line("XLA Ops", [])]
    with pytest.raises(ValueError):
        R.reduce(planes, FAMILIES)


def test_unaligned_clocks_fall_back_to_the_device_extent():
    planes = hand_trace()
    for ln in planes[2].lines:
        for e in ln.events:
            e.start_ns += 10_000 * MS
    red = R.reduce(planes, FAMILIES)
    assert not red["clock_aligned"]
    assert red["window_s"] == pytest.approx(0.183)
    assert red["busy_s"] == pytest.approx(0.104)


def test_loader_reads_a_recorded_trace():
    """A CPU trace of two annotated steps recorded with jax.profiler
    (python tracer off): the loader finds the annotations, nested."""
    planes = R.load_xplane(os.path.join(HERE, "data", "cpu_small.xplane.pb"))
    assert any(p.name == "/host:CPU" for p in planes)
    line = R.host_annotation_line(planes)
    assert line is not None
    proves = [e for e in line.events if e.name == "bench.prove"]
    assert len(proves) == 2
    inner = [e for e in line.events if e.name == "round1"][0]
    path = R.span_path_at(line.events, inner.start_ns + 1)
    assert path[:2] == ["bench.prove", "round1"]
    with pytest.raises(ValueError):  # a CPU trace has no device plane
        R.reduce(planes, FAMILIES)
