"""tools/idle_by_span.py on traces built by hand: every idle gap has one
owner and the owners' sum is the window's idle time."""

import pytest

from benchmark import reduce_trace as R
from benchmark.tools import idle_by_span as I

MS = 1e6  # ns


def ev(name, start_ms, dur_ms):
    return R.Event(name, start_ms * MS, dur_ms * MS)


def planes(host_events, op_events):
    return [
        R.Plane("/host:CPU", [R.Line("python", host_events)]),
        R.Plane("/device:TPU:0", [
            R.Line("XLA Modules", []), R.Line("XLA Ops", op_events),
        ]),
    ]


def two_proves():
    """Two proves of 100 ms, the device at work at each one's start and
    end. Idle in each: 0.5-10 under the round alone, 20-24 under
    host.transcript, 30-30.5 under an upload's child span (a short gap),
    40-60 under queries/queries.assemble with a runtime event open, 70-99.5
    under the harness's annotation alone; and 1 ms between the annotations
    under nothing."""
    host, ops = [], []
    for k, t in enumerate((0, 101)):
        host += [
            ev("bench.prove", t, 100),
            ev("prove", t, 65),
            ev("round1_witness_commit", t, 35),
            ev("host.transcript", t + 19, 6),
            ev("host.upload", t + 29, 2), ev("h2d.sweep_table", t + 29.2, 1.6),
            ev("shard_args", t + 29.4, 1.2),  # the runtime's, inside the site
            ev("queries", t + 35, 30), ev("queries.assemble", t + 39, 22),
            ev("PjitFunction(concatenate)", t + 45, 10),
        ]
        ops += [
            ev("s", t, 0.5), ev("a", t + 10, 10), ev("b", t + 24, 6),
            ev("c", t + 30.5, 9.5), ev("d", t + 60, 10), ev("e", t + 99.5, 0.5),
        ]
    return planes(host, ops)


def test_the_table_sums_to_the_windows_idle_time():
    t = I.table(two_proves(), min_ms=1.0)
    assert t["proves"] == 2 and t["window_ms"] == pytest.approx(201.0)
    # a prove: 9.5 + 4 + 0.5 + 20 + 29.5 idle; 1 ms between the two
    assert t["idle_ms"] == pytest.approx(2 * 63.5 + 1.0)
    total = sum(a + b for a, b in t["rows"].values())
    assert total == pytest.approx(t["idle_ms"], abs=1e-6)
    # and it is the idle time reduce_trace reports for the same window
    red = R.reduce(two_proves(), [])
    assert red["clock_aligned"]
    assert 1e3 * (red["window_s"] - red["busy_s"]) == pytest.approx(t["idle_ms"])
    assert "sum less the window's idle time: +0.000000" in I.render(t, 1.0)


@pytest.mark.parametrize("owner,long_ms,short_ms", [
    # under two nested spans the gap goes to the inner one
    ("host.transcript", 8.0, 0.0),
    ("queries.assemble", 40.0, 0.0),
    ("h2d.sweep_table", 0.0, 1.0),
    # a stage with no child open owns its own gaps
    ("round1_witness_commit", 19.0, 0.0),
    ("bench.prove", 59.0, 0.0),
    # under no span at all
    ("no_annotation", 1.0, 0.0),
])
def test_a_gap_goes_to_the_innermost_span_open_at_its_middle(owner, long_ms, short_ms):
    rows = I.table(two_proves(), min_ms=1.0)["rows"]
    assert rows[owner] == pytest.approx([long_ms, short_ms])
    assert "host.upload" not in rows and "queries" not in rows and "prove" not in rows
    # the runtime's own event under the site's span does not take the gap
    assert "shard_args" not in rows


def test_long_gaps_carry_the_whole_host_path_and_their_prove():
    t = I.table(two_proves(), min_ms=1.0)
    by = {(g["prove"], round(g["at_ms"])): g for g in t["long_gaps"]}
    assert len(t["long_gaps"]) == 9  # four a prove and the one between
    g = by[(1, 40)]
    assert g["ms"] == pytest.approx(20.0)
    assert g["path"] == (
        "bench.prove/prove/queries/queries.assemble/PjitFunction(concatenate)"
    )
    assert by[(0, 100)]["path"] == "no_annotation"
    short = I.table(two_proves(), min_ms=0.1)["long_gaps"]
    assert any(g["path"].endswith("h2d.sweep_table/shard_args") for g in short)


def test_unowned_share_and_span_totals():
    t = I.table(two_proves(), min_ms=1.0)
    # of 127 ms in long gaps, 19 + 59 + 1 fall to a stage, the harness or nothing
    assert I.unowned_share(t["rows"]) == pytest.approx(79.0 / 127.0)
    assert t["spans"]["host.transcript"] == [2, pytest.approx(12.0)]
    assert "bench.prove" not in t["spans"]
    text = I.render(t, 1.0)
    assert "62.2 % falls to a stage span" in text
    assert "host.transcript" in text.split("program spans a prove")[1]


def test_a_trace_nothing_can_own_is_an_error():
    p = two_proves()
    with pytest.raises(ValueError):  # no annotation on any host line
        I.table([R.Plane("/host:CPU", [R.Line("python", [])]), p[1]])
    for e in p[1].lines[1].events:  # clocks apart: no op inside the window
        e.start_ns += 10_000 * MS
    with pytest.raises(ValueError):
        I.table(p)
