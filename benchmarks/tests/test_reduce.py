"""The trace reduction, on hand-made intervals (exact answers) and on the
recorded fixture (against a brute-force count that shares no code with it)."""

import os
import re

import pytest

from benchmarks.trace import extract, reduce
from benchmarks.trace import intervals as iv
from benchmarks.trace.extract import Op, Span, Trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def op(name, lo, hi, lane="sync"):
    return Op(name, lo, hi, lane=lane)


@pytest.fixture()
def handmade():
    """One device: A [0,2], B [1,3], all-reduce [3,5] with C [4,4.5] under it;
    a second device busy [0,1] only. Harness spans: epoch [0,6] holding
    prefetch.get [0,0.5] and run_epoch [0.5,5.5]; a second epoch [6,10] with
    nothing inside. Window [0,10]."""
    return Trace(
        devices={
            "d0": [op("A", 0, 2), op("B", 1, 3), op("all-reduce.1", 3, 5),
                   op("C", 4, 4.5)],
            "d1": [op("A", 0, 1)],
        },
        spans=[Span("bench/epoch", 0, 6), Span("bench/prefetch.get", 0, 0.5),
               Span("bench/run_epoch", 0.5, 5.5), Span("bench/epoch", 6, 10)],
    )


def test_interval_arithmetic():
    assert iv.union([(3, 4), (0, 1), (1, 2), (1.5, 2.5)]) == [(0, 2.5), (3, 4)]
    assert iv.total(iv.union([(0, 2), (1, 3)])) == 3
    assert iv.clip([(0, 2), (5, 9)], 1, 6) == [(1, 2), (5, 6)]
    assert iv.intersect([(0, 4)], [(1, 2), (3, 5)]) == [(1, 2), (3, 4)]
    assert iv.subtract([(0, 10)], [(1, 2), (4, 12)]) == [(0, 1), (2, 4)]
    assert iv.subtract([(0, 1)], []) == [(0, 1)]


def test_busy_union_and_idle_share(handmade):
    window = handmade.window()
    assert window == (0, 10)
    assert reduce.busy_seconds(handmade, window) == {"d0": 5.0, "d1": 1.0}
    idle = reduce.idle_share(handmade, window)
    assert idle["d0"] == pytest.approx(0.5) and idle["d1"] == pytest.approx(0.9)
    # a window that cuts an operation counts only the part inside
    assert reduce.busy_seconds(handmade, (1.5, 3.5))["d0"] == pytest.approx(2.0)


def test_pattern_sums_count_overlap_twice(handmade):
    sec = reduce.matching_seconds(handmade, (0, 10), r"^(A|B)$")
    assert sec == {"d0": 4.0, "d1": 1.0}  # a sum, not the union (3.0)
    assert reduce.matching_seconds(handmade, (0, 10), "all-reduce")["d0"] == 2.0
    assert reduce.matching_seconds(handmade, (0, 10), "nothing")["d0"] == 0.0


def test_gap_attribution_by_innermost_span(handmade):
    gaps = dict(reduce.idle_gaps(handmade, (0, 10), device="d0"))
    # idle [5,10]: run_epoch still open until 5.5, then the rest of epoch 1
    # until 6, then epoch 2 (a leaf: nothing nested in it) until 10
    assert gaps == {"bench/run_epoch": pytest.approx(0.5),
                    "bench/epoch": pytest.approx(4.5)}
    # the idlest device is the default: d1 is idle from 1 on
    worst = dict(reduce.idle_gaps(handmade, (0, 10)))
    assert sum(worst.values()) == pytest.approx(9.0)
    # time outside every span is named so
    assert dict(reduce.idle_gaps(handmade, (0, 12), device="d0"))["outside"] \
        == pytest.approx(2.0)


def test_nested_ops_count_once_and_async_spans_are_not_busy():
    """The rounds scan is one event over its whole body: sums and rankings
    take leaves and self time, the busy union takes everything; a span of
    the asynchronous lane is never busy time."""
    ops = extract.nest([op("while.1", 0, 10), op("fusion.1", 1, 3),
                        op("all-reduce-start.1", 3, 3.5),
                        op("fusion.2", 4, 8), op("all-reduce-done.1", 8, 9)])
    ops.append(op("all-reduce-start.1", 3, 9, lane="async"))
    ops.append(op("copy-start.1", 11, 12, lane="async"))
    trace = Trace(devices={"d0": ops}, spans=[Span("bench/epoch", 0, 20)])
    window = (0, 20)
    assert [o.leaf for o in ops[:5]] == [False, True, True, True, True]
    assert ops[0].self_s == pytest.approx(10 - 2 - 0.5 - 4 - 1)
    assert reduce.busy_seconds(trace, window)["d0"] == pytest.approx(10.0)
    assert reduce.matching_seconds(trace, window, "fusion|while")["d0"] \
        == pytest.approx(6.0)  # the parent is not a leaf
    collective = dict(pattern="^all-reduce", field="name")
    assert reduce.matching_seconds(trace, window, how="union",
                                   lanes=("sync", "async"), **collective)["d0"] \
        == pytest.approx(6.0)  # [3, 9], start, span and done together
    top = dict(reduce.top_ops(trace, window))
    assert top["fusion.2"] == pytest.approx(4.0)
    assert top["while.1 (self)"] == pytest.approx(2.5)
    assert "copy-start.1" not in top


def test_host_seconds_per_span_and_top_ops(handmade):
    host = reduce.span_host_seconds(handmade, "bench/epoch", device="d0")
    assert host == [pytest.approx(1.0), pytest.approx(4.0)]
    top = reduce.top_ops(handmade, (0, 10), n=2)
    assert top[0][0] == "A" and top[0][1] == pytest.approx(1.5)  # (2+1)/2 devices


# -- the recorded trace ------------------------------------------------------


def raw_events():
    """The fixture read directly: [(name, start, end, has_hlo_op)]."""
    from jax.profiler import ProfileData

    path = os.path.join(FIXTURES, "tiny.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                lo = e.start_ns * 1e-9
                out.append((e.name, lo, lo + e.duration_ns * 1e-9,
                            "hlo_op" in dict(e.stats)))
    return out


def covered(segments, lo, hi):
    """Brute force: cut [lo, hi] at every boundary and add up the pieces that
    some segment covers."""
    cuts = sorted({lo, hi, *[t for s in segments for t in s if lo < t < hi]})
    return sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s[0] <= a and b <= s[1] for s in segments))


def test_fixture_is_read_whole_and_as_a_rehearsal():
    trace = extract.load(FIXTURES)
    raw = raw_events()
    assert trace.rehearsal and list(trace.devices) == ["host-xla"]
    assert len(trace.devices["host-xla"]) == sum(1 for e in raw if e[3])
    assert len([s for s in trace.spans if s.name == "bench/epoch"]) == 3
    assert {s.name for s in trace.spans} == {
        "bench/epoch", "bench/prefetch.get", "bench/run_epoch"}


def test_fixture_busy_idle_pattern_and_gaps_match_brute_force():
    trace = extract.load(FIXTURES)
    raw = raw_events()
    epochs = [e for e in raw if e[0] == "bench/epoch"]
    window = (min(e[1] for e in epochs), max(e[2] for e in epochs))
    assert trace.window() == pytest.approx(window)
    ops = [(e[1], e[2]) for e in raw if e[3]]
    busy = covered(ops, *window)
    assert 0 < busy < window[1] - window[0]
    assert reduce.busy_seconds(trace, window)["host-xla"] == pytest.approx(busy)
    assert reduce.idle_share(trace, window)["host-xla"] == pytest.approx(
        1 - busy / (window[1] - window[0]))

    dots = [e for e in raw if e[3] and re.search("dot_general", e[0])]
    assert dots
    assert reduce.matching_seconds(trace, window, "dot_general")["host-xla"] \
        == pytest.approx(sum(min(e[2], window[1]) - max(e[1], window[0])
                             for e in dots))

    gaps = dict(reduce.idle_gaps(trace, window))
    assert sum(gaps.values()) == pytest.approx(window[1] - window[0] - busy)
    # every prefetch.get span sleeps with nothing running: all of it is idle
    sleeps = sum(e[2] - e[1] for e in raw if e[0] == "bench/prefetch.get")
    assert gaps["bench/prefetch.get"] == pytest.approx(sleeps, rel=0.02)
    # between the epochs the recording sleeps outside every span
    ordered = sorted(epochs, key=lambda e: e[1])
    between = [(a[2], b[1]) for a, b in zip(ordered, ordered[1:])]
    assert gaps["outside"] == pytest.approx(
        sum(b - a for a, b in between)
        - sum(covered(ops, a, b) for a, b in between))
    assert gaps["bench/run_epoch"] > 0
