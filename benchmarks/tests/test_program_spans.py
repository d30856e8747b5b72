"""The reader of the program's own host spans (``trace/program_spans.py``,
``trace/readers/program_span_ms.py``): on hand-made intervals (exact answers),
on a small profile made here (``find`` and ``load`` against the files), and in
a rehearsed cell (all eight metrics read something)."""

import json
import os
import time

import pytest

from benchmarks.lib import cells
from benchmarks.run import Ctx
from benchmarks.trace import extract, program_spans, reduce
from benchmarks.trace.extract import Op, Trace
from benchmarks.trace.program_spans import Span
from benchmarks.trace.readers import program_span_ms

from benchmarks.tests.test_harness import run_cell

LOOP, BUILDER = "host#0", "host#1"  # two thread lines
METRICS = sorted(
    m["name"] for m in cells.benchmark_json()["per_layer"]
    if cells.layer_metric(m["name"])["reader"] == "program_span_ms")
BOTH = ["epoch-dispatch", "loss-fetch"]


def epoch_spans(n, t, wait, inputs, dispatch, fetch, account):
    """The loop's spans of epoch ``n`` laid end to end from ``t``, and the
    builder thread's plan-build over the same stretch."""
    out, at = [], t
    for name, length in (("plan-wait", wait), ("epoch-inputs", inputs),
                         ("epoch-dispatch", dispatch), ("loss-fetch", fetch),
                         ("epoch-account", account)):
        out.append(Span(name, at, at + length, LOOP, {"epoch": n}))
        at += length
    out.append(Span("plan-build", t, t + 0.5, BUILDER, {"epoch": n + 1}))
    return out


@pytest.fixture()
def handmade():
    """Three epochs of 10 s, each: plan-wait 1, inputs 1, dispatch 2,
    loss-fetch 5, account 1. The device (one program a ``while`` over two
    leaf ops) runs [t+3, t+8] in epochs 1 and 3: it starts 1 s into the
    dispatch, so the dispatch holds 1 s of idle, the fetch no head and a tail
    of 1 s (the fetch ends at t+9). Epoch 2 stalls: its program starts 3 s
    INTO the fetch (t+7) and ends at t+8.5: dispatch idle 2, head 3, tail
    0.5. A second device is busy throughout and must not be chosen."""
    ops, spans = [], []
    for n, t in ((1, 0.0), (2, 10.0), (3, 20.0)):
        lo, hi = (t + 7, t + 8.5) if n == 2 else (t + 3, t + 8)
        mid = (lo + hi) / 2
        ops += [Op("while.1", lo, hi), Op("fusion.1", lo, mid),
                Op("fusion.2", mid, hi)]
        spans += epoch_spans(n, t, 1, 1, 2, 5, 1)
    trace = Trace(
        devices={"idle-one": extract.nest(ops), "busy-one": [Op("A", 0, 30)]},
        spans=[extract.Span("bench/epoch", t, t + 10) for t in (0, 10, 20)])
    ctx = Ctx(trace, trace.window(), {})
    ctx.program_spans = spans  # what program_spans.of(ctx) would have read
    return ctx


def read(ctx, span, measure, how="median"):
    return program_span_ms.read(ctx, span=span, measure=measure, how=how)


def test_head_tail_and_outside_split_the_idle_time(handmade):
    assert read(handmade, "plan-wait", "length") == pytest.approx(1000)
    assert read(handmade, "loss-fetch", "length", "max") == pytest.approx(5000)
    assert read(handmade, "epoch-dispatch", "idle") == pytest.approx(1000)
    assert read(handmade, "epoch-dispatch", "idle", "max") == pytest.approx(2000)
    assert read(handmade, "loss-fetch", "idle_head") == pytest.approx(0)
    assert read(handmade, "loss-fetch", "idle_tail") == pytest.approx(1000)
    # per epoch outside the two spans: wait 1 + inputs 1 + account 1
    assert read(handmade, BOTH, "idle_outside") == pytest.approx(3000)
    # the builder thread's span overlaps the loop's and changes nothing
    assert read(handmade, "plan-build", "idle") == pytest.approx(500)


def test_a_stalled_epoch_shows_in_the_wait_that_held_it(handmade):
    assert read(handmade, "loss-fetch", "idle_head", "max") == pytest.approx(3000)
    assert read(handmade, "loss-fetch", "idle_tail", "max") == pytest.approx(1000)
    assert read(handmade, "loss-fetch", "idle_tail", "mean") == pytest.approx(
        (1000 + 500 + 1000) / 3)


def test_the_four_means_add_up_to_the_windows_idle_time(handmade):
    parts = (read(handmade, "epoch-dispatch", "idle", "mean")
             + read(handmade, "loss-fetch", "idle_head", "mean")
             + read(handmade, "loss-fetch", "idle_tail", "mean")
             + read(handmade, BOTH, "idle_outside"))
    window = handmade.window
    share = max(reduce.idle_share(handmade.trace, window).values())
    assert share == pytest.approx(1 - 11.5 / 30)
    assert parts == pytest.approx(share * (window[1] - window[0]) / 3 * 1e3)


def test_a_span_no_operation_starts_or_ends_in_is_all_head():
    """A fetch that waits with the device idle throughout (nothing starts,
    nothing ends in it) is head, not tail, and is counted once."""
    trace = Trace(devices={"d": [Op("A", 0, 1), Op("B", 9, 10)]},
                  spans=[extract.Span("bench/epoch", 0, 10)])
    ctx = Ctx(trace, trace.window(), {})
    ctx.program_spans = [Span("loss-fetch", 2, 6, LOOP)]
    assert read(ctx, "loss-fetch", "idle") == pytest.approx(4000)
    assert read(ctx, "loss-fetch", "idle_head") == pytest.approx(4000)
    assert read(ctx, "loss-fetch", "idle_tail") == pytest.approx(0)
    assert read(ctx, ["loss-fetch"], "idle_outside") == pytest.approx(4000)


def test_nothing_to_read_is_none_not_zero(handmade):
    assert read(Ctx(None, None, {}), "loss-fetch", "idle") is None
    handmade.program_spans = []  # the parent's program: no span of its own
    for name in METRICS:
        spec = cells.layer_metric(name)
        assert program_span_ms.read(handmade, **spec["args"]) is None, name
    with pytest.raises(ValueError):
        handmade.program_spans = [Span("loss-fetch", 1, 2, LOOP)]
        read(handmade, "loss-fetch", "no-such-measure")


def test_the_eight_metrics_are_the_issues(handmade):
    assert METRICS == sorted([
        "loop_plan_wait_ms_per_epoch", "loop_dispatch_idle_ms_per_epoch",
        "loop_launch_idle_ms_per_epoch", "loop_fetch_tail_idle_ms_per_epoch",
        "loop_unattributed_idle_ms_per_epoch", "loop_dispatch_idle_ms_max",
        "loop_launch_idle_ms_max", "loop_fetch_tail_idle_ms_max"])
    values = {name: program_span_ms.read(
        handmade, **cells.layer_metric(name)["args"]) for name in METRICS}
    assert values == pytest.approx({
        "loop_plan_wait_ms_per_epoch": 1000,
        "loop_dispatch_idle_ms_per_epoch": 1000,
        "loop_launch_idle_ms_per_epoch": 0,
        "loop_fetch_tail_idle_ms_per_epoch": 1000,
        "loop_unattributed_idle_ms_per_epoch": 3000,
        "loop_dispatch_idle_ms_max": 2000, "loop_launch_idle_ms_max": 3000,
        "loop_fetch_tail_idle_ms_max": 1000})


# -- against the profiler's files ----------------------------------------------


def profile(trace_dir, epochs=2):
    """A small CPU profile as the traced stretch writes it: the harness's
    ``bench/epoch`` around the program's tracer spans and one jitted call."""
    import jax
    import jax.numpy as jnp

    from dinunet_implementations_tpu.telemetry import PROFILER_TRACER, scopes

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        for epoch in range(1, epochs + 1):
            with jax.profiler.TraceAnnotation("bench/epoch", epoch=epoch):
                with PROFILER_TRACER.span(scopes.EPOCH_DISPATCH, epoch=epoch):
                    y = f(x)
                with PROFILER_TRACER.span(scopes.LOSS_FETCH, epoch=epoch):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout's ``bench_out`` with an OLDER cell's trace beside the one
    the run under test wrote."""
    root = tmp_path_factory.mktemp("checkout")
    profile(root / "bench_out" / "older.cell" / "trace")
    time.sleep(0.05)
    profile(root / "bench_out" / "this.cell" / "trace", epochs=3)
    return root


def test_load_reads_the_programs_spans_with_line_and_stats(checkout):
    spans = program_spans.load(str(checkout / "bench_out" / "this.cell" / "trace"))
    assert [s.name for s in spans] == ["epoch-dispatch", "loss-fetch"] * 3
    assert [s.stats["epoch"] for s in spans] == [1, 1, 2, 2, 3, 3]
    assert len({s.line for s in spans}) == 1
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
    harness = extract.load(str(checkout / "bench_out" / "this.cell" / "trace"))
    lo, hi = harness.window()  # the same clock, the same units
    assert all(lo <= s.start and s.end <= hi for s in spans)


def test_find_takes_the_newest_trace_only_if_its_epochs_are_the_runs(checkout):
    this = str(checkout / "bench_out" / "this.cell" / "trace")
    trace = extract.load(this)
    ctx = Ctx(trace, trace.window(), {})
    assert program_spans.find(ctx, root=str(checkout)) == this
    assert len(program_spans.of(ctx, root=str(checkout))) == 6
    # the older cell's run: the newest directory is not its own
    older = extract.load(str(checkout / "bench_out" / "older.cell" / "trace"))
    stale = Ctx(older, older.window(), {})
    assert program_spans.find(stale, root=str(checkout)) is None
    assert program_spans.of(stale, root=str(checkout)) == []
    # one nanosecond off in one epoch span: refused, not guessed
    first = trace.spans[0]
    moved = extract.Span(first.name, first.start, first.end + 1e-9)
    off = Ctx(Trace(trace.devices, [moved] + trace.spans[1:]), trace.window(), {})
    assert program_spans.find(off, root=str(checkout)) is None
    assert program_spans.find(Ctx(None, None, {}), root=str(checkout)) is None
    assert program_spans.find(ctx, root=str(checkout / "bench_out")) is None


def test_a_program_without_the_prefix_reads_nothing(checkout, monkeypatch):
    """The benchmark's files over the parent's program: ``scopes`` has no
    ``HOST_PREFIX``, every metric is left out and nothing raises."""
    from dinunet_implementations_tpu.telemetry import scopes

    monkeypatch.delattr(scopes, "HOST_PREFIX")
    this = str(checkout / "bench_out" / "this.cell" / "trace")
    assert program_spans.load(this) == []
    trace = extract.load(this)
    ctx = Ctx(trace, trace.window(), {})
    assert program_spans.of(ctx, root=str(checkout)) == []
    assert program_span_ms.read(ctx, span="loss-fetch", measure="idle") is None


def test_a_rehearsed_traced_cell_lists_all_eight_metrics_with_a_value():
    out = run_cell("--workload", "icalstm-hcp32.dsgd", "--seed", "5",
                   "--seconds", "1", "--trace", "1", "--rehearse", "tiny")
    assert out.returncode == 0, out.stderr[-2000:]
    left_out = next(json.loads(l)["per_layer_left_out"]
                    for l in out.stdout.splitlines()
                    if l.startswith('{"per_layer_left_out"'))
    for name in METRICS:
        assert left_out[name].startswith("cpu rehearsal"), (name, left_out[name])
        float(left_out[name].rsplit(": ", 1)[1])
    assert os.path.isdir(os.path.join(cells.ROOT, "bench_out",
                                      "icalstm-hcp32.dsgd", "trace"))
