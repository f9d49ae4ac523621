"""CPU tests of the reduction from a profiler trace to the per-layer metrics.

A small trace written out by hand checks every reader against numbers worked
out on paper; the trace recorded on the chip (``bench/fixtures``) checks the
same readers against sums taken straight from its events.
"""
import pathlib
import types

import pytest

from harness import manifest, trace

FIXTURES = pathlib.Path(__file__).resolve().parents[1] / "fixtures"
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _ev(where, kind, name, start, end):
    return trace.Event(where, kind, name, start, end)


HAND = [
    _ev("host", "span", trace.WINDOW_SPAN, 0.0, 10.0),
    _ev("host", "span", "bench.batch", 0.0, 0.1),
    _ev("host", "span", "bench.step", 0.1, 1.0),
    _ev("host", "span", "bench.drain", 9.0, 10.0),
    _ev(D0, "module", "jit_round_none", 0.5, 4.5),
    _ev(D0, "module", "jit_apply", 4.5, 5.0),
    _ev(D0, "module", "jit_round_none", 5.6, 9.5),
    _ev(D0, "op", "%fusion.1", 0.5, 2.0),
    _ev(D0, "op", "%fusion.2", 1.5, 4.5),  # overlaps fusion.1: counted once in busy
    _ev(D0, "op", "%all-gather.3", 4.5, 5.0),
    _ev(D0, "op", "%fusion.1", 5.6, 9.5),
    _ev(D0, "op", "%fusion.9", 10.5, 11.0),  # after the window: left out
    _ev(D1, "module", "jit_round_none", 1.0, 1.7),
    _ev(D1, "op", "%all-gather.7", 1.0, 1.7),
]


def _ctx(events, steps=2):
    t = trace.Trace(events)
    return types.SimpleNamespace(trace=t, steps=steps, requests=[0.0, 4.0, 8.0],
                                 tokens_per_s=0.0, cell=None, device_kind="TPU v5 lite")


def _read(metric, ctx):
    return manifest.load_reader(metric)(ctx)


def test_hand_trace_reduces_to_the_paper_numbers():
    ctx = _ctx(HAND)
    # busy: TPU:0 [0.5, 5.0] + [5.6, 9.5] = 8.4 s, TPU:1 0.7 s; window 10 s
    assert ctx.trace.busy_s(D0) == pytest.approx(8.4)
    assert ctx.trace.mean_busy_s() == pytest.approx(4.55)
    assert _read("device_idle_share", ctx) == pytest.approx(54.5)
    # round program: (7.9 + 0.7) / 2 chips / 2 steps; apply: 0.5 / 2 / 2
    assert _read("round_program_ms", ctx) == pytest.approx(2150.0)
    assert _read("apply_program_ms", ctx) == pytest.approx(125.0)
    # all-gather: the chip that spends most, 0.7 s over 2 steps
    assert _read("allgather_ms", ctx) == pytest.approx(350.0)
    assert _read("host_step_ms", ctx) == pytest.approx(4000.0)
    assert ctx.trace.top_ops(3) == [["%fusion.1", pytest.approx(2.7)],
                                    ["%fusion.2", pytest.approx(1.5)],
                                    ["%all-gather.7", pytest.approx(0.35)]]
    assert ctx.trace.idle_gaps(D0) == [["window", pytest.approx(0.6)],
                                       ["step dispatch", pytest.approx(0.5)],
                                       ["drain", pytest.approx(0.5)]]


def test_readers_return_nothing_where_nothing_is_read():
    ctx = _ctx([e for e in HAND if "all-gather" not in e.name and e.name != "jit_apply"])
    assert _read("allgather_ms", ctx) is None
    assert _read("apply_program_ms", ctx) is None
    ctx.trace = None
    for metric in ("device_idle_share", "round_program_ms", "allgather_ms"):
        assert _read(metric, ctx) is None


def _union_s(intervals):
    """Seconds covered by ``intervals``, by a sweep over their edges."""
    edges = sorted([(s, 1) for s, _ in intervals] + [(t, -1) for _, t in intervals],
                   key=lambda x: (x[0], -x[1]))
    covered, depth, since = 0.0, 0, None
    for x, step in edges:
        if depth == 0 and step == 1:
            since = x
        depth += step
        if depth == 0:
            covered += x - since
    return covered


@pytest.mark.parametrize("fixture, chips", [
    # ten steps of the one-chip LAD cell (--seed 4000000007 --seconds 3 --trace 1)
    ("lad-cwtm.s512", 1),
    # the four-chip LAD cell (--seed 2147483902 --seconds 3 --trace 1), the
    # window cut at its fourth batch request to keep the file small
    ("lad-cwtm.s512x4", 4),
])
def test_recorded_trace_reduces_to_sums_of_its_events(fixture, chips):
    """Traces as ``trace.load`` read them from TPU v5e profiles."""
    events = trace.load_events(FIXTURES / f"{fixture}.events.json.gz")
    window = next(e for e in events if e.name == trace.WINDOW_SPAN)
    lo, hi = window.start, window.end

    def inside(kind, match):
        return [(max(e.start, lo), min(e.end, hi)) for e in events
                if e.kind == kind and match(e) and e.end > lo and e.start < hi]

    steps = len([e for e in events if e.name == "bench.step" and lo <= e.start <= hi])
    ctx = _ctx(events, steps=steps)
    devices = sorted({e.where for e in events if e.where != "host"})
    assert list(ctx.trace.devices) == devices and len(devices) == chips
    busy = [_union_s(inside("op", lambda e, d=d: e.where == d)) for d in devices]
    assert all(0 < b < hi - lo for b in busy)
    assert _read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - sum(busy) / chips / (hi - lo)))
    for metric, prefix in (("round_program_ms", "jit_round_"), ("apply_program_ms", "jit_apply")):
        spans = inside("module", lambda e: e.name.startswith(prefix))
        assert spans, prefix
        assert _read(metric, ctx) == pytest.approx(
            1e3 * sum(t - s for s, t in spans) / chips / steps)
    def per_chip(names):
        return [sum(t - s for s, t in inside(
            "op", lambda e, d=d: e.where == d and e.name.startswith(names))) for d in devices]

    exchange = per_chip(("%all-gather", "%async-collective-start", "%async-collective-done"))
    if chips == 1:
        assert _read("allgather_ms", ctx) is None  # one chip: no exchange
    else:
        assert min(exchange) > 0
        assert _read("allgather_ms", ctx) == pytest.approx(1e3 * max(exchange) / steps)
        # the losses' all-reduce is in the trace, and not in the exchange
        assert min(per_chip(("%all-reduce",))) > 0


def test_trace_needs_exactly_one_window():
    with pytest.raises(ValueError, match="one 'bench.window' span"):
        trace.Trace([e for e in HAND if e.name != trace.WINDOW_SPAN])
