"""CPU tests of the reduction by the program's stage scopes and step spans.

A profile written by hand checks what ``stages.load`` reads from an
``.xplane.pb``; a small trace written by hand checks ``StageTrace`` and the
readers against numbers worked out on paper; the traces recorded on the chip
(``bench/fixtures/*.stages.events.json.gz``) check the same readers against
sums taken straight from their events.
"""
import importlib.util
import pathlib
import types

import pytest

import tiny
from harness import cell as cell_lib
from harness import device, manifest, stages, trace
from test_harness_trace import _union_s

BENCH = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = BENCH / "fixtures"
D0, D1 = "/device:TPU:0", "/device:TPU:1"
READERS = ("fanout_ms", "flatten_ms", "encode_ms", "aggregate_ms", "place_ms")


def _row(e):
    """An event's fields as ``trace.Event`` has them (without the scope)."""
    return (e.where, e.kind, e.name, e.start, e.end)


@pytest.mark.parametrize("scope, stage", [
    ("jit(round_none)/vmap(lad.fanout)/dot_general", "lad.fanout"),
    ("jit(round_none)/vmap(lad.fanout)/transpose(jvp(lad.fanout))/mul", "lad.fanout"),
    ("jit(round_shard_map)/shard_map/lad.gather/all_gather", "lad.gather"),
    ("jit(apply)/lad.unflatten/reshape:", "lad.unflatten"),
    # XLA joins the op_names of an instruction made from several
    ("jit(f)/sub;jit(f)/lad.aggregate/sort;jit(f)/lad.attack/mul", "lad.aggregate"),
    ("jit(round_none)/sub", None),
    ("params['embed']['table']", None),
    ("jit(f)/blad.fanout/add", None),  # not a path component
    ("", None),
])
def test_stage_of_finds_the_scope_as_a_path_component(scope, stage):
    assert stages.stage_of(scope) == stage


# ---------------------------------------------------------------- load

_HLO = """
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

body {
  p = (s32[], f32[8]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  one = s32[] constant(1)
  i1 = s32[] add(i, one)
  x = f32[8] get-tuple-element(p), index=1
  y = f32[8] multiply(x, x)
  ROOT next = (s32[], f32[8]) tuple(i1, y)
}

cond {
  q = (s32[], f32[8]) parameter(0)
  j = s32[] get-tuple-element(q), index=0
  n = s32[] constant(3)
  ROOT lt = pred[] compare(j, n), direction=LT
}

ENTRY main {
  a = f32[8] parameter(0)
  b = f32[8] sine(a), metadata={op_name="jit(f)/vmap(lad.flatten)/sin"}
  z = s32[] constant(0)
  t = (s32[], f32[8]) tuple(z, b)
  w = (s32[], f32[8]) while(t), condition=cond, body=body
  r = f32[8] get-tuple-element(w), index=1
  ROOT c = f32[8] cosine(r), metadata={op_name="jit(f)/transpose(jvp(lad.aggregate))/cos"}
}
"""


def _module_proto() -> bytes:
    from jax._src.lib import xla_client

    return xla_client._xla.hlo_module_from_text(_HLO).as_serialized_hlo_module_proto()


def test_instructions_without_op_name_take_their_producers():
    """The loop the compiler made has no op_name: it, its body and the
    instructions that read it take ``b``'s, through the loop's operand."""
    got = stages.hlo_scopes(memoryview(_module_proto()))
    flatten, aggregate = "jit(f)/vmap(lad.flatten)/sin", "jit(f)/transpose(jvp(lad.aggregate))/cos"
    assert got["c"] == aggregate
    for name in ("b", "t", "w", "r", "p", "x", "y", "next", "q", "lt"):
        assert got[name] == flatten, name
    for name in ("a", "z", "one", "n"):  # nothing with an op_name before them
        assert got[name] == "", name


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _xspace_text() -> str:
    module = _module_proto()
    hlo_proto = b"\x0a" + _varint(len(module)) + module  # HloProto.hlo_module
    escaped = "".join(f"\\{b:03o}" for b in hlo_proto)

    def events(*rows):
        return " ".join(f"events {{ metadata_id: {m} offset_ps: {o} duration_ps: {d} }}"
                        for m, o, d in rows)

    def metadata(*names):
        return " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                        for k, n in enumerate(names, 1))

    return f"""
planes {{ id: 1 name: "{stages.METADATA_PLANE}"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_f(42)"
    stats {{ metadata_id: 7 bytes_value: "{escaped}" }} }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "{stages.HLO_STAT}" }} }} }}
planes {{ id: 2 name: "{D0}"
  lines {{ id: 1 name: "{trace.MODULE_LINE}" timestamp_ns: 1000 {events((1, 0, 9000000))} }}
  lines {{ id: 2 name: "{trace.OP_LINE}" timestamp_ns: 1000
    {events((2, 0, 1000000), (3, 1000000, 6000000), (4, 2000000, 1000000),
            (5, 7000000, 2000000), (6, 9500000, 100000))} }}
  lines {{ id: 3 name: "Steps" timestamp_ns: 1000 {events((1, 0, 9000000))} }}
  {metadata("jit_f(42)", "%b = f32[8]{{0}} sine(f32[8]{{0}} %a)",
            "%w = (s32[], f32[8]{{0}}) while(%t), condition=%cond, body=%body",
            "%y = f32[8]{{0}} multiply(%x, %x)", "%c = f32[8]{{0}} cosine(%r)",
            "%b = f32[8]{{0}} sine(f32[8]{{0}} %a)")} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {events((1, 0, 20000000), (2, 500000, 3000000), (3, 600000, 1000000),
            (4, 700000, 100000))} }}
  {metadata(trace.WINDOW_SPAN, "lad.step", "lad.place", "PjitFunction(round)")} }}
"""


def test_load_reads_scopes_programs_and_program_spans(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace_text()))
    events = stages.load(path)
    ops = {(e.name, round(e.start * 1e9)): e.scope for e in events if e.kind == "op"}
    flatten, aggregate = "jit(f)/vmap(lad.flatten)/sin", "jit(f)/transpose(jvp(lad.aggregate))/cos"
    assert ops == {("%b", 1000): flatten, ("%w", 2000): flatten, ("%y", 3000): flatten,
                   ("%c", 8000): aggregate,
                   ("%b", 10500): ""}  # outside any program: no scope
    assert [e.name for e in events if e.kind == "span"] == [
        trace.WINDOW_SPAN, "lad.step", "lad.place"]
    # what trace.load reads, unchanged
    assert [trace.Event(*_row(e)) for e in events
            if not e.name.startswith(stages.SPAN_PREFIX)] == trace.load(path)


# ---------------------------------------------------------------- hand trace

def _ev(where, kind, name, start, end, scope=""):
    return stages.Event(where, kind, name, start, end, scope)


HAND = [
    _ev("host", "span", trace.WINDOW_SPAN, 0.0, 10.0),
    _ev("host", "span", "bench.batch", 0.0, 0.1),
    _ev("host", "span", "bench.step", 0.1, 9.0),
    _ev("host", "span", "lad.step", 0.2, 8.9),
    _ev("host", "span", "lad.place", 0.2, 0.5),
    _ev("host", "span", "lad.place", 1.0, 1.2),
    _ev("host", "span", "lad.place", 1.1, 1.3),  # overlaps the one before: counted once
    _ev("host", "span", "lad.dispatch_round", 1.3, 5.0),
    _ev("host", "span", "lad.dispatch_apply", 5.0, 8.0),
    _ev("host", "span", "lad.readback", 8.5, 8.9),
    _ev("host", "span", "bench.drain", 9.0, 10.0),
    _ev(D0, "module", "jit_round_none(1)", 0.5, 6.0),
    _ev(D0, "module", "jit_apply(2)", 6.5, 8.0),
    _ev(D0, "op", "%fusion.1", 0.5, 1.5, "jit(round_none)/vmap(lad.fanout)/dot_general"),
    # a while op and the two ops of its body: the body's time is theirs
    _ev(D0, "op", "%while.2", 1.5, 3.5, "jit(round_none)/vmap(lad.flatten)/concatenate"),
    _ev(D0, "op", "%dynamic-update-slice.3", 2.0, 2.5,
        "jit(round_none)/vmap(lad.flatten)/transpose(jvp(lad.flatten))/pad"),
    _ev(D0, "op", "%copy.4", 3.0, 3.5, ""),
    _ev(D0, "op", "%sort.5", 3.5, 5.0, "jit(round_none)/lad.aggregate/sort"),
    _ev(D0, "op", "%fusion.6", 5.0, 6.0, "jit(round_none)/sub"),
    _ev(D0, "op", "%fusion.7", 6.5, 7.0, "jit(apply)/lad.unflatten/reshape"),
    _ev(D0, "op", "%fusion.8", 7.0, 8.0, "jit(apply)/lad.optimizer/mul;jit(apply)/lad.unflatten/add"),
    _ev(D0, "op", "%fusion.9", 10.5, 11.0, "jit(round_none)/vmap(lad.fanout)/add"),  # after the window
    _ev(D1, "module", "jit_round_none(1)", 0.5, 2.5),
    _ev(D1, "op", "%fusion.1", 0.5, 2.5, "jit(round_none)/vmap(lad.fanout)/dot_general"),
]


def _ctx(events, steps=2, kind=stages.StageTrace):
    return types.SimpleNamespace(trace=kind(events), steps=steps, requests=[0.0, 4.0, 8.0],
                                 tokens_per_s=0.0, cell=None, device_kind="TPU v5 lite")


def _read(metric, ctx):
    return manifest.load_reader(metric)(ctx)


def test_hand_trace_reduces_to_the_paper_numbers():
    ctx = _ctx(HAND)
    t = ctx.trace
    # TPU:0 busy [0.5, 6.0] + [6.5, 8.0] = 7.0 s; self time by stage:
    # fanout 1.0; flatten 2.0 - 0.5 - 0.5 (while) + 0.5 (its update) = 1.5;
    # aggregate 1.5; unflatten 0.5; optimizer 1.0 (the first op_name decides);
    # no stage: the copy in the loop 0.5 + fusion.6 1.0 = 1.5
    want = {"lad.fanout": 1.0, "lad.flatten": 1.5, "lad.aggregate": 1.5,
            "lad.unflatten": 0.5, "lad.optimizer": 1.0, None: 1.5}
    assert t.stage_seconds(D0) == pytest.approx(want)
    assert sum(t.stage_seconds(D0).values()) == pytest.approx(t.busy_s(D0)) == 7.0
    assert sum(t.stage_seconds(D1).values()) == pytest.approx(t.busy_s(D1)) == 2.0
    # inside the round program: 5.5 s of ops, 1.5 s of them under no stage
    assert t.scope_s(D0, None, r"jit_round_") == pytest.approx(1.5)
    assert sum(t.stage_seconds(D0, r"jit_round_").values()) == pytest.approx(5.5)
    # per step (2), mean over the two chips; TPU:1 spends 2.0 s in the fan-out
    assert _read("fanout_ms", ctx) == pytest.approx(1e3 * (1.0 + 2.0) / 2 / 2)
    assert _read("flatten_ms", ctx) == pytest.approx(1e3 * 1.5 / 2 / 2)
    assert _read("aggregate_ms", ctx) == pytest.approx(1e3 * 1.5 / 2 / 2)
    assert _read("encode_ms", ctx) is None  # no op under lad.encode
    # lad.place: [0.2, 0.5] + [1.0, 1.3] = 0.6 s over 2 steps
    assert _read("place_ms", ctx) == pytest.approx(300.0)
    assert t.span_s("lad.dispatch_round") == pytest.approx(3.7)
    # gaps of TPU:0: [8, 10] in the drain; [0, 0.5] while the inputs were
    # placed; [6, 6.5] in the apply dispatch, where trace.Trace sees only
    # the benchmark's step span
    assert t.idle_gaps(D0) == [["drain", pytest.approx(2.0)],
                               ["lad.place", pytest.approx(0.5)],
                               ["lad.dispatch_apply", pytest.approx(0.5)]]
    assert trace.Trace(HAND).idle_gaps(D0) == [["drain", pytest.approx(2.0)],
                                               ["step dispatch", pytest.approx(0.5)],
                                               ["step dispatch", pytest.approx(0.5)]]


def test_readers_return_nothing_without_scopes_or_spans():
    plain = [trace.Event(*_row(e)) for e in HAND]
    for metric in READERS:
        assert _read(metric, _ctx(plain, kind=trace.Trace)) is None
        assert _read(metric, types.SimpleNamespace(trace=None, steps=2)) is None
    unscoped = [_ev(*_row(e)) for e in HAND if not e.name.startswith("lad.")]
    for metric in READERS:
        assert _read(metric, _ctx(unscoped)) is None


def test_cut_keeps_the_first_steps():
    cut = stages.cut(HAND + [_ev("host", "span", "bench.batch", 9.0, 9.05)], 1)
    window = next(e for e in cut if e.name == trace.WINDOW_SPAN)
    assert (window.start, window.end) == (0.0, 9.0)
    assert all(e.start < 9.0 for e in cut) and len(cut) == len(HAND) - 2


# ---------------------------------------------------------------- recorded

def _self_times(ops):
    """Each op's self time: its length less the union of the ops that started
    after it (of two that started together, the shorter) and overlap it.  On
    TPU a ``while`` op spans the ops of its body, and asynchronous copies and
    slices overlap the ops around them."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, (s, t, scope) in enumerate(ops):
        later = []
        for s2, t2, _ in ops[i + 1:]:
            if s2 >= t:
                break
            later.append((s2, min(t2, t)))
        out.append((s, t, scope, t - s - _union_s(later)))
    return out


@pytest.mark.parametrize("fixture, chips, unscoped", [
    # ten steps of the one-chip plain cell (--seed 3000000019 --seconds 3)
    ("plain.s512", 1, 0.05),
    # the first three steps of the four-chip LAD cell's window (--seed
    # 2147480021 --seconds 20).  6.8 % of its round program is under no
    # stage: the losses' all-reduce (8.9 ms a step) and the index iota of
    # CWTM's sort (6.0 ms), to which the compiler gives the op_name of the
    # shard_map itself
    ("lad-cwtm.s512x4", 4, 0.07),
])
def test_recorded_trace_reduces_to_sums_of_its_events(fixture, chips, unscoped):
    """Traces as ``stages.load`` read them from TPU v5e profiles."""
    events = stages.load_events(FIXTURES / f"{fixture}.stages.events.json.gz")
    window = next(e for e in events if e.name == trace.WINDOW_SPAN)
    lo, hi = window.start, window.end
    steps = len([e for e in events if e.name == "bench.step" and lo <= e.start <= hi])
    ctx = _ctx(events, steps=steps)
    devices = sorted({e.where for e in events if e.where != "host"})
    assert list(ctx.trace.devices) == devices and len(devices) == chips

    by_stage, round_share = {}, []
    for d in devices:
        ops = [(max(e.start, lo), min(e.end, hi), e.scope) for e in events
               if e.where == d and e.kind == "op" and e.end > lo and e.start < hi]
        selfs = _self_times(ops)
        assert sum(x[3] for x in selfs) == pytest.approx(_union_s([o[:2] for o in ops]))
        for _, _, scope, secs in selfs:
            stage = stages.stage_of(scope)
            by_stage[stage] = by_stage.get(stage, 0.0) + secs / chips
        # the round program's ops: those that start inside a jit_round_ module
        rounds = [(e.start, e.end) for e in events if e.where == d and e.kind == "module"
                  and e.name.startswith("jit_round_")]
        inside = [x for x in selfs if any(s <= x[0] < t for s, t in rounds)]
        none_s = sum(x[3] for x in inside if stages.stage_of(x[2]) is None)
        round_share.append(none_s / sum(x[3] for x in inside))
    for metric in ("fanout_ms", "flatten_ms", "aggregate_ms"):
        assert _read(metric, ctx) == pytest.approx(
            1e3 * by_stage["lad." + metric[:-3]] / steps, rel=1e-9)
    encode = by_stage.get("lad.encode", 0.0)
    assert _read("encode_ms", ctx) == (
        pytest.approx(1e3 * encode / steps, rel=1e-9) if encode else None)
    # ops under no stage: their share of the round program's op time, every chip
    assert max(round_share) < unscoped, round_share
    places = [(max(e.start, lo), min(e.end, hi)) for e in events
              if e.name == "lad.place" and e.end > lo and e.start < hi]
    assert _read("place_ms", ctx) == pytest.approx(1e3 * _union_s(places) / steps)
    # every long idle gap is named by the program's step part or the window
    for label, _ in ctx.trace.idle_gaps(devices[0]):
        assert label.startswith(stages.SPAN_PREFIX) or label in ("window", "drain"), label


# ---------------------------------------------------------------- the tool

def test_stages_tool_reads_the_window_it_runs(tmp_path, monkeypatch):
    """``bench/stages.py`` runs the cell through ``cell.run`` and reads the
    same profile again: one ``lad.step`` per step of the window, each with
    the spans of its parts."""
    spec = importlib.util.spec_from_file_location("bench_stages_tool", BENCH / "stages.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    root = tiny.make_root(tmp_path)
    monkeypatch.setitem(device.PEAKS, "cpu", device.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(cell_lib, "enable_cache", lambda root: None)
    result, events = tool.run("tiny.lad", 3, 0.3, root=root, check_device=False)
    assert result["correct"] is True and result["attempted"] > 0
    t = stages.StageTrace(events)
    steps = [e for e in t.spans if e.name == "lad.step" and t.start <= e.start <= t.end]
    assert len(steps) == result["attempted"]
    out = tool.summary(t, result["attempted"], 512)
    assert all(out["span_ms"][s] > 0 for s in stages.SPANS)
    assert out["span_ms"]["lad.step"] >= out["span_ms"]["lad.dispatch_round"]
