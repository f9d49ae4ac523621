"""Stage scopes and step spans of the engine step, from a profiler trace.

The program names its own work (``launch/train.py``, ``core/byzantine.py``).
On the device, each stage of the round and apply programs runs under a
``jax.named_scope`` (``STAGES``), which XLA keeps in the ``op_name`` metadata
of the HLO instructions.  On the host, each step and its parts run under
``lad.*`` trace spans (``SPANS``), on the same clock as the device events.

On TPU v5e the profiler gives an op's op_name only in its event *metadata*
(stat ``tf_op``), which ``jax.profiler.ProfileData`` does not read, and not
at all for the ops the compiler made (copies, loops that split a large
update: half of the one-chip round program's time).  It also keeps each
program's compiled ``HloProto`` in the profile.  So ``load`` takes every
op's scope from there (``program_scopes``), with the ops the compiler made
given the op_name of the op whose output they read.  Ops to which the
compiler gives an op_name of its own stay under no stage (on four chips
the losses' all-reduce and the index iota of a sort, both named after the
``shard_map``).

``load`` reads what ``trace.load`` reads, and besides the ``lad.*`` host
spans and each op's scope.  ``StageTrace`` answers what ``trace.Trace``
answers, and besides: the self time of each stage, the host time inside a
span, and the longest idle gaps named by the innermost ``bench.*`` or
``lad.*`` span around them.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import json
import pathlib
import re

from harness import trace

# the stat under which the profiler keeps each program's compiled HloProto,
# on the event metadata of the ``/host:metadata`` plane, named as the program
HLO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
STAGES = ("lad.fanout", "lad.flatten", "lad.gather", "lad.encode", "lad.compress",
          "lad.attack", "lad.aggregate", "lad.unflatten", "lad.optimizer")
SPANS = ("lad.step", "lad.place", "lad.dispatch_round", "lad.dispatch_apply",
         "lad.readback")
SPAN_PREFIX = "lad."
GAP_LABELS = {**trace.GAP_LABELS, **{s: s for s in SPANS}}
# a ``lad.*`` scope as one component of an op_name path, bare or inside
# transform wrappers: ``jit(f)/vmap(lad.fanout)/transpose(jvp(lad.fanout))/dot``
_SCOPE = re.compile(r"(?:^|[/(])(lad\.[A-Za-z_]+)(?=[)/:]|$)")


@dataclasses.dataclass(frozen=True)
class Event(trace.Event):
    scope: str = ""  # an op's op_name metadata; "" for spans and programs


@functools.lru_cache(maxsize=None)
def stage_of(scope: str) -> str | None:
    """The ``lad.*`` stage an op_name names, innermost first; XLA joins the
    op_names of an instruction made from several with ``;``: the first of
    them that names a stage decides."""
    for part in scope.split(";"):
        found = _SCOPE.findall(part)
        if found:
            return found[-1]
    return None


def _fields(buf: memoryview):
    """``(field number, value)`` of one protocol buffer message: an int for a
    varint, a memoryview for a length-delimited or fixed-width value."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protocol buffer wire type {wire} at byte {i}")
        yield key >> 3, value


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _ints(value) -> list[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _message(buf: memoryview, *numbers: int) -> dict[int, list]:
    """The values of the fields ``numbers`` of one message, by number."""
    out = {n: [] for n in numbers}
    for f, v in _fields(buf):
        if f in out:
            out[f].append(v)
    return out


def _text(values: list) -> str:
    return bytes(values[0]).decode() if values else ""


def hlo_scopes(module: memoryview) -> dict[str, str]:
    """Instruction name -> op_name metadata of one compiled ``HloModuleProto``
    (``xla/service/hlo.proto``).  An instruction the compiler made without
    op_name (a copy, a layout change, a loop that splits a large update)
    takes the op_name of its nearest producer that has one, in operand order;
    a computation's parameter reads the operands of the instructions that
    call the computation (a ``while`` body's, its loop's)."""
    instrs, callers = {}, {}
    for comp in _message(module, 3)[3]:  # HloModuleProto.computations
        c = _message(comp, 2, 5)  # instructions, id
        cid = c[5][0] if c[5] else 0  # proto3 leaves a 0 out
        for raw in c[2]:
            # name, opcode, metadata, id, operand_ids, called_computation_ids
            i = _message(raw, 1, 2, 7, 35, 36, 38)
            op_name = _text(_message(i[7][0], 2)[2]) if i[7] else ""
            param = cid if _text(i[2]) == "parameter" else None
            operands = [x for v in i[36] for x in _ints(v)]
            iid = i[35][0] if i[35] else 0
            instrs[iid] = (_text(i[1]), op_name, operands, param)
            for called in (x for v in i[38] for x in _ints(v)):
                callers.setdefault(called, []).append(iid)

    def inputs(iid):
        _, _, operands, param = instrs[iid]
        if param is None:
            return operands
        return [x for caller in callers.get(param, ()) for x in instrs[caller][2]]

    resolved: dict[int, str] = {}
    for root in instrs:  # depth first, without recursion: producers first
        stack, opened = [root], set()
        while stack:
            iid = stack[-1]
            if iid in resolved or iid not in instrs:
                stack.pop()
                continue
            if instrs[iid][1]:
                resolved[iid] = instrs[iid][1]
                stack.pop()
                continue
            todo = [x for x in inputs(iid) if x not in resolved and x not in opened]
            if todo and iid not in opened:
                opened.add(iid)
                stack.extend(reversed(todo))
                continue
            resolved[iid] = next((resolved[x] for x in inputs(iid) if resolved.get(x)), "")
            stack.pop()
    return {instrs[iid][0]: scope for iid, scope in resolved.items()}


def program_scopes(path: str | pathlib.Path) -> dict[str, dict[str, str]]:
    """Program name (as its ``XLA Modules`` events name it) -> ``hlo_scopes``
    of its compiled module, from the profile's ``METADATA_PLANE``."""
    out = {}
    space = memoryview(pathlib.Path(path).read_bytes())
    for plane in _message(space, 1)[1]:  # XSpace.planes
        p = _message(plane, 2, 4, 5)  # name, event_metadata, stat_metadata
        if _text(p[2]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in p[5]:  # map entry {1: key, 2: XStatMetadata {1: id, 2: name}}
            meta = _message(_message(entry, 2)[2][0], 1, 2)
            stat_names[meta[1][0] if meta[1] else 0] = _text(meta[2])
        for entry in p[4]:  # map entry {2: XEventMetadata {2: name, 5: stats}}
            meta = _message(_message(entry, 2)[2][0], 2, 5)
            for stat in meta[5]:  # XStat {1: metadata id, 6: bytes}
                st = _message(stat, 1, 6)
                if st[6] and stat_names.get(st[1][0] if st[1] else 0) == HLO_STAT:
                    module = _message(st[6][0], 1)[1]  # HloProto.hlo_module
                    if module:
                        out[_text(meta[2])] = hlo_scopes(module[0])
    return out


def load(path: str | pathlib.Path) -> list[Event]:
    """Events of one ``.xplane.pb`` file: those ``trace.load`` reads, each op
    with its scope (the op_name of its instruction in the program it ran
    in, ``program_scopes``), and the ``lad.*`` host spans."""
    from jax.profiler import ProfileData

    scopes = program_scopes(path)
    base = trace.load(path)
    programs = {}  # device -> its program events, by start
    for e in base:
        if e.kind == "module":
            programs.setdefault(e.where, []).append(e)
    for runs in programs.values():
        runs.sort(key=lambda e: e.start)
    starts = {d: [e.start for e in runs] for d, runs in programs.items()}

    def scope(e: trace.Event) -> str:
        at = bisect.bisect_right(starts.get(e.where, []), e.start) - 1
        if e.kind != "op" or at < 0 or e.start >= programs[e.where][at].end:
            return ""
        return scopes.get(programs[e.where][at].name, {}).get(e.name.lstrip("%"), "")

    events = [Event(*dataclasses.astuple(e), scope(e)) for e in base]
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            events += [Event("host", "span", e.name, e.start_ns * 1e-9,
                             e.start_ns * 1e-9 + e.duration_ns * 1e-9)
                       for line in plane.lines for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
    return events


def save_events(events: list[Event], path: str | pathlib.Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str | pathlib.Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def cut(events: list[Event], steps: int) -> list[Event]:
    """The events of the window's first ``steps`` steps: the window ends at
    the batch request after them, as if the run had stopped there."""
    window = next(e for e in events if e.name == trace.WINDOW_SPAN)
    batches = sorted(e.start for e in events if e.name == "bench.batch"
                     and window.start <= e.start <= window.end)
    end = batches[steps] if steps < len(batches) else window.end
    kept = [e for e in events if e is not window and e.start < end and e.end > window.start]
    return [dataclasses.replace(window, end=end)] + kept


class StageTrace(trace.Trace):
    """A ``trace.Trace`` whose op events carry their scope, and whose idle
    gaps are named by the ``lad.*`` span the host was in."""

    gap_labels = GAP_LABELS

    def __init__(self, events: list[Event]):
        super().__init__(events)
        self._pieces: dict = {}
        self._stages: dict = {}

    def _self_time(self, device: str):
        """``(start, end, op)`` pieces of the busy time on ``device``: every
        instant goes to the innermost op that covers it, the one that started
        last (on TPU a ``while`` op spans the ops of its body, and
        asynchronous copies overlap the ops around them)."""
        if device not in self._pieces:
            ops = sorted(((s, t, e) for e, s, t in self._of(device, "op")),
                         key=lambda o: o[0])
            edges = sorted({x for s, t, _ in ops for x in (s, t)})
            pieces, active, i = [], [], 0
            for lo, hi in zip(edges, edges[1:]):
                while i < len(ops) and ops[i][0] <= lo:
                    active.append(ops[i])
                    i += 1
                active = [o for o in active if o[1] > lo]
                if active:
                    inner = max(active, key=lambda o: (o[0], -o[1]))
                    pieces.append((lo, hi, inner[2]))
            self._pieces[device] = pieces
        return self._pieces[device]

    def stage_seconds(self, device: str, module: str | None = None) -> dict:
        """Self time on ``device`` by stage (``None``: ops under no stage),
        inside programs whose name matches ``module`` (``re.match``) if
        given.  Over all programs the stages and ``None`` partition
        ``busy_s``."""
        if (device, module) not in self._stages:
            pieces = self._self_time(device)
            if module is not None:
                rx = re.compile(module)
                pieces = _inside(pieces, trace.merge(
                    (s, t) for e, s, t in self._of(device, "module") if rx.match(e.name)))
            out: dict = {}
            for lo, hi, op in pieces:
                stage = stage_of(op.scope)
                out[stage] = out.get(stage, 0.0) + hi - lo
            self._stages[device, module] = out
        return self._stages[device, module]

    def scope_s(self, device: str, stage: str | None, module: str | None = None) -> float:
        """Seconds of ``stage_seconds`` under ``stage``."""
        return self.stage_seconds(device, module).get(stage, 0.0)

    def mean_scope_s(self, stage: str | None, module: str | None = None) -> float:
        """``scope_s`` averaged over the devices."""
        return (sum(self.scope_s(d, stage, module) for d in self.devices)
                / max(len(self.devices), 1))

    def span_s(self, name: str) -> float:
        """Host seconds inside ``name`` spans (their union) in the window."""
        return sum(t - s for s, t in trace.merge(
            (max(e.start, self.start), min(e.end, self.end)) for e in self.spans
            if e.name == name and e.end > self.start and e.start < self.end))


def _inside(pieces, spans):
    """The parts of time-ordered ``(lo, hi, op)`` pieces inside sorted
    disjoint ``spans``."""
    out, j = [], 0
    for lo, hi, op in pieces:
        while j < len(spans) and spans[j][1] <= lo:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < hi:
            a, b = max(lo, spans[k][0]), min(hi, spans[k][1])
            if b > a:
                out.append((a, b, op))
            k += 1
    return out


def stage_ms(ctx, stage: str) -> float | None:
    """A reader's value: ``stage``'s self time per step, mean over chips;
    nothing from a trace without scopes."""
    t = ctx.trace
    if not isinstance(t, StageTrace) or not t.devices or ctx.steps == 0:
        return None
    seconds = t.mean_scope_s(stage)
    return 1e3 * seconds / ctx.steps if seconds > 0 else None


def span_ms(ctx, name: str) -> float | None:
    """A reader's value: host time per step inside ``name`` spans."""
    t = ctx.trace
    if not isinstance(t, StageTrace) or ctx.steps == 0:
        return None
    seconds = t.span_s(name)
    return 1e3 * seconds / ctx.steps if seconds > 0 else None
