"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``Event`` tuples: the benchmark's own host spans (names starting ``bench.``)
and, per device, its program (``XLA Modules``) and operation (``XLA Ops``,
named by their HLO instruction) events.  ``Trace`` then answers in seconds, inside the traced window only:
busy time (the union of operation intervals), time per program, time in
operations whose name matches, the operations that took most time, and the
longest idle gaps labelled with the host span they fell in.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import pathlib
import re

HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# what the host was doing, by the innermost span around a moment
GAP_LABELS = {"bench.batch": "batch", "bench.step": "step dispatch",
              "bench.drain": "drain", WINDOW_SPAN: "window"}


@dataclasses.dataclass(frozen=True)
class Event:
    where: str  # "host" or the device's plane name
    kind: str  # "span", "module" or "op"
    name: str
    start: float  # seconds on the trace's clock
    end: float


def load(path: str | pathlib.Path) -> list[Event]:
    """Events of one ``.xplane.pb`` file (needs only JAX)."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if on_device and line.name in (MODULE_LINE, OP_LINE):
                kind = "module" if line.name == MODULE_LINE else "op"
                where = plane.name
            elif not on_device and plane.name.startswith("/host:"):
                kind, where = "span", "host"
            else:
                continue
            for e in line.events:
                if kind == "span" and not e.name.startswith(HOST_PREFIX):
                    continue
                start = e.start_ns * 1e-9
                # an op event's name is its HLO text: keep the instruction's name
                name = e.name.split(" = ", 1)[0] if kind == "op" else e.name
                events.append(Event(where, kind, name, start, start + e.duration_ns * 1e-9))
    return events


def save_events(events: list[Event], path: str | pathlib.Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str | pathlib.Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, lo, hi):
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield e, s, t


class Trace:
    """The events of one traced window."""

    gap_labels = GAP_LABELS  # the host spans that name an idle gap

    def __init__(self, events: list[Event]):
        windows = [e for e in events if e.where == "host" and e.name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(windows)}")
        self.start, self.end = windows[0].start, windows[0].end
        self.spans = [e for e in events if e.where == "host"]
        by_device = collections.defaultdict(list)
        for e in events:
            if e.where != "host":
                by_device[e.where].append(e)
        self.devices = {d: by_device[d] for d in sorted(by_device)}

    @property
    def window_s(self) -> float:
        return self.end - self.start

    def _of(self, device: str, kind: str):
        return _clip((e for e in self.devices[device] if e.kind == kind),
                     self.start, self.end)

    def busy_s(self, device: str) -> float:
        return sum(t - s for s, t in merge((s, t) for _, s, t in self._of(device, "op")))

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        busy = [self.busy_s(d) for d in self.devices]
        return sum(busy) / len(busy) if busy else 0.0

    def module_s(self, device: str, pattern: str) -> float:
        """Seconds of programs whose name matches ``pattern`` (``re.match``)."""
        rx = re.compile(pattern)
        return sum(t - s for e, s, t in self._of(device, "module") if rx.match(e.name))

    def mean_module_s(self, pattern: str) -> float:
        """``module_s`` averaged over the devices."""
        return sum(self.module_s(d, pattern) for d in self.devices) / max(len(self.devices), 1)

    def op_s(self, device: str, pattern: str) -> float:
        """Seconds of operations whose name matches ``pattern`` (``re.search``)."""
        rx = re.compile(pattern)
        return sum(t - s for e, s, t in self._of(device, "op") if rx.search(e.name))

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operation names with most seconds, averaged over devices."""
        total: dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            for e, s, t in self._of(d, "op"):
                total[e.name] += (t - s) / len(self.devices)
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, device: str, n: int = 10) -> list[list]:
        """The ``n`` longest gaps between operations on ``device`` inside the
        window, each named by the innermost host span around its midpoint
        that ``gap_labels`` names."""
        busy = merge((s, t) for _, s, t in self._of(device, "op"))
        edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        named = []
        for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (s + t)
            around = [e for e in self.spans
                      if e.start <= mid <= e.end and e.name in self.gap_labels]
            inner = min(around, key=lambda e: e.end - e.start, default=None)
            named.append([self.gap_labels[inner.name] if inner else "outside", t - s])
        return named
