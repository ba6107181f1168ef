"""Reduction of a profiler trace to device busy time, per-name device time
and idle gaps labelled by the host span they fall in.

A trace is read with ``jax.profiler.ProfileData`` into plain tuples, so the
arithmetic below is independent of the trace format and is tested on a
trace recorded with the CPU profiler. Device events are those of the "XLA
Ops" line of each ``/device:TPU:<n>`` plane; host spans are the harness's
own ``TraceAnnotation``s (names starting ``bench.``) on the host plane.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile

import jax

Event = tuple[str, int, int]        # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: list[list[Event]]      # per device: its operations
    modules: list[list[Event]]      # per device: its program executions
    spans: list[Event]              # host spans of the harness
    lo: int                         # the traced window, in the same clock
    hi: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


def op_name(name: str) -> str:
    """The operation's own name: a TPU trace names an "XLA Ops" event by its
    whole HLO instruction ("%condensed_matmul.59 = bf16[8,2048] custom-call(
    ...)"); keep the instruction's name ("condensed_matmul.59")."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _events(line) -> list[Event]:
    names: dict[str, str] = {}
    out = []
    for e in line.events:
        raw = e.name
        name = names.get(raw)
        if name is None:
            name = names[raw] = op_name(raw)
        start = int(e.start_ns)
        out.append((name, start, start + int(e.duration_ns)))
    return out


def load(trace_dir: str, *, device_plane: str = "/device:TPU:",
         ops_line: str = "XLA Ops", modules_line: str = "XLA Modules",
         span_prefix: str = "bench.") -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: operations from
    the first line whose name starts with ``ops_line`` on each plane whose
    name starts with ``device_plane``, program executions likewise, and
    host spans (names starting ``span_prefix``) from any line. The window
    is the ``bench.window`` span, else the first to the last span."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, modules, spans = [], [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith(device_plane):
            ops = [l for l in lines if l.name.startswith(ops_line)]
            mods = [l for l in lines if l.name.startswith(modules_line)]
            if ops:
                devices.append(_events(ops[0]))
                modules.append(_events(mods[0]) if mods else [])
        for line in lines:
            spans += [e for e in _events(line)
                      if e[0].startswith(span_prefix)]
    return from_events(devices, modules, spans)


def from_events(devices, modules, spans) -> Trace:
    window = [s for s in spans if s[0] == "bench.window"]
    if window:
        lo, hi = window[0][1], window[0][2]
    elif spans:
        lo, hi = min(s[1] for s in spans), max(s[2] for s in spans)
    else:
        allev = [e for d in devices for e in d]
        lo, hi = min(e[1] for e in allev), max(e[2] for e in allev)
    return Trace(devices=devices, modules=modules, spans=spans, lo=lo, hi=hi)


def clip(events: list[Event], lo: int, hi: int) -> list[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: list[Event]) -> list[tuple[int, int]]:
    """Merged [start, end) intervals covered by any event."""
    merged: list[list[int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(trace: Trace) -> float:
    """Seconds in the window in which an operation ran, averaged over the
    devices."""
    if not trace.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in union(clip(d, trace.lo, trace.hi)))
              for d in trace.devices)
    return tot / len(trace.devices) / 1e9


def leaves(events: list[Event]) -> list[Event]:
    """Events that hold no other event: a loop's operation spans the
    operations of its body, which would otherwise count twice."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    holds = [False] * len(order)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(order, holds) if not h]


def time_by_name(events: list[Event], lo: int, hi: int) -> dict[str, float]:
    """Seconds of device time per event name inside [lo, hi), over the
    events that hold no other."""
    out: dict[str, float] = {}
    for n, s, e in clip(leaves(events), lo, hi):
        out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def matching_seconds(events: list[Event], lo: int, hi: int, pred) -> float:
    return sum(t for n, t in time_by_name(events, lo, hi).items() if pred(n))


def count_matching(events: list[Event], lo: int, hi: int, pred) -> int:
    return sum(1 for n, _, _ in clip(leaves(events), lo, hi) if pred(n))


def idle_gaps(trace: Trace, device: int = 0,
              top: int = 10) -> list[tuple[str, float]]:
    """The ``top`` longest gaps between busy intervals of one device inside
    the window, longest first, each labelled by the innermost harness span
    that holds the gap's midpoint ("none" when no span does)."""
    busy = union(clip(trace.devices[device], trace.lo, trace.hi))
    edges = [trace.lo] + [x for iv in busy for x in iv] + [trace.hi]
    gaps = sorted(((e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                   if e > s), key=lambda g: (-g[0], g[1]))[:top]
    out = []
    for length, s, e in gaps:
        mid = (s + e) // 2
        holding = [sp for sp in trace.spans
                   if sp[1] <= mid < sp[2] and sp[0] != "bench.window"]
        label = (min(holding, key=lambda sp: sp[2] - sp[1])[0]
                 if holding else "none")
        out.append((label, length / 1e9))
    return out


def kind(name: str) -> str:
    """An operation's name without its instance number
    ("condensed_matmul.59" -> "condensed_matmul")."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, by kind (summed over the
    devices and divided by their count), and the longest idle gaps of
    device 0."""
    ops: dict[str, float] = {}
    for d in trace.devices:
        for n, t in time_by_name(d, trace.lo, trace.hi).items():
            ops[kind(n)] = ops.get(kind(n), 0.0) + t / len(trace.devices)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace, top=top) if trace.devices else []
    return {"device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": [[n, t] for n, t in gaps]}


class Tracer:
    """Profiler trace of (a part of) the window, with the harness's host
    spans; ``trace`` holds the reduced trace once stopped."""

    def __init__(self, on: bool):
        self.on = on
        self.running = False
        self.trace = None
        self.dir = None

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if self.on:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
            self.running = True

    def stop(self):
        if not self.running:
            return
        self.running = False
        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        """Read the stopped trace (after the window: reading takes time)."""
        if self.dir is None:
            return None
        try:
            self.trace = load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
        return self.trace
