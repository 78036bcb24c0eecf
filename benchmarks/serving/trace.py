"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The harness opens the profiler over part of the window and wraps its own
calls in ``TraceAnnotation`` spans named ``bench.<what>`` (``bench.window``
around the whole traced stretch).  From the trace this module takes:

  * device busy time: the union of the intervals in which an operation
    ran on each TPU, inside ``bench.window``, averaged over the chips;
  * per-program device time (the ``XLA Modules`` line), keyed by the
    jitted function's name;
  * per-operation device time (the ``XLA Ops`` line), keyed by the HLO
    instruction's name, so that a Pallas kernel is keyed by its kernel
    name; ops that hold other ops (a scan's ``while``) are left out of
    these sums, not out of the busy time;
  * idle gaps: stretches of the window with no operation on the device,
    each named by the ``bench.*`` span the host was in.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                               # mean over chips
    modules: dict[str, list[float]]             # program -> durations (s)
    ops: dict[str, float]                       # op or kernel -> seconds
    gaps: list[tuple[str, float]]               # (host span, seconds)
    chips: int

    def module_durations(self, fragment: str) -> list[float]:
        return [d for name, ds in self.modules.items() if fragment in name
                for d in ds]


CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def op_name(event) -> str:
    """The HLO instruction's name without its instance number: an event
    named ``%bitslice_mvm.50 = s32[32,256] custom-call(...)`` is
    ``bitslice_mvm`` (Pallas kernels keep their kernel name), one named
    ``%fusion.12 = ...`` is ``fusion``."""
    head = event.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(profile) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``."""
    spans: list[tuple[float, float, str]] = []
    window = None
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == SPAN_PREFIX + "window":
                    window = iv
                else:
                    spans.append((iv[0], iv[1], ev.name[len(SPAN_PREFIX):]))
    if window is None:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = window

    modules: dict[str, list[float]] = defaultdict(list)
    ops: dict[str, float] = defaultdict(float)
    busy_total, chips = 0.0, 0
    idle: list[tuple[float, float]] = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        chips += 1
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                if line.name == MODULES_LINE:
                    modules[re.sub(r"\(\d+\)$", "", ev.name)].append(
                        ev.duration_ns * 1e-9)
                    continue
                name = op_name(ev)
                if name not in CONTAINERS:
                    ops[name] += ev.duration_ns * 1e-9
                intervals.append((max(s, w0), min(e, w1)))
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        if chips == 1:
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if chips == 0:
        raise ValueError("trace holds no TPU plane")

    def host_span(s: float, e: float) -> str:
        best, over = "none", 0.0
        for a, b, name in spans:
            o = min(b, e) - max(a, s)
            if o > over:
                best, over = name, o
        return best

    gaps = sorted(((host_span(s, e), (e - s) * 1e-9) for s, e in idle),
                  key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / chips,
                   modules=dict(modules), ops=dict(ops), gaps=gaps,
                   chips=chips)


def describe(profile, events_per_line: int = 12) -> dict:
    """The trace's planes and lines with a few events each, stats
    included: what to read before changing the reduction."""
    out = {}
    for plane in profile.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns,
                           "stats": {k: str(v)[:200] for k, v in e.stats}}
                          for e in evs[:events_per_line]]}
        out[plane.name] = lines
    return out
