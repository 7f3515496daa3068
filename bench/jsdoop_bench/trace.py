"""From a profiler trace of the window to the numbers the per-layer
metrics read.

The trace is the JAX profiler's ``.xplane.pb``. Its device planes
(``/device:TPU:<n>``) hold a line of XLA modules (one event per program
run) and a line of XLA ops. Its host plane holds a line per thread, with
the benchmark's spans (``bench.*``, from ``jax.profiler.TraceAnnotation``).

* busy: the union of the device's op intervals inside the traced window,
  averaged over the chips used; idle is the rest of the window;
* per layer: the device time of the programs the model family names for
  that layer (``PROGRAMS``: program name -> layer); a program it does not
  name is charged to ``OTHER``, never to a layer;
* idle gaps: the stretches of the window with no op on the device, each
  named for the host span that covered most of it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

#: the span the benchmark opens around the traced window
WINDOW_SPAN = "bench.window"
#: the layer of a device program that the model family does not name
OTHER = "other"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_TOP = 10

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: Interval, window: Interval) -> Optional[Interval]:
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, e) if e > s else None


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that ``busy`` (merged) leaves free."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class TraceSummary:
    """What one traced window says. Times in seconds."""
    window_s: float
    busy_s: float
    #: layer -> device seconds of its programs (``OTHER``: unnamed ones)
    layers: Dict[str, float] = field(default_factory=dict)
    #: (span that covered most of the gap, seconds), longest first
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    #: (program, device seconds in the window), most first
    device_ops: List[Tuple[str, float]] = field(default_factory=list)

    def layer_device_s(self, layer: str) -> float:
        return self.layers.get(layer, 0.0)

    def breakdown(self) -> Dict[str, List]:
        return {"device_ops": [[n, s] for n, s in self.device_ops[:_TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:_TOP]]}


def _module_name(name: str) -> str:
    """``jit_loss(123)`` -> ``jit_loss``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(profile, programs: Mapping[str, str]) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a ``TraceSummary``; each
    program run is charged to the layer ``programs`` names for it."""
    host_spans: List[Span] = []
    window: Optional[Interval] = None
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == WINDOW_SPAN:
                    window = (start, end)
                elif ev.name.startswith(SPAN_PREFIX):
                    host_spans.append(Span(ev.name, start, end))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")

    busy_total = 0.0
    by_program: Dict[str, float] = defaultdict(float)
    layers: Dict[str, float] = defaultdict(float)
    per_device_busy: List[List[Interval]] = []
    for plane in devices:
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name.startswith("XLA Ops"):
                for ev in line.events:
                    iv = clip((ev.start_ns, ev.start_ns + ev.duration_ns),
                              window)
                    if iv:
                        ops.append(iv)
            elif line.name.startswith("XLA Modules"):
                for ev in line.events:
                    iv = clip((ev.start_ns, ev.start_ns + ev.duration_ns),
                              window)
                    if not iv:
                        continue
                    secs = (iv[1] - iv[0]) * 1e-9
                    name = _module_name(ev.name)
                    by_program[name] += secs
                    layers[programs.get(name, OTHER)] += secs
        merged = union(ops)
        per_device_busy.append(merged)
        busy_total += sum(e - s for s, e in merged)
    if not devices:
        raise ValueError("trace has no TPU device plane")
    window_s = (window[1] - window[0]) * 1e-9
    busy_s = busy_total * 1e-9 / len(devices)

    named_gaps = [(_cover(host_spans, g), (g[1] - g[0]) * 1e-9)
                  for g in gaps(per_device_busy[0], window)]
    named_gaps.sort(key=lambda x: -x[1])
    ops_by_time = sorted(by_program.items(), key=lambda x: -x[1])
    return TraceSummary(window_s=window_s, busy_s=busy_s,
                        layers=dict(layers), idle_gaps=named_gaps,
                        device_ops=ops_by_time)


def _cover(spans: List[Span], gap: Interval) -> str:
    """The span name that covers most of ``gap`` (over all threads)."""
    cover: Dict[str, float] = defaultdict(float)
    for s in spans:
        lo, hi = max(s.start, gap[0]), min(s.end, gap[1])
        if hi > lo:
            cover[s.name] += hi - lo
    return max(cover, key=cover.get) if cover else "no span"


def reduce_file(path: str, programs: Mapping[str, str]) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), programs)


def find_trace(logdir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler`` wrote under ``logdir``."""
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one trace under {logdir}, found {found}")
    return found[0]
