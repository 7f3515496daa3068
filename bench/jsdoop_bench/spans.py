"""The program's own spans (``repro.obs``), as the per-layer metrics and
the traced breakdown read them.

Two sources, one per clock:

* ``run.spans``: what ``repro.obs.take()`` returned for a run, one table
  per span name, timed by ``time.perf_counter_ns()``, the clock of the
  benchmark's window. The metric readers take the spans begun in the
  window. A run of a program without ``repro.obs`` has none, and every
  reader then returns ``None``.
* the profile's host plane, where each span also lands as a
  ``TraceAnnotation`` on the clock of the device planes:
  ``idle_by_program_span`` charges each idle stretch of the device to the
  span the program was working in.

A span's self time is its duration less its children's.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from jsdoop_bench import trace as tracemod
from jsdoop_bench.stats import percentile

PREFIX = "repro."
#: spans whose own time is a wait, not work: the dispatch lock's wait, and
#: a client's call, which waits for the gateway's reply between its codecs
WAITS = frozenset({"repro.lock_wait", "repro.call"})
_TOP = 10


def of(run) -> Optional[Mapping]:
    """The run's span tables, or None where the program recorded none."""
    return getattr(run, "spans", None)


def window_ns(run) -> Tuple[int, int]:
    return int(round(run.window[0] * 1e9)), int(round(run.window[1] * 1e9))


def begun(table, window: Tuple[int, int]) -> np.ndarray:
    """Mask of the spans of ``table`` that began in ``window``."""
    return (table.start_ns >= window[0]) & (table.start_ns < window[1])


def durations_ms(spans: Mapping, name: str, window: Tuple[int, int],
                 **attrs: str) -> np.ndarray:
    """Durations (ms) of the spans ``name`` begun in ``window`` whose
    attributes equal ``attrs``."""
    t = spans.get(name)
    if t is None:
        return np.zeros(0)
    keep = begun(t, window)
    for k, v in attrs.items():
        keep &= t.attrs[k] == v
    return t.duration_ns[keep] / 1e6


def clipped_s(spans: Mapping, name: str, window: Tuple[int, int]) -> float:
    """Seconds of ``window`` that the spans ``name`` covered, each clipped
    to it (spans of one name do not overlap on one thread; a lock's holds
    do not overlap at all)."""
    t = spans.get(name)
    if t is None:
        return 0.0
    lo = np.maximum(t.start_ns, window[0])
    hi = np.minimum(t.end_ns, window[1])
    return float(np.clip(hi - lo, 0, None).sum()) / 1e9


# ---------------------------------------------------------------------------
# the tree of spans: self times, and what lies under a set of spans
# ---------------------------------------------------------------------------

class Tree:
    """Every span of every table, flattened: name, times, parent, and self
    time (its duration less its direct children's)."""

    def __init__(self, spans: Mapping):
        names, ids, starts, ends, parents, threads = [], [], [], [], [], []
        for name, t in spans.items():
            names += [name] * len(t)
            ids.append(t.id)
            starts.append(t.start_ns)
            ends.append(t.end_ns)
            parents.append(t.parent)
            threads.append(t.thread)
        cat = (lambda xs: np.concatenate(xs) if xs
               else np.zeros(0, np.int64))
        self.name = np.array(names, dtype=object)
        self.id, self.start, self.end = cat(ids), cat(starts), cat(ends)
        self.parent, self.thread = cat(parents), cat(threads)
        order = np.argsort(self.id)
        self._sorted_ids = self.id[order]
        self._order = order
        dur = self.end - self.start
        child = np.zeros(len(self.id), np.int64)
        at = self.index(self.parent)
        ok = at >= 0
        np.add.at(child, at[ok], dur[ok])
        self.self_ns = dur - child

    def index(self, ids: np.ndarray) -> np.ndarray:
        """Row of each id, -1 where the span is not in the tree."""
        pos = np.searchsorted(self._sorted_ids, ids)
        pos = np.minimum(pos, max(len(self._sorted_ids) - 1, 0))
        if not len(self._sorted_ids):
            return np.full(len(ids), -1)
        found = self._sorted_ids[pos] == ids
        return np.where(found, self._order[pos], -1)

    def under(self, roots: np.ndarray) -> Dict[str, float]:
        """Seconds of self time, by span name, of the spans ``roots`` (rows)
        and of everything opened inside them."""
        root_of = np.full(len(self.id), -1)
        root_of[roots] = roots
        rows = np.arange(len(self.id))
        up = self.index(self.parent)
        # walk each span up its chain until it meets a root (or the top)
        cur = up.copy()
        pending = (root_of < 0) & (cur >= 0)
        while pending.any():
            hit = pending & (root_of[np.maximum(cur, 0)] >= 0)
            root_of[rows[hit]] = root_of[cur[hit]]
            cur = np.where(pending & ~hit, up[np.maximum(cur, 0)], -1)
            pending = (root_of < 0) & (cur >= 0)
        out: Dict[str, float] = defaultdict(float)
        for r in np.nonzero(root_of >= 0)[0]:
            out[self.name[r]] += self.self_ns[r] / 1e9
        return dict(out)


def table(spans: Mapping, window: Tuple[int, int]) -> List[Tuple]:
    """``(name, count, total_s, p50_ms, p95_ms, self_s)`` of the spans
    begun in ``window``, by total, most first."""
    tree = Tree(spans)
    rows = []
    for name, t in spans.items():
        keep = begun(t, window)
        if not keep.any():
            continue
        d = t.duration_ns[keep] / 1e6
        mine = (tree.name == name) & (tree.start >= window[0]) & \
            (tree.start < window[1])
        rows.append((name, int(keep.sum()), float(d.sum()) / 1e3,
                     percentile(list(d), 50), percentile(list(d), 95),
                     float(tree.self_ns[mine].sum()) / 1e9))
    rows.sort(key=lambda r: -r[2])
    return rows


class _Last:
    """The last span of one name on a thread that ended by a given time."""

    def __init__(self, tree: Tree, name: str):
        rows = np.nonzero(tree.name == name)[0]
        self.by_thread = {}
        for th in np.unique(tree.thread[rows]):
            r = rows[tree.thread[rows] == th]
            r = r[np.argsort(tree.end[r])]
            self.by_thread[th] = (tree.end[r], r)

    def before(self, thread: int, t: int) -> int:
        ends, rows = self.by_thread.get(thread, ((), ()))
        k = int(np.searchsorted(ends, t, side="right")) - 1
        return int(rows[k]) if k >= 0 else -1


def fetch_coverage(spans: Mapping, window: Tuple[int, int]) -> Dict:
    """What a ``FetchModel`` call's time at the client is made of: the mean
    ``repro.call``, and per call, the gateway's request decode, its wait for
    the dispatch lock and its ``repro.serve``, with the client's own encode,
    send and decode; the rest is the socket and thread wake-ups. A call is
    paired with the serve of the same ``vid`` and ``seq`` begun during
    it."""
    calls, serves = spans.get("repro.call"), spans.get("repro.serve")
    if calls is None or serves is None:
        return {}
    tree = Tree(spans)
    by_key: Dict[Tuple, List[int]] = defaultdict(list)
    for i, (v, s, ty) in enumerate(zip(serves.attrs["vid"],
                                       serves.attrs["seq"],
                                       serves.attrs["type"])):
        if ty == "FetchModel":
            by_key[(v, int(s))].append(i)
    waits, decodes = _Last(tree, "repro.lock_wait"), _Last(tree, "repro.decode")
    parts: Dict[str, float] = defaultdict(float)
    n = 0
    pick = np.nonzero(begun(calls, window)
                      & (calls.attrs["type"] == "FetchModel"))[0]
    for c in pick:
        # the serve of this call: same connection and index, begun during
        # the call (a gateway that served an earlier run may reuse vid and
        # seq; the serve may end after the client has its reply)
        inside = [i for i in by_key.get((calls.attrs["vid"][c],
                                         int(calls.attrs["seq"][c])), ())
                  if calls.start_ns[c] <= serves.start_ns[i]
                  <= calls.end_ns[c]]
        if not inside:
            continue
        s = inside[0]
        n += 1
        parts["call"] += (calls.end_ns[c] - calls.start_ns[c]) / 1e9
        parts["serve"] += (serves.end_ns[s] - serves.start_ns[s]) / 1e9
        thread = serves.thread[s]
        held = tree.index(np.array([serves.parent[s]]))[0]
        # the gateway's wait and its decode of the request, on the serve's
        # thread, both begun during the call
        if held >= 0 and tree.name[held] == "repro.lock_held":
            w = waits.before(thread, tree.start[held])
            if w >= 0 and tree.start[w] >= calls.start_ns[c]:
                parts["lock_wait"] += (tree.end[w] - tree.start[w]) / 1e9
                d = decodes.before(thread, tree.start[w])
                if d >= 0 and tree.start[d] >= calls.start_ns[c]:
                    parts["gateway_decode"] += \
                        (tree.end[d] - tree.start[d]) / 1e9
        kids = tree.parent == calls.id[c]
        for name in ("repro.encode", "repro.send", "repro.decode"):
            m = kids & (tree.name == name)
            parts["client_" + name[len(PREFIX):]] += \
                float((tree.end[m] - tree.start[m]).sum()) / 1e9
    if not n:
        return {}
    out = {k: v / n * 1e3 for k, v in parts.items()}      # ms per fetch
    out["calls"] = n
    covered = out.get("lock_wait", 0.0) + out["serve"] + \
        out.get("client_encode", 0.0) + out.get("client_decode", 0.0)
    out["covered_share"] = covered / out["call"]
    return out


# ---------------------------------------------------------------------------
# the profile: idle stretches of the device, charged to program spans
# ---------------------------------------------------------------------------

def _self_intervals(events: Sequence[Tuple[int, int, str]]):
    """The innermost open span at each moment of one thread's line: a list
    of ``(start, end, name)`` from properly nested ``(start, end, name)``."""
    out = []
    stack: List[List] = []                  # [end, name, covered-from]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            if top[2] < top[0]:
                out.append((top[2], top[0], top[1]))
            if stack:
                stack[-1][2] = top[0]
        if stack and stack[-1][2] < s:
            out.append((stack[-1][2], s, stack[-1][1]))
        stack.append([e, name, s])
    while stack:
        top = stack.pop()
        if top[2] < top[0]:
            out.append((top[2], top[0], top[1]))
        if stack:
            stack[-1][2] = top[0]
    return out


def _integral(intervals: Sequence[Tuple[float, float]]):
    """``(t, F)``: the integral over time of how many ``intervals`` are
    open, as a piecewise-linear function sampled at its breakpoints."""
    if not intervals:
        return np.zeros(1), np.zeros(1)
    iv = np.asarray(intervals, np.float64)
    t = np.concatenate([iv[:, 0], iv[:, 1]])
    step = np.concatenate([np.ones(len(iv)), -np.ones(len(iv))])
    order = np.argsort(t, kind="stable")
    t, step = t[order], step[order]
    open_after = np.cumsum(step)
    area = np.concatenate([[0.0], np.cumsum(open_after[:-1] * np.diff(t))])
    return t, area


def idle_by_program_span(profile) -> List[List]:
    """Each idle stretch of the first device inside the traced window,
    charged to the ``repro.*`` span whose own time (not its children's, and
    not a wait's: ``WAITS``) covered most of it, summed over threads.
    Returns the ten largest ``[name, seconds]`` totals; ``no span`` takes
    the stretches no such span covered."""
    window = None
    by_line: List[List[Tuple[int, int, str]]] = []
    device = None
    for plane in profile.planes:
        if device is None and tracemod._DEVICE_PLANE.match(plane.name):
            device = plane
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name == tracemod.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
            if evs:
                by_line.append(evs)
    if window is None or device is None:
        return []
    ops = []
    for line in device.lines:
        if line.name.startswith("XLA Ops"):
            for ev in line.events:
                iv = tracemod.clip((ev.start_ns, ev.start_ns + ev.duration_ns),
                                   window)
                if iv:
                    ops.append(iv)
    idle = tracemod.gaps(tracemod.union(ops), window)
    if not idle:
        return []
    own: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for evs in by_line:
        for s, e, name in _self_intervals(evs):
            if name not in WAITS:
                own[name].append((s, e))
    names = sorted(own)
    lo = np.array([g[0] for g in idle], np.float64)
    hi = np.array([g[1] for g in idle], np.float64)
    cover = np.zeros((len(idle), len(names)))
    for j, name in enumerate(names):
        t, area = _integral(own[name])
        cover[:, j] = np.interp(hi, t, area) - np.interp(lo, t, area)
    totals: Dict[str, float] = defaultdict(float)
    for i, g in enumerate(idle):
        j = int(np.argmax(cover[i])) if names else -1
        name = names[j] if j >= 0 and cover[i, j] > 0 else "no span"
        totals[name] += (g[1] - g[0]) * 1e-9
    top = sorted(totals.items(), key=lambda x: -x[1])[:_TOP]
    return [[n, s] for n, s in top]


def idle_by_program_span_file(path: str) -> List[List]:
    from jax.profiler import ProfileData
    return idle_by_program_span(ProfileData.from_file(path))


def step_clock_misses(profile, slack_ns: float = 50e3) -> Tuple[int, int]:
    """``(misses, runs)``: of the volunteer step's device runs (``jit_loss``
    module events on the first device), how many do not lie inside some
    ``repro.step`` host span, give or take ``slack_ns``. Zero misses says
    the program's spans and the device share one clock."""
    steps, runs = [], []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith("/host:") and \
                        ev.name == "repro.step":
                    steps.append(iv)
                elif plane.name == "/device:TPU:0" and \
                        line.name.startswith("XLA Modules") and \
                        tracemod._module_name(ev.name) == "jit_loss":
                    runs.append(iv)
    st = np.array(steps, np.float64).reshape(-1, 2)
    misses = sum(not np.any((st[:, 0] - slack_ns <= s) &
                            (e <= st[:, 1] + slack_ns)) for s, e in runs)
    return int(misses), len(runs)
