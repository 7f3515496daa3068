"""Spans of the served path's own host work, on the device trace's clock.

Off by default: ``span`` then returns one shared no-op object; it records
nothing, reads no clock and calls nothing in JAX. After ``enable()`` each
span does two things:

* it opens a ``jax.profiler.TraceAnnotation`` of its name and attributes,
  so that a profile taken meanwhile (``jax.profiler.trace(logdir)``) holds
  it in the host plane, on the same clock as the device planes;
* it records its start and end (``time.perf_counter_ns``), its thread, and
  the span it was opened inside on that thread.

``take()`` returns what was recorded since the last ``take()``, one
``Spans`` table per name, and clears it. Records are kept as rows of whole
numbers in one flat ``array`` per name, which the garbage collector never
scans.

Every span the program opens is named ``repro.<what>``; README "Tracing"
says which question each answers.
"""
from __future__ import annotations

import itertools
import struct
import threading
import time
from array import array
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

_on = False
_annotation: Any = None              # jax.profiler.TraceAnnotation, once on
_ids = itertools.count(1)            # span ids; 0 is "no parent"
_local = threading.local()           # .state: this thread's ``_Thread``
_lock = threading.Lock()             # guards _recorder and its columns


class _Off:
    """The span of a switched-off tracer."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


OFF = _Off()


@dataclass(frozen=True)
class Spans:
    """Every span of one name that ``take`` collected, in the order they
    ended. Times are ``time.perf_counter_ns()``. An attribute a span did not
    carry reads ``""`` (text) or -1 (whole numbers)."""
    id: np.ndarray                   # unique within the process
    start_ns: np.ndarray
    end_ns: np.ndarray
    thread: np.ndarray               # ``threading.get_native_id()``
    parent: np.ndarray               # id of the enclosing span, 0 if none
    parent_name: np.ndarray          # its name, "" if none
    attrs: Dict[str, np.ndarray]

    def __len__(self) -> int:
        return len(self.id)

    @property
    def duration_ns(self) -> np.ndarray:
        return self.end_ns - self.start_ns


_FIXED = 6            # id, start, end, thread, parent id, parent name code


class _Recorder:
    """Spans as rows of whole numbers, one flat ``array`` per span name and
    set of attribute keys; text (parent names, string attributes) as codes
    into one table. An attribute keeps the kind, text or whole number, of
    its first value under that name."""

    def __init__(self):
        # (name, attribute keys) -> (rows, row format, which keys are text)
        self.groups: Dict[tuple, tuple] = {}
        self.strings: Dict[str, int] = {}

    def code(self, s: str) -> int:
        c = self.strings.get(s)
        if c is None:
            c = self.strings[s] = len(self.strings)
        return c

    def add(self, sp: "_Span", end: int) -> None:
        attrs = sp.attrs
        key = (sp.name, tuple(attrs))
        group = self.groups.get(key)
        if group is None:
            group = self.groups[key] = (
                array("q"), struct.Struct(f"={_FIXED + len(attrs)}q"),
                tuple(isinstance(v, str) for v in attrs.values()))
        rows, row, text = group
        parent = sp.parent
        rows.frombytes(row.pack(
            sp.id, sp.start, end, sp._thread.tid,
            parent.id if parent is not None else 0,
            self.code(parent.name) if parent is not None else -1,
            *[self.code(v) if t else int(v)
              for v, t in zip(attrs.values(), text)]))

    def tables(self) -> Dict[str, Spans]:
        text = np.array(list(self.strings) + [""])   # code -1 reads ""
        by_name: Dict[str, list] = {}
        for (name, keys), (rows, _, kinds) in self.groups.items():
            m = np.frombuffer(rows, np.int64).reshape(-1, _FIXED + len(keys))
            by_name.setdefault(name, []).append((m, dict(zip(keys, kinds))))
        out = {}
        for name, parts in by_name.items():
            m = np.concatenate([g[:, :_FIXED] for g, _ in parts])
            kinds: Dict[str, bool] = {}
            for _, ks in parts:
                for k, t in ks.items():
                    kinds.setdefault(k, t)
            attrs = {}
            for k, t in kinds.items():
                col = np.concatenate([
                    g[:, _FIXED + list(ks).index(k)] if k in ks
                    else np.full(len(g), -1) for g, ks in parts])
                attrs[k] = text[col] if t else col
            out[name] = Spans(id=m[:, 0], start_ns=m[:, 1], end_ns=m[:, 2],
                              thread=m[:, 3], parent=m[:, 4],
                              parent_name=text[m[:, 5]], attrs=attrs)
        return out


_recorder = _Recorder()


class _Thread:
    """One thread's open spans, innermost last, and its native id."""

    __slots__ = ("stack", "tid")

    def __init__(self):
        self.stack: List["_Span"] = []
        self.tid = threading.get_native_id()


def _this_thread() -> _Thread:
    th = getattr(_local, "state", None)
    if th is None:
        th = _local.state = _Thread()
    return th


class _Span:
    __slots__ = ("name", "attrs", "id", "start", "parent", "_note",
                 "_thread")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        th = self._thread = _this_thread()
        stack = th.stack
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.id = next(_ids)
        self._note = _annotation(self.name, **self.attrs)
        self._note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        stack = self._thread.stack
        if stack and stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        with _lock:
            _recorder.add(self, end)
        return False


def span(name: str, **attrs):
    """A context manager around one piece of work: ``OFF`` while the
    tracer is off, else a recorded, annotated span (see the module
    docstring). Attribute values are strings or whole numbers."""
    if not _on:
        return OFF
    return _Span(name, attrs)


def enable() -> None:
    """Switch every later span on."""
    global _on, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Switch later spans off; what was recorded stays for ``take``."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def take() -> Dict[str, Spans]:
    """What was recorded since the last ``take``, by span name; clears it.
    A span still open is recorded when it ends, in the next ``take``."""
    global _recorder
    with _lock:
        rec, _recorder = _recorder, _Recorder()
    return rec.tables()


class TimedLock:
    """A lock whose waits and holds are spans: ``repro.lock_wait`` from the
    call to ``acquire`` until it returns, ``repro.lock_held`` from then
    until ``release``. Wraps any lock with ``threading.Lock``'s surface
    (``with``, ``acquire``, ``release``, ``locked``)."""

    def __init__(self, lock):
        self._lock = lock
        self._held: Optional[Any] = None

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        with span("repro.lock_wait"):
            ok = self._lock.acquire(blocking, timeout)
        if ok:
            held = span("repro.lock_held")
            held.__enter__()
            self._held = held
        return ok

    def release(self) -> None:
        held, self._held = self._held, None
        self._lock.release()
        if held is not None:
            held.__exit__(None, None, None)

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "TimedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False
