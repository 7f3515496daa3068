"""``repro.obs``: the program's spans, off by default, and the spans the
served path records when they are on."""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import gateway

#: every span the served real-apply path opens
SERVED_SPANS = {"repro.lock_wait", "repro.lock_held", "repro.serve",
                "repro.materialize", "repro.drain", "repro.admit",
                "repro.pack", "repro.apply", "repro.publish", "repro.encode",
                "repro.decode", "repro.to_host", "repro.send", "repro.call",
                "repro.step"}


@pytest.fixture
def on():
    """Spans on for one test; off again, and nothing left recorded, after."""
    obs.take()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.take()


class _Refuse:
    """Stands in for the clock and for JAX: any use fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"an off span used {name}")


def test_off_span_is_one_shared_noop():
    assert not obs.enabled()
    assert obs.span("a") is obs.span("b", vid="v", seq=1) is obs.OFF
    with obs.span("a") as s:
        assert s is obs.OFF
    assert obs.take() == {}


def test_off_dispatch_lock_is_a_plain_lock():
    assert not obs.enabled()
    lock = gateway._make_lock("gateway._lock", guard=True)
    assert type(lock) is type(threading.Lock())


def test_off_spans_read_no_clock_and_call_no_jax(monkeypatch):
    # a whole socket run with every span site on its path, while off
    from repro.core.simulator import SyntheticProblem
    monkeypatch.setattr(obs, "time", _Refuse())
    monkeypatch.setattr(obs, "_annotation", _Refuse())
    server = gateway.GatewayServer(SyntheticProblem(n_versions=2, n_mb=2),
                                   n_versions=2)
    server.start()
    try:
        tr = gateway.SocketTransport("127.0.0.1", server.port, "v0")
        try:
            assert gateway.run_volunteer(tr, "v0", 2) == (2, 6)
        finally:
            tr.close()
    finally:
        server.close()
    assert obs.take() == {}


def test_on_records_nesting_and_take_clears(on):
    with obs.span("repro.outer", vid="v1", seq=3):
        with obs.span("repro.inner"):
            time.sleep(0.002)
        with obs.span("repro.inner", n=2):
            pass
    got = obs.take()
    assert set(got) == {"repro.outer", "repro.inner"}
    outer, inner = got["repro.outer"], got["repro.inner"]
    assert len(outer) == 1 and len(inner) == 2
    assert list(inner.parent) == [outer.id[0]] * 2
    assert list(inner.parent_name) == ["repro.outer"] * 2
    assert outer.parent[0] == 0 and outer.parent_name[0] == ""
    assert outer.start_ns[0] <= inner.start_ns[0] <= inner.end_ns[1] \
        <= outer.end_ns[0]
    assert inner.duration_ns[0] >= 2e6
    assert list(outer.attrs["vid"]) == ["v1"] and list(outer.attrs["seq"]) == [3]
    # an attribute one span of the name did not carry reads -1
    assert list(inner.attrs["n"]) == [-1, 2]
    assert obs.take() == {}


def test_on_spans_keep_threads_apart(on):
    def work():
        with obs.span("repro.step"):
            time.sleep(0.001)

    with obs.span("repro.outer"):
        th = threading.Thread(target=work)
        th.start()
        th.join(10)
    assert not th.is_alive()
    got = obs.take()
    step = got["repro.step"]
    assert step.parent[0] == 0              # opened on another thread
    assert step.thread[0] != got["repro.outer"].thread[0]


def test_span_open_at_take_lands_in_the_next(on):
    with obs.span("repro.long"):
        assert obs.take() == {}
    assert len(obs.take()["repro.long"]) == 1


def test_timed_lock_records_wait_and_hold(on):
    lock = obs.TimedLock(threading.Lock())
    held = threading.Event()

    def holder():
        with lock:
            held.set()
            time.sleep(0.1)

    th = threading.Thread(target=holder)
    th.start()
    assert held.wait(10)
    with lock:
        assert lock.locked()
        with obs.span("repro.serve"):
            pass
    th.join(10)
    assert not th.is_alive() and not lock.locked()
    got = obs.take()
    waits, holds = got["repro.lock_wait"], got["repro.lock_held"]
    assert len(waits) == len(holds) == 2
    assert waits.duration_ns.max() >= 20e6       # waited behind the holder
    assert holds.duration_ns.max() >= 90e6
    assert list(got["repro.serve"].parent_name) == ["repro.lock_held"]
    # each hold begins where its wait ends, on the wait's thread
    for w in range(2):
        h = int(np.nonzero(holds.thread == waits.thread[w])[0][0])
        assert 0 <= holds.start_ns[h] - waits.end_ns[w] < 1e6


def test_timed_lock_composes_with_analysis_instrument(on, monkeypatch):
    from repro.analysis.runtime import Analysis, MonitoredLock
    monkeypatch.setenv("ANALYSIS_INSTRUMENT", "1")
    Analysis.reset()
    try:
        lock = gateway._make_lock("gateway._lock", guard=True)
        assert isinstance(lock, obs.TimedLock)
        assert isinstance(lock._lock, MonitoredLock)
        mon = Analysis.instrument()
        with lock:
            # the monitor still sees its guard lock held through the wrapper
            mon.note_blocking("socket-recv")
        assert [v.rule for v in mon.violations] == ["LOCK-BLOCK"]
        assert not lock.locked()
        # an unguarded lock is never timed
        assert isinstance(gateway._make_lock("gateway._snap_lock"),
                          MonitoredLock)
    finally:
        Analysis.reset()
    got = obs.take()
    assert len(got["repro.lock_wait"]) == len(got["repro.lock_held"]) == 1


def _serving(before):
    return [t for t in threading.enumerate()
            if "_serve_conn" in t.name and t not in before]


def test_served_run_records_every_span(on):
    from repro.core.mapreduce import TrainingProblem
    from repro.data.text import synthetic_corpus
    problem = TrainingProblem.paper_problem(
        seed=3, corpus=synthetic_corpus(20_000, seed=3), d_model=8)
    before = set(threading.enumerate())
    server, results, _ = gateway.serve_real_run(
        problem, ["v0", "v1"], n_versions=1, policy="staleness:2")
    assert sorted(r[0] for r in results.values()) == [16, 16]
    # the gateway's connection threads end their last spans after the
    # clients close
    deadline = time.monotonic() + 30
    while _serving(before) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _serving(before)
    got = obs.take()
    assert SERVED_SPANS <= set(got), SERVED_SPANS - set(got)
    # encode nests in a serve, which nests in the dispatch lock's hold
    serve, enc = got["repro.serve"], got["repro.encode"]
    assert set(serve.parent_name) == {"repro.lock_held"}
    in_serve = set(enc.parent[enc.parent_name == "repro.serve"].tolist())
    assert in_serve and in_serve <= set(serve.id.tolist())
    assert set(got["repro.to_host"].parent_name) == {"repro.encode"}
    # the two ends of one request share vid and seq, and agree on its type
    call = got["repro.call"]
    calls = set(zip(call.attrs["vid"], call.attrs["seq"].tolist(),
                    call.attrs["type"]))
    served = set(zip(serve.attrs["vid"], serve.attrs["seq"].tolist(),
                     serve.attrs["type"]))
    assert served <= calls
    assert {(v, t) for v, _, t in served if t == "FetchModel"} == \
        {("v0", "FetchModel"), ("v1", "FetchModel")}
    # every submit is drained once, admitted or stale
    assert got["repro.drain"].attrs["n"].sum() == \
        server.applier.applied + server.applier.rejected
    assert len(got["repro.step"]) >= 16
