"""The readers of the program's own spans (``repro.obs``), the idle
stretches of the device charged to them, and a run with them on."""
import gzip
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

import benchtest
from jsdoop_bench import spans, spec
from jsdoop_bench.driver import Call, RunRecord, Ticket

SPAN_METRICS = ("lock_wait_p95_ms", "lock_held_share", "fetch_serve_ms",
                "codec_ms_per_update", "drain_host_us_per_update",
                "step_host_ms")
MS = 1_000_000                                  # ns


def read(metric, run):
    return spec.load_reader(metric)(run)


def tables(rows):
    """Span tables from ``(name, id, start_ms, end_ms, parent_id, attrs)``
    rows, as ``repro.obs.take`` returns them (thread 1 unless ``thread``
    is among the attributes)."""
    from repro.obs import Spans
    by_name = {}
    for name, i, s, e, parent, attrs in rows:
        by_name.setdefault(name, []).append((i, s, e, parent, attrs))
    names = {i: name for name, i, *_ in rows}
    out = {}
    for name, rs in by_name.items():
        keys = sorted({k for r in rs for k in r[4] if k != "thread"})
        out[name] = Spans(
            id=np.array([r[0] for r in rs]),
            start_ns=np.array([int(r[1] * MS) for r in rs]),
            end_ns=np.array([int(r[2] * MS) for r in rs]),
            thread=np.array([r[4].get("thread", 1) for r in rs]),
            parent=np.array([r[3] for r in rs]),
            parent_name=np.array([names.get(r[3], "") for r in rs]),
            attrs={k: np.array([r[4].get(k) for r in rs]) for k in keys})
    return out


def record(span_rows, *, applied=4, commits=4):
    """A run over the window 10 s .. 11 s with ``commits`` commits replied
    in it and ``applied`` updates applied."""
    tickets = [Ticket("v", 10.0 + i * 0.1, 10.05 + i * 0.1, ("map", i, 0),
                      i, i + 1, 1.0, 10.0 + i * 0.1,
                      [Call("FetchModel", 10.0 + i * 0.1, 0.004, 10, 1000)])
               for i in range(commits)]
    zero = {"applied": 0, "rejected": 0, "batches": 0, "batched_updates": 0}
    run = RunRecord(
        cell="t", config={}, traffic={}, kind="map", setup_s=1.0,
        window=(10.0, 11.0), tickets=tickets,
        counters={"t0": zero, "t1": dict(zero, applied=applied)},
        peaks=None, flops_per_update=0.0, gradients_per_ticket=1,
        applier_bytes_per_update=0.0)
    run.spans = None if span_rows is None else tables(span_rows)
    return run


#: times in ms on the perf_counter clock; the window is 10,000 .. 11,000
ROWS = [
    # a wait and hold of the dispatch lock before the window, clipped out
    ("repro.lock_wait", 1, 9_990, 9_995, 0, {}),
    ("repro.lock_held", 2, 9_995, 10_010, 0, {}),
    # two fetches served in the window, one lease, one drain of 2
    ("repro.lock_wait", 3, 10_100, 10_102, 0, {}),
    ("repro.lock_held", 4, 10_102, 10_110, 0, {}),
    ("repro.serve", 5, 10_102, 10_110, 4,
     {"type": "FetchModel", "vid": "v0", "seq": 3}),
    ("repro.encode", 6, 10_103, 10_108, 5, {}),
    ("repro.to_host", 7, 10_103, 10_106, 6, {}),
    ("repro.lock_wait", 8, 10_200, 10_210, 0, {}),
    ("repro.lock_held", 9, 10_210, 10_214, 0, {}),
    ("repro.serve", 10, 10_210, 10_214, 9,
     {"type": "FetchModel", "vid": "v1", "seq": 3}),
    ("repro.lock_wait", 11, 10_300, 10_301, 0, {}),
    ("repro.lock_held", 12, 10_301, 10_302, 0, {}),
    ("repro.serve", 13, 10_301, 10_302, 12,
     {"type": "LeaseReq", "vid": "v0", "seq": 4}),
    ("repro.lock_wait", 14, 10_400, 10_400.5, 0, {}),
    ("repro.lock_held", 15, 10_400.5, 10_406.5, 0, {}),
    ("repro.drain", 16, 10_400.5, 10_406.5, 15, {"n": 2}),
    ("repro.decode", 17, 10_500, 10_501, 0, {}),
    ("repro.step", 18, 10_600, 10_602, 0, {}),
    ("repro.step", 19, 10_700, 10_704, 0, {}),
    # begun after the window closed: read by none
    ("repro.step", 20, 11_000, 11_100, 0, {}),
    ("repro.lock_wait", 21, 11_000, 11_050, 0, {}),
]


def test_each_reader_reads_the_window():
    run = record(ROWS)
    # waits 2, 10, 1, 0.5 ms begun in the window
    assert read("lock_wait_p95_ms", run) == pytest.approx(
        np.percentile([2, 10, 1, 0.5], 95, method="linear"), rel=1e-3)
    # holds 10 (clipped to the window's start), 8, 4, 1, 6 ms of 1 s
    assert read("lock_held_share", run) == pytest.approx(100 * 29e-3)
    assert read("fetch_serve_ms", run) == pytest.approx((8 + 4) / 2)
    # encode 5 ms and decode 1 ms over 4 commits
    assert read("codec_ms_per_update", run) == pytest.approx(6 / 4)
    # a 6 ms drain over 4 applied updates
    assert read("drain_host_us_per_update", run) == pytest.approx(6e3 / 4)
    assert read("step_host_ms", run) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_reader_returns_none_without_spans(metric):
    # a program without repro.obs, or a run with the spans off
    assert read(metric, record(None)) is None
    run = record(None)
    del run.spans
    assert read(metric, run) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_reader_returns_none_without_the_span(metric):
    assert read(metric, record([("repro.other", 1, 10_100, 10_200, 0, {})],
                               applied=0, commits=0)) is None


def test_self_time_and_what_lies_under_spans():
    tree = spans.Tree(tables(ROWS))
    row = {int(i): r for r, i in enumerate(tree.id)}
    assert tree.self_ns[row[5]] == 3 * MS          # serve 8 less encode 5
    assert tree.self_ns[row[6]] == 2 * MS          # encode 5 less to_host 3
    assert tree.self_ns[row[4]] == 0               # the hold is the serve
    under = tree.under(np.array([row[5], row[10]]))
    assert under == pytest.approx({"repro.serve": 7e-3,
                                   "repro.encode": 2e-3,
                                   "repro.to_host": 3e-3})
    table = {r[0]: r for r in spans.table(tables(ROWS), (10_000 * MS,
                                                        11_000 * MS))}
    assert table["repro.lock_wait"][1] == 4
    assert table["repro.serve"][5] == pytest.approx(8e-3)


def test_fetch_coverage_pairs_calls_with_serves():
    rows = ROWS + [
        # v0's fetch at the client: encode 0.5, send 0.5, decode 1 ms
        ("repro.call", 30, 10_099, 10_112, 0,
         {"type": "FetchModel", "vid": "v0", "seq": 3, "thread": 2}),
        ("repro.encode", 31, 10_099, 10_099.5, 30, {"thread": 2}),
        ("repro.send", 32, 10_099.5, 10_100, 30, {"thread": 2}),
        ("repro.decode", 33, 10_111, 10_112, 30, {"thread": 2}),
        # the gateway decodes the request before it waits for the lock
        ("repro.decode", 34, 10_099.8, 10_099.9, 0, {}),
        # an earlier gateway's serve with the same vid and seq, outside it
        ("repro.serve", 35, 9_000, 9_050, 0,
         {"type": "FetchModel", "vid": "v0", "seq": 3}),
        # v1's fetch, whose serve ends after the client has its reply
        ("repro.call", 36, 10_195, 10_213, 0,
         {"type": "FetchModel", "vid": "v1", "seq": 3, "thread": 3}),
    ]
    got = spans.fetch_coverage(tables(rows), (10_000 * MS, 11_000 * MS))
    # per fetch, over v0's (13 ms, serve 8, wait 2) and v1's (18 ms, serve
    # 4, wait 10, no client spans)
    assert got["calls"] == 2
    assert got["call"] == pytest.approx((13 + 18) / 2)
    assert got["serve"] == pytest.approx((8 + 4) / 2)
    assert got["lock_wait"] == pytest.approx((2 + 10) / 2)
    assert got["gateway_decode"] == pytest.approx(0.1 / 2)
    assert got["client_encode"] == pytest.approx(0.5 / 2)
    assert got["client_send"] == pytest.approx(0.5 / 2)
    assert got["client_decode"] == pytest.approx(1.0 / 2)
    assert got["covered_share"] == pytest.approx(
        (2 + 8 + 0.5 + 1 + 10 + 4) / 31)
    assert spans.fetch_coverage(tables(ROWS[:2]), (0, 1)) == {}


def test_self_intervals_of_nested_spans():
    evs = [(0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (60, 70, "d")]
    assert sorted(spans._self_intervals(evs)) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 60, "a"), (60, 70, "d"), (70, 100, "a")]


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def profile():
    """Window 0..1000 ns. The device runs 200..300 and 400..700. Thread A
    serves 0..250 and encodes inside it 50..170; thread B waits in a call
    0..1000 and decodes inside it 800..900; thread C waits for the lock
    300..400."""
    host = NS(name="/host:CPU", lines=[
        line("main", [ev("bench.window", 0, 1000)]),
        line("A", [ev("repro.serve", 0, 250), ev("repro.encode", 50, 120)]),
        line("B", [ev("repro.call", 0, 1000), ev("repro.decode", 800, 100)]),
        line("C", [ev("repro.lock_wait", 300, 100),
                   ev("bench.fetch", 300, 100)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit__lambda(1)", 200, 100),
                             ev("jit_loss(2)", 400, 300)]),
        line("XLA Ops", [ev("fusion.1", 200, 100), ev("while.2", 400, 300)]),
    ])
    return NS(planes=[host, dev])


def test_idle_charged_to_the_innermost_working_span():
    got = dict((n, s) for n, s in spans.idle_by_program_span(profile()))
    # 0..200: the encode's 120 over the serve's own 80; 300..400: only a
    # wait, so no span; 700..1000: the decode inside B's waiting call
    assert got == {"repro.encode": pytest.approx(200e-9),
                   "repro.decode": pytest.approx(300e-9),
                   "no span": pytest.approx(100e-9)}


def test_idle_needs_the_window_and_a_device():
    p = profile()
    p.planes = [p.planes[0]]
    assert spans.idle_by_program_span(p) == []


def test_step_clock_check_on_a_synthetic_profile():
    p = profile()
    p.planes[0].lines.append(line("D", [ev("repro.step", 410, 280)]))
    assert spans.step_clock_misses(p) == (0, 1)
    assert spans.step_clock_misses(p, slack_ns=0) == (1, 1)


@pytest.mark.parametrize("name,volunteers", [("paper-lstm.tcp1", 1),
                                             ("paper-lstm.ws32", 4)])
def test_a_run_with_the_spans_on_reads_every_metric(name, volunteers):
    import jax
    import program_spans
    cell = benchtest.small_cell(name, volunteers=volunteers)
    line = program_spans.one_run(cell, 2**31 + 101, 1.0, False,
                                 t_start=time.perf_counter(),
                                 devices=jax.devices(), peaks=None)
    assert line["correct"] and line["obs"]
    for m in SPAN_METRICS:
        assert line["metrics"][m] > 0, m
    assert line["metrics"]["updates_per_s"] > 0
    fetch = line["fetch"]
    assert fetch["calls"] > 0 and 0 < fetch["covered_share"] <= 1
    # the call the program times and the port the benchmark times agree
    assert fetch["call"] == pytest.approx(fetch["port_mean_ms"], rel=0.1)
    assert {"repro.to_host", "repro.encode"} <= \
        set(line["under"]["fetch_serve_ms"])
    assert {"repro.admit", "repro.apply", "repro.publish"} <= \
        set(line["under"]["drain_us_per_update"])
    from repro import obs
    assert not obs.enabled() and obs.take() == {}


def test_a_run_with_the_spans_off_reads_none(monkeypatch):
    import jax
    import repro
    import program_spans
    # a program without repro.obs runs as one with the spans off
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert program_spans._obs(True) is None
    monkeypatch.undo()
    cell = benchtest.small_cell("paper-lstm.tcp1", volunteers=1)
    line = program_spans.one_run(cell, 2**31 + 102, 0.5, False,
                                 t_start=time.perf_counter(),
                                 devices=jax.devices(), peaks=None, on=False)
    assert line["correct"] and not line["obs"]
    assert all(line["metrics"][m] is None for m in SPAN_METRICS)
    assert "fetch" not in line


#: 0.25 s of ``paper-lstm.tcp1``'s window with the program's spans on,
#: traced on one TPU v5e
FIXTURE = benchtest.BENCH / "tests" / "fixtures" / \
    "paper-lstm.tcp1.spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_profile():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        gzip.decompress(FIXTURE.read_bytes()))


def test_program_spans_share_the_device_clock(chip_profile):
    # every run of the volunteer step's program lies inside the host span
    # of the step that launched and waited for it
    misses, runs = spans.step_clock_misses(chip_profile)
    assert runs > 0 and misses == 0


def test_idle_on_the_chip_is_charged_to_program_spans(chip_profile):
    from jsdoop_bench import trace
    got = spans.idle_by_program_span(chip_profile)
    names = [n for n, _ in got]
    assert any(n.startswith("repro.") for n in names)
    assert not set(names) & spans.WAITS
    s = trace.reduce(chip_profile, spec.load_family("lstm").PROGRAMS)
    # the ten largest totals hold most of the idle time of a window whose
    # spans are few kinds
    assert sum(secs for _, secs in got) <= s.window_s - s.busy_s + 1e-9
