"""The reduction from a profiler trace to busy time, per-layer device time
and named idle gaps."""
import gzip
from types import SimpleNamespace as NS

import pytest

import benchtest
from jsdoop_bench import trace


PROGRAMS = {"jit_loss": "volunteer step", "jit__lambda": "applier"}


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def profile():
    """A window 0..1000 ns, with bench.drain over 100..200 and bench.grad
    over 300..800 on the host. The device runs the applier's jit__lambda at
    200..300 (two ops), the volunteer's jit_loss at 400..700, and a program
    no layer names at 750..760."""
    host = NS(name="/host:CPU", lines=[
        line("main", [ev("bench.window", 0, 1000)]),
        line("gateway", [ev("bench.drain", 100, 100)]),
        line("vol0", [ev("bench.grad", 300, 500)]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit__lambda(42)", 200, 100),
                             ev("jit_loss(43)", 400, 300),
                             ev("jit_convert_element_type(9)", 750, 10)]),
        line("XLA Ops", [ev("fusion.1", 200, 60), ev("copy.2", 250, 50),
                         ev("while.3", 400, 300), ev("convert.4", 750, 10)]),
    ])
    return NS(planes=[host, dev, NS(name="/host:metadata", lines=[])])


def test_interval_arithmetic():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.gaps([(1, 4), (5, 7)], (0, 10)) == [(0, 1), (4, 5), (7, 10)]
    assert trace.clip((5, 15), (0, 10)) == (5, 10)
    assert trace.clip((12, 15), (0, 10)) is None


def test_reduce_attributes_device_time_to_launching_spans():
    # each program's time goes to the layer the family names for it
    s = trace.reduce(profile(), PROGRAMS)
    assert s.window_s == pytest.approx(1000e-9)
    # 200..300, 400..700 and 750..760
    assert s.busy_s == pytest.approx(410e-9)
    assert s.layer_device_s("applier") == pytest.approx(100e-9)
    assert s.layer_device_s("volunteer step") == pytest.approx(300e-9)
    assert s.device_ops[:2] == [("jit_loss", pytest.approx(300e-9)),
                                ("jit__lambda", pytest.approx(100e-9))]
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_loss"
    # idle 0..200, 300..400, 700..750 and 760..1000: the longest lies
    # under bench.grad (to 800), the first half under bench.drain
    assert b["idle_gaps"][0] == ["bench.grad", pytest.approx(240e-9)]
    assert ["bench.drain", pytest.approx(200e-9)] in b["idle_gaps"]
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_reduce_needs_the_window_and_a_device():
    p = profile()
    p.planes[0].lines[0].events = []
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(p, PROGRAMS)
    p = profile()
    p.planes = [p.planes[0]]
    with pytest.raises(ValueError, match="device"):
        trace.reduce(p, PROGRAMS)


def test_runs_without_a_launch_are_charged_by_program_name():
    # a program that no layer names is charged to none of them
    s = trace.reduce(profile(), PROGRAMS)
    assert s.layer_device_s(trace.OTHER) == pytest.approx(10e-9)
    assert sum(s.layers.values()) == pytest.approx(410e-9)


#: 0.25 s of ``paper-lstm.tcp1``'s window, traced on one TPU v5e
FIXTURE = benchtest.BENCH / "tests" / "fixtures" / \
    "paper-lstm.tcp1.xplane.pb.gz"


def test_reduce_a_trace_recorded_on_the_chip():
    from jax.profiler import ProfileData
    from jsdoop_bench import spec
    programs = spec.load_family("lstm").PROGRAMS
    profile = ProfileData.from_serialized_xspace(
        gzip.decompress(FIXTURE.read_bytes()))
    s = trace.reduce(profile, programs)
    assert 0.2 < s.window_s < 0.3
    assert 0 < s.busy_s < s.window_s
    assert s.layer_device_s("volunteer step") > 0
    assert s.layer_device_s("applier") > 0
    assert s.layer_device_s(trace.OTHER) == 0, s.device_ops
    assert sum(s.layers.values()) == pytest.approx(
        sum(secs for _, secs in s.device_ops))
    assert {"jit_loss", "jit__lambda"} <= {n for n, _ in s.device_ops}
    gap_spans = {n for n, _ in s.idle_gaps}
    assert gap_spans & {"bench.lease", "bench.fetch", "bench.grad",
                        "bench.submit", "bench.drain", "bench.serve_fetch",
                        "bench.serve"}
    assert sum(secs for _, secs in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
