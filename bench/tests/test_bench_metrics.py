"""The metric arithmetic: tails over every request, rates over the whole
window, and the FLOP and byte counts from the shapes."""
import math

import pytest

import benchtest  # noqa: F401
from jsdoop_bench import spec, stats
from jsdoop_bench.driver import Call, RunRecord, Ticket
from jsdoop_bench.trace import TraceSummary

REF = spec.load_family("lstm").REFERENCE
PAPER = spec.load_config("paper-lstm")
#: char-rnn's 2x128 LSTM on the same corpus
CHAR = dict(PAPER, name="char-rnn-2x128", hidden_size=128)


def read(metric, run):
    return spec.load_reader(metric)(run)


def _record(tickets, *, counters=None, trace=None, trace_window=None,
            window=(10.0, 20.0)):
    zero = {"applied": 0, "rejected": 0, "batches": 0, "batched_updates": 0}
    return RunRecord(
        cell="t", config=PAPER, traffic={}, kind="map", setup_s=3.5,
        window=window, tickets=tickets,
        counters=counters or {"t0": zero, "t1": zero}, peaks={
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        flops_per_update=REF.flops_per_gradient(PAPER),
        gradients_per_ticket=1,
        applier_bytes_per_update=REF.applier_bytes_per_update(PAPER, "map"),
        trace=trace, trace_window=trace_window)


def _tickets(n, wait, start=10.0, step=0.05):
    out = []
    for i in range(n):
        t0 = start + i * step
        out.append(Ticket("v", t0, t0 + wait(i), ("map", i, 0), 0, i + 1,
                          1.0, t0, [Call("SubmitUpdate", t0, 0.001 * (i + 1),
                                         100, 20)]))
    return out


def test_parameter_counts_match_the_published_models():
    assert REF.param_count(PAPER) == 39_938
    assert REF.param_count(CHAR) == 221_990


def test_flops_and_bytes_from_shapes():
    # 2 layers x 40 steps of [8, 38+50] @ [88, 200] and [8, 100] @ [100, 200],
    # the head once, x3 for forward and backward
    fwd = 40 * 2 * 8 * (88 * 200 + 100 * 200) + 2 * 8 * 50 * 38
    assert REF.flops_per_gradient(PAPER) == 3 * fwd
    assert REF.applier_bytes_per_update(PAPER, "map") == 5 * 39_938 * 4
    assert REF.applier_bytes_per_update(PAPER, "local") == 6 * 39_938 * 4


def test_percentile_interpolates_over_all_values():
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0


def test_tail_counts_every_ticket_leased_in_the_window():
    # 200 tickets leased in the window, the last 10 slow: the 95th
    # percentile falls among them, and a ticket whose reply comes after the
    # close still counts
    tickets = _tickets(200, lambda i: 1.0 if i >= 190 else 0.01)
    run = _record(tickets)
    assert read("commit_p95_ms", run) == pytest.approx(
        stats.percentile([t.t_reply - t.t_lease for t in tickets], 95) * 1e3)
    assert read("commit_p95_ms", run) > 10.0


def test_rate_is_over_the_whole_window():
    tickets = _tickets(100, lambda i: 0.01)          # replies 10.01 .. 14.96
    run = _record(tickets)
    assert read("updates_per_s", run) == pytest.approx(100 / 10.0)
    late = _record(tickets, window=(12.0, 22.0))
    assert read("updates_per_s", late) == pytest.approx(
        sum(12.0 <= t.t_reply < 22.0 for t in tickets) / 10.0)


def test_counter_metrics():
    counters = {"t0": {"applied": 10, "rejected": 0, "batches": 2,
                       "batched_updates": 8},
                "t1": {"applied": 110, "rejected": 25, "batches": 22,
                       "batched_updates": 98}}
    run = _record(_tickets(5, lambda i: 0.01), counters=counters)
    # 100 applied in 20 drains of >= 2 (90 updates) and 10 alone: 30 drains
    assert read("drain_len_mean", run) == pytest.approx(100 / 30)
    assert read("stale_share", run) == pytest.approx(100 * 25 / 125)


def test_wire_bytes_per_commit():
    run = _record(_tickets(50, lambda i: 0.01))
    assert read("wire_kB_per_update", run) == pytest.approx(120 * 50 / 50 / 1e3)
    assert read("submit_p95_ms", run) == pytest.approx(
        stats.percentile([0.001 * (i + 1) for i in range(50)], 95) * 1e3)
    assert read("fetch_p95_ms", run) is None


def test_device_metrics_from_a_trace():
    tickets = _tickets(100, lambda i: 0.01)
    summary = TraceSummary(window_s=2.0, busy_s=0.5,
                           layers={"applier": 0.02, "volunteer step": 0.3,
                                   "other": 0.1})
    run = _record(tickets, trace=summary, trace_window=(10.0, 12.0))
    commits = sum(10.0 <= t.t_reply < 12.0 for t in tickets)
    assert read("device_idle_share", run) == pytest.approx(75.0)
    assert read("applier_us_per_update", run) == pytest.approx(
        0.02 / commits * 1e6)
    least = 5 * 39_938 * 4 / 819e9
    assert read("applier_roofline", run) == pytest.approx(
        100 * least / (0.02 / commits))
    assert read("grad_device_ms", run) == pytest.approx(0.3 / commits * 1e3)
    mfu = read("train_mfu", run)
    assert mfu == pytest.approx(100 * REF.flops_per_gradient(PAPER) * commits
                                / 2.0 / 197e12)
    assert 0 < mfu < 1e-2


def test_device_metrics_need_a_trace():
    run = _record(_tickets(10, lambda i: 0.01))
    for m in ("device_idle_share", "applier_us_per_update", "applier_roofline",
              "grad_device_ms", "train_mfu"):
        assert read(m, run) is None
    assert not math.isnan(read("updates_per_s", run))


def test_p95_by_part_reads_each_stretch_of_the_window_alone():
    from jsdoop_bench.driver import _p95_by_part
    # 200 tickets over [10, 20): the first half waits 0.1 s, the second 0.3 s,
    # and a ticket with no reply counts in none of the parts
    tickets = _tickets(200, lambda i: 0.1 if i < 100 else 0.3)
    tickets.append(Ticket("v", 12.0))
    parts = _p95_by_part(tickets, 10.0, 20.0, 2)
    assert parts == pytest.approx([100.0, 300.0])
    assert math.isnan(_p95_by_part(tickets, 30.0, 40.0, 1)[0])


def test_host_pauses_count_the_collectors_passes_and_oversleep():
    import gc
    from jsdoop_bench.driver import HostPauses
    pauses = HostPauses()
    try:
        gc.collect()
        pauses.nap(0.001)
    finally:
        pauses.close()
    assert any(g == 2 for g, _ in pauses.gc)
    assert pauses.oversleep >= 0.0
    assert "full" in pauses.summary()
    assert pauses._on_gc not in gc.callbacks
