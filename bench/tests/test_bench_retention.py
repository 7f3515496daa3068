"""The schedule of tickets, and a check that keeps its own versions: whole
runs on the CPU against a program that holds every published version, and
against one that holds only the last few."""
import functools

import numpy as np
import pytest

from benchtest import run_small, small_cell
from jsdoop_bench import correctness, driver, spec
from jsdoop_bench.inputs import Seeds

#: the fastest rate a cell has read, updates/s (ws32 on TPU v5e)
FASTEST = 146.67


@pytest.mark.parametrize("cell", ["paper-lstm.tcp1", "paper-lstm.ws32"])
def test_schedule_of_the_paper_lstm_is_kept(cell):
    c = spec.load_cell(cell)
    assert driver.schedule(1, c.config["mini_batches_per_version"]) \
        == (20_163, 1_261)


def test_schedule_outlasts_warmup_and_the_longest_window():
    # the same for every model: a version of gigabytes (Mellum2-12B-A2.5B
    # cut to 340,349,184 parameters) gets as many tickets as paper-lstm
    tickets, _ = driver.schedule(1, 16)
    assert tickets >= 4096
    assert tickets > 2 * FASTEST * 51


def _keep_last(monkeypatch, k):
    """The program's gateway builds its real applier with ``gc_keep=k``."""
    from repro.core import applier, gateway
    monkeypatch.setattr(gateway, "make_real_applier", functools.partial(
        applier.make_real_applier, gc_keep=k))


def _seen():
    got = {}

    def look(reference, record, commits, sample, fetched, params0, weight):
        got.update(record=record, sample=sample, fetched=sorted(fetched))
    return got, look


def test_sample_with_every_version_kept_is_the_seeds_draw():
    cell = small_cell()
    got, look = _seen()
    res = run_small(cell, look=look)
    assert res["correct"], res["checks"]
    assert res["checks"]["unchecked"] == {"value": 0, "limit": 0}
    record = got["record"]
    # the draw over every commit replied in the window, as the check made
    # it before it kept its own versions
    inside = sorted(t.committed for t in record.tickets
                    if t.committed > 0 and record.in_window(t.t_reply))
    picked = np.random.default_rng(
        Seeds.of(2**31 + 11).sample).choice(
            inside, size=min(cell.traffic["checked_commits"], len(inside)),
            replace=False)
    final = max(t.committed for t in record.tickets)
    assert got["sample"] == sorted({int(c) for c in picked} | {final})


@pytest.mark.parametrize("name,volunteers,keep", [("paper-lstm.tcp1", 1, 8),
                                                  ("paper-lstm.ws32", 4, 16)])
def test_run_against_bounded_retention_is_correct(monkeypatch, name,
                                                  volunteers, keep):
    _keep_last(monkeypatch, keep)
    got, look = _seen()
    res = run_small(small_cell(name, volunteers=volunteers), look=look)
    assert res["correct"], res["checks"]
    final = max(t.committed for t in got["record"].tickets)
    assert final > 2 * keep              # the program dropped versions
    assert got["sample"] and all(c > final - keep for c in got["sample"])
    assert set(correctness.FIRST_VERSIONS) <= set(got["fetched"])


def test_retention_short_of_the_sample_is_not_correct(monkeypatch):
    _keep_last(monkeypatch, 2)
    res = run_small(small_cell("paper-lstm.tcp1", volunteers=1))
    assert not res["correct"]
    assert res["checks"]["unchecked"]["value"] > 0
