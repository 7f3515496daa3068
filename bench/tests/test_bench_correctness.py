"""The comparison that decides ``correct``: silent on the reference's own
answers, and failing on a perturbed model, on the control one precision
below float32 at ``highest`` (three bfloat16 passes per product), on one
bfloat16 pass, and on planted faults."""
import json
import pathlib
import time

import jax
import pytest

from benchtest import (BENCH, SMALL_LIMITS, OverBudget, check_budget,
                       cpu_config, sequential_log)
from jsdoop_bench import correctness, spec
from jsdoop_bench.driver import RunRecord, Ticket

CFG = dict(spec.load_config("paper-lstm"), hidden_size=16)
N = 6
FIRST_SAMPLE = 2


@pytest.fixture(scope="module")
def served():
    return sequential_log(CFG, N, spill=False)


def _readings(served, fetched=None, stand_in=None):
    reference, record, commits, base_fetched, params0 = served
    sample = list(range(FIRST_SAMPLE, N + 1))
    return correctness.readings(reference, record, commits, sample,
                                fetched or base_fetched, params0, weight=1.0,
                                stand_in=stand_in)


def test_reference_against_itself_reads_nought(served):
    r = _readings(served)
    assert all(v < 1e-6 for v in r.values()), r


def test_perturbed_model_fails(served):
    fetched = dict(served[3])
    params, opt = fetched[5]
    bad = jax.tree.map(lambda a: a, params)
    bad["head"]["w"] = bad["head"]["w"] * 1.05 + 0.01
    fetched[5] = (bad, opt)
    r = _readings(served, fetched)
    assert r["step_gap"] > SMALL_LIMITS["step_gap"], r


@pytest.mark.parametrize("stand_in", [("high", None), ("bfloat16", None),
                                      ("float32", "half"),
                                      ("float32", "token")],
                         ids=["high-control", "bf16-control", "half-batch",
                              "token-altered"])
def test_control_and_faults_fail(served, stand_in):
    r = _readings(served, stand_in=stand_in)
    judged = correctness.judge(r, SMALL_LIMITS)
    assert not all(c["ok"] for c in judged.values()), r


def test_exact_checks_count_what_went_wrong(served):
    reference, record, commits, fetched, params0 = served
    ok = correctness.exact(record, staleness=2, stuck=0, unchecked=0)
    assert ok == {"version_gaps": 0, "ticket_repeats": 0, "over_stale": 0,
                  "stuck": 0, "unchecked": 0}
    broken = list(record.tickets)
    broken[3] = Ticket("v", 4.0, 4.5, broken[2].task, 0, 5, 1.0)
    rec = RunRecord(**{**record.__dict__, "tickets": broken})
    bad = correctness.exact(rec, staleness=2, stuck=1, unchecked=2)
    assert bad["version_gaps"] == 2          # version 4 lost, 5 published twice
    assert bad["ticket_repeats"] == 1
    assert bad["over_stale"] == 1            # computed at 0, applied onto 4
    assert bad["stuck"] == 1
    assert bad["unchecked"] == 2
    checks = correctness.check(reference, rec, correctness.commit_log(broken),
                               [5], fetched, params0, staleness=2, weight=1.0,
                               stuck=1, limits=SMALL_LIMITS, unchecked=0)
    assert not all(c["ok"] for c in checks.values())
    # a sound log with a sample short of what was asked
    checks = correctness.check(reference, record, commits, [5], fetched,
                               params0, staleness=2, weight=1.0, stuck=0,
                               limits=SMALL_LIMITS, unchecked=1)
    assert [n for n, c in checks.items() if not c["ok"]] == ["unchecked"]


def test_sample_holds_the_final_version(served):
    _, record, commits, _, _ = served
    sample, shortfall = correctness.sample_commits(record, 7, 3,
                                                   set(range(N + 1)))
    assert max(sample) == N and len(sample) <= 4 and shortfall == 0
    assert correctness.FIRST_VERSIONS == (1, 2, 3)
    needed = correctness.versions_needed(commits, sample)
    assert all({c - 1, c} - {0} <= set(needed) for c in sample)


@pytest.mark.parametrize("held,n,shortfall", [
    (range(0, N + 1), 3, 0),
    (range(3, N + 1), 3, 0),        # commits 4 to 6 can be followed
    (range(4, N + 1), 3, 1),        # only 5 and 6
    (range(N - 1, N + 1), 3, 2),    # only the final version
    (range(N, N + 1), 3, 4),        # not even the final version
    (range(0, N + 1), N + 2, 0),    # the window made fewer than asked
    (range(4, N + 1), N + 2, N - 2),
], ids=["all", "last-4", "last-3", "last-2", "last-1", "few-all",
        "few-last-3"])
def test_sample_is_drawn_from_what_the_program_holds(served, held, n,
                                                     shortfall):
    _, record, commits, _, _ = served
    sample, short = correctness.sample_commits(record, 7, n, set(held))
    assert short == shortfall
    assert set(correctness.versions_needed(commits, sample)) <= set(held)
    assert (N in sample) == (N - 1 in held)


@pytest.mark.parametrize("stand_in", [None, ("high", None)],
                         ids=["program", "high-control"])
def test_spilled_readings_equal_in_memory_readings(served, stand_in):
    reference, record, commits, fetched, params0 = served
    sample = list(range(FIRST_SAMPLE, N + 1))
    versions = correctness.Versions()
    try:
        for v in sorted(fetched):
            if v:
                versions.put(v, fetched[v])
        assert sorted(versions) == list(range(1, N + 1)) and 0 not in versions
        spilled = correctness.readings(reference, record, commits, sample,
                                       versions, params0, weight=1.0,
                                       stand_in=stand_in)
    finally:
        versions.close()
    in_memory = _readings(served, stand_in=stand_in)
    assert spilled == in_memory
    assert not versions._trees and not pathlib.Path(versions._dir.name).exists()


@pytest.mark.parametrize("stand_in", [None, ("high", None)],
                         ids=["program", "high-control"])
def test_sequential_log_spilled_equals_in_memory(served, stand_in):
    spilled = sequential_log(CFG, N)
    fetched = spilled[3]
    try:
        assert isinstance(fetched, correctness.Versions)
        assert sorted(fetched) == list(range(1, N + 1))
        assert _readings(spilled, stand_in=stand_in) \
            == _readings(served, stand_in=stand_in)
    finally:
        fetched.close()


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_control_fails_the_cells_limits_at_full_width(cell):
    """The control, the reference with three bfloat16 passes per product in
    the program's place, at the cell's own widths and batch, judged against
    the cell's committed limits. A configuration with a ``"cpu"`` form
    runs at that form (widths as published, within the CPU budget), and
    its cells' limits files carry the control's full-size chip reading."""
    c = spec.load_cell(cell)
    served = sequential_log(cpu_config(c.config), 5, seed=31)
    reference, record, commits, fetched, params0 = served
    try:
        r = correctness.readings(reference, record, commits, [4, 5], fetched,
                                 params0, weight=1.0, stand_in=("high", None))
    finally:
        fetched.close()
    judged = correctness.judge(r, c.limits)
    assert not all(v["ok"] for v in judged.values()), (r, c.limits)


#: paper-lstm at hidden size 4096 (202,137,638 parameters), with the CPU
#: form of one layer
WIDE = json.loads((BENCH / "tests" / "fixtures" / "paper-lstm-4096.json")
                  .read_text())


def test_over_the_budget_fails_at_once_naming_the_cpu_form():
    cfg = {k: v for k, v in WIDE.items() if k != "cpu"}
    t = time.perf_counter()
    with pytest.raises(OverBudget, match='"cpu" object') as e:
        sequential_log(cfg, 5, seed=31)
    assert time.perf_counter() - t < 1.0
    assert "202,137,638 parameters" in str(e.value)


def test_cpu_form_is_within_the_budget():
    cfg = cpu_config(WIDE)
    assert cfg["num_layers"] == 1 and cfg["hidden_size"] == 4096
    check_budget(cfg)
