"""The comparison that decides ``correct``: silent on the reference's own
answers, and failing on a perturbed model, on the control one precision
below float32 at ``highest`` (three bfloat16 passes per product), on one
bfloat16 pass, and on planted faults."""
import json

import jax
import pytest

from benchtest import SMALL_LIMITS, sequential_log
from jsdoop_bench import correctness, spec
from jsdoop_bench.driver import RunRecord, Ticket

CFG = dict(spec.load_config("paper-lstm"), hidden_size=16)
N = 6
FIRST_SAMPLE = 2


@pytest.fixture(scope="module")
def served():
    return sequential_log(CFG, N)


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
    ok = correctness.exact(record, staleness=2, stuck=0)
    assert ok == {"version_gaps": 0, "ticket_repeats": 0, "over_stale": 0,
                  "stuck": 0}
    broken = list(record.tickets)
    broken[3] = Ticket("v", 4.0, 4.5, broken[2].task, 0, 5, 1.0)
    rec = RunRecord(**{**record.__dict__, "tickets": broken})
    bad = correctness.exact(rec, staleness=2, stuck=1)
    assert bad["version_gaps"] == 2          # version 4 lost, 5 published twice
    assert bad["ticket_repeats"] == 1
    assert bad["over_stale"] == 1            # computed at 0, applied onto 4
    assert bad["stuck"] == 1
    checks = correctness.check(reference, rec, correctness.commit_log(broken),
                               [5], fetched, params0, staleness=2, weight=1.0,
                               stuck=1, limits=SMALL_LIMITS)
    assert not all(c["ok"] for c in checks.values())


def test_sample_holds_the_final_version(served):
    _, record, commits, _, _ = served
    sample = correctness.sample_commits(record, 7, 3)
    assert max(sample) == N and len(sample) <= 4
    assert correctness.versions_needed(commits, sample)[:4] == [0, 1, 2, 3]


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_control_fails_the_cells_limits_at_full_width(cell):
    """The control, the reference with three bfloat16 passes per product in
    the program's place, at the cell's own widths and batch, judged against
    the cell's committed limits."""
    c = spec.load_cell(cell)
    served = sequential_log(c.config, 5, seed=31)
    reference, record, commits, fetched, params0 = served
    r = correctness.readings(reference, record, commits, [4, 5], fetched,
                             params0, weight=1.0, stand_in=("high", None))
    judged = correctness.judge(r, c.limits)
    assert not all(v["ok"] for v in judged.values()), (r, c.limits)
