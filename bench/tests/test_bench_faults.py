"""A whole run on the CPU, with the timed path broken underneath: the
check must come out false for every fault a training cell can have, and
true when nothing is broken."""
import jax
import jax.numpy as jnp
import pytest

from benchtest import run_small, small_cell


def _state_unchanged(problem):
    """The applier's step returns the state it was given."""
    def apply_batch_flat(carry, rows, donate=True):
        steps = jax.tree.map(lambda a: jnp.stack([a] * len(rows)), carry)
        return carry, steps
    problem.apply_batch_flat = apply_batch_flat


def _half_batch(problem):
    """Half of each mini-batch is left out; the mean is over the rest."""
    whole = problem.minibatch

    def minibatch(version, mb_index):
        b = whole(version, mb_index)
        return {k: v[:len(v) // 2] for k, v in b.items()}
    problem.minibatch = minibatch


def _token_altered(problem):
    """One label of each mini-batch is altered where the batch is made."""
    whole = problem.minibatch

    def minibatch(version, mb_index):
        b = dict(whole(version, mb_index))
        y = b["y"].copy()
        y[0] = (y[0] + 1) % problem.cfg.vocab
        b["y"] = y
        return b
    problem.minibatch = minibatch


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


def test_sound_run_is_correct():
    res = run_small(small_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"updates_per_s", "commit_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(fault):
    res = run_small(small_cell(), tamper=FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_local_steps_run_is_checked_through_the_delta_path():
    # one volunteer: with two or more, the program's LocalSteps deltas of
    # RMSprop's state drive it negative and the model to NaN (PERF.md)
    cell = small_cell(volunteers=1, policy="local:4")
    # four local steps at lr 0.1 inside each commit, chained on each side:
    # float32 round-off grows to 1.5e-5 (grad1_gap), 3e-5 (change_gap) and
    # 7e-5 (step_gap) over six runs
    cell.limits.update(grad1_gap=1e-3, change_gap=1e-3, step_gap=1e-3)
    res = run_small(cell)
    assert res["correct"], res["checks"]
