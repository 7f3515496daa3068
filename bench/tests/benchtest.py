"""Shared helpers of the benchmark's own tests: small cells that run on
the CPU through the same harness as the chip's."""
from __future__ import annotations

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from jsdoop_bench import spec  # noqa: E402

#: limits for the small CPU cells: the program in float32 on the CPU reads
#: under 1e-7 on every number against the float32 reference (six runs of
#: 2x8 and 2x16), the control with three bfloat16 passes per product 3e-6
#: or more on at least one number, and each fault 1e-2 or more
SMALL_LIMITS = {"loss_gap": 1e-6, "grad1_gap": 1e-6, "change_gap": 1e-6,
                "step_gap": 1e-6}


def small_cell(name: str = "paper-lstm.ws32", *, hidden: int = 8,
               volunteers: int = 4, policy=None) -> spec.Cell:
    """The cell ``name`` with the width cut to ``hidden`` and ``volunteers``
    volunteers, so that a run fits a test on the CPU."""
    cell = spec.load_cell(name)
    cell.config = dict(cell.config, hidden_size=hidden)
    cell.traffic = dict(cell.traffic, volunteers=volunteers, warmup_commits=8,
                        checked_commits=4)
    if policy is not None:
        cell.traffic["policy"] = policy
    cell.limits = dict(SMALL_LIMITS)
    return cell


def run_small(cell: spec.Cell, *, seed: int = 2**31 + 11, seconds: float = 1.0,
              tamper=None):
    """One run of ``cell`` on the CPU (the harness's device check is the
    only part left out)."""
    import jax
    from jsdoop_bench import driver
    return driver.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(), devices=jax.devices(),
                           peaks=None, tamper=tamper)


def sequential_log(cfg, n: int, seed: int = 123):
    """A commit log of ``n`` sequential gradient commits, each computed at
    the version before it, and the versions the float32 reference itself
    publishes along it: a served run as the reference would have made it.
    Returns ``(reference, record, commits, fetched, params0)``."""
    import jax
    import numpy as np
    from jsdoop_bench import correctness
    from jsdoop_bench.driver import RunRecord, Ticket
    from jsdoop_bench.inputs import Seeds, corpus

    ref = spec.load_family(cfg["family"]).REFERENCE
    seeds = Seeds.of(seed)
    reference = ref.Reference(cfg, corpus(cfg["corpus_chars"], seeds.text),
                              seeds.schedule, 64)
    params0 = jax.tree.map(np.asarray, jax.jit(
        lambda k: ref.init_params(cfg, k))(jax.random.PRNGKey(seeds.weights)))
    state = reference.zero_state(params0)
    fetched, tickets = {0: state}, []
    for c in range(1, n + 1):
        loss, g = reference.loss_and_grad(state[0], reference.batch(c - 1, 0))
        state = reference.rmsprop(state, g)
        fetched[c] = jax.tree.map(np.asarray, state)
        tickets.append(Ticket("v", float(c), float(c) + 0.5,
                              ("map", c - 1, 0), c - 1, c, loss))
    record = RunRecord(cell="t", config=cfg, traffic={}, kind="map",
                       setup_s=0.0, window=(0.0, 1e9), tickets=tickets,
                       counters={}, peaks=None, flops_per_update=0.0,
                       gradients_per_ticket=1, applier_bytes_per_update=0.0)
    return reference, record, correctness.commit_log(tickets), fetched, params0
