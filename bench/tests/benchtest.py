"""Shared helpers of the benchmark's own tests: small cells that run on
the CPU through the same harness as the chip's."""
from __future__ import annotations

import pathlib
import re
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from jsdoop_bench import spec  # noqa: E402

#: keys of a configuration that are widths (the model-configs guide, section
#: 4): cut neither in ``reduced`` nor in a CPU form
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection|window|"
                    r"_dim$|_rank$|head|expansion|experts_per_tok)")
#: the most one CPU case may compute, checked before it computes anything:
#: parameters (the family's ``param_count``) and FLOPs of one gradient
#: (``flops_per_gradient``). The control's case at both (one LSTM layer of
#: 4,440 cells over 41 characters: 79.7M parameters, 1.57e11 FLOPs) took
#: 67 s and 6.9 GB of peak RSS alone on an 8-core CPU, where a test may
#: take 90 s and 8 GB (PERF.md, section 4).
CPU_MAX_PARAMS = 80_000_000
CPU_MAX_FLOPS = 1.6e11

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
              tamper=None, look=None):
    """One run of ``cell`` on the CPU (the harness's device check is the
    only part left out)."""
    import jax
    from jsdoop_bench import driver
    return driver.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(), devices=jax.devices(),
                           peaks=None, tamper=tamper, look=look)


class OverBudget(ValueError):
    """A CPU case that would compute more than the budget allows."""


def cpu_config(cfg):
    """``cfg`` as the CPU tests run it: the overrides of its ``"cpu"``
    object applied, and the object itself left out. A CPU form may cut
    depth, the experts held, the vocabulary's rows, the sequence length and
    the mini-batch; it may not change a width, since the rounding gap that
    the control exposes grows with the contraction lengths, nor name a key
    the configuration does not have. Only the CPU tests read it: a run on
    the chip runs the configuration as it stands."""
    form = cfg.get("cpu", {})
    for key in form:
        if key not in cfg or key == "cpu":
            raise spec.SpecError(f"{cfg['name']}: its \"cpu\" form sets "
                                 f"{key!r}, which the configuration does "
                                 f"not have")
        if WIDTHS.search(key):
            raise spec.SpecError(f"{cfg['name']}: its \"cpu\" form sets "
                                 f"{key!r}, a width; widths stay as "
                                 f"published on the CPU too")
    return {**{k: v for k, v in cfg.items() if k != "cpu"}, **form}


def check_budget(cfg) -> None:
    """Raise ``OverBudget`` where one gradient of ``cfg`` is over the CPU
    budget, naming the ``"cpu"`` form that would bring it within."""
    ref = spec.load_family(cfg["family"]).REFERENCE
    params, flops = ref.param_count(cfg), ref.flops_per_gradient(cfg)
    if params > CPU_MAX_PARAMS or flops > CPU_MAX_FLOPS:
        raise OverBudget(
            f"{cfg['name']}: {params:,} parameters and {flops:.4g} FLOPs a "
            f"gradient, over the CPU budget of {CPU_MAX_PARAMS:,} and "
            f"{CPU_MAX_FLOPS:.4g}: give bench/configs/{cfg['name']}.json a "
            f"\"cpu\" object of overrides that cuts depth, the experts held, "
            f"the vocabulary's rows, the sequence length or the mini-batch "
            f"(never a width) to within it")


def sequential_log(cfg, n: int, seed: int = 123, *, spill: bool = True):
    """A commit log of ``n`` sequential gradient commits, each computed at
    the version before it, and the versions the float32 reference itself
    publishes along it: a served run as the reference would have made it.
    ``cfg`` is first held to the CPU budget. The versions are kept in a
    ``correctness.Versions`` spill, as a run keeps those it fetches (the
    caller closes it), or with ``spill=False`` in a dict.
    Returns ``(reference, record, commits, fetched, params0)``."""
    check_budget(cfg)
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
    fetched = correctness.Versions() if spill else {}
    keep = fetched.put if spill else fetched.__setitem__
    tickets = []
    for c in range(1, n + 1):
        loss, g = reference.loss_and_grad(state[0], reference.batch(c - 1, 0))
        state = reference.rmsprop(state, g)
        keep(c, jax.tree.map(np.asarray, state))
        tickets.append(Ticket("v", float(c), float(c) + 0.5,
                              ("map", c - 1, 0), c - 1, c, loss))
    record = RunRecord(cell="t", config=cfg, traffic={}, kind="map",
                       setup_s=0.0, window=(0.0, 1e9), tickets=tickets,
                       counters={}, peaks=None, flops_per_update=0.0,
                       gradients_per_ticket=1, applier_bytes_per_update=0.0)
    return reference, record, correctness.commit_log(tickets), fetched, params0
