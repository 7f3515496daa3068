"""Whether the served path trained correctly, judged against the plain
reference once the window has closed.

Two parts, both read from what the timed path produced:

* The first three commits. Set-up drives the served path from the seed
  through its first commits, with the window's own volunteers, gateway and
  applier; the reference follows the same three steps, computing each
  update on the mini-batch its ticket names, from the published version
  its volunteer computed at, and applying it onto the published version
  before it. Compared: each step's loss (the one the volunteer reported
  over the wire), the first gradient as the optimizer holds it
  (``sqrt(sum(ms) / (1 - rho))`` per leaf after one step), and the
  parameters' change over the three steps.
* Commits of the window, a sample drawn from the seed plus the final
  version, followed alike: the parameters' one-step change is compared.

Every version the reference starts from is fetched back over the wire.

Norms are compared by leaf: the gap between the program's norm and the
reference's, over the reference's norm of that leaf or of the median leaf,
whichever is larger. Leaves whose reference gradient (in the window: whose
reference step) is under a thousandth of the median leaf's are left out of
the changes. Exact checks of
the commit log (every version published once, every ticket committed
once, no update admitted beyond the staleness bound, no volunteer stuck)
have the limit 0.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

#: leaves whose reference gradient norm is under this share of the median
#: leaf's move by round-off alone and are left out of the changes
NEGLIGIBLE = 1e-3
#: what the numbers of the first part follow
FIRST = 3
NUMBERS = ("loss_gap", "grad1_gap", "change_gap", "step_gap")
EXACT = ("version_gaps", "ticket_repeats", "over_stale", "stuck")


def commit_log(tickets) -> Dict[int, Any]:
    """Committed version -> its ticket (the last one, if one repeats)."""
    return {t.committed: t for t in tickets if t.committed > 0}


def sample_commits(record, sample_seed: int, n: int) -> List[int]:
    """``n`` commits whose reply reached their volunteer in the window,
    drawn from the seed, and the final version."""
    inside = sorted(t.committed for t in record.tickets
                    if t.committed > 0 and record.in_window(t.t_reply))
    rng = np.random.default_rng(sample_seed)
    picked = rng.choice(inside, size=min(n, len(inside)), replace=False) \
        if inside else []
    final = max(t.committed for t in record.tickets)
    return sorted({int(c) for c in picked} | {final})


def versions_needed(commits: Dict[int, Any], sample: Iterable[int]) -> List[int]:
    want = set(range(FIRST + 1))
    for c in sample:
        want |= {c - 1, c, commits[c].base}
    return sorted(want)


# -- norms --------------------------------------------------------------------

def change_norms(after, before) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)
                                          - np.asarray(b, np.float64)))
                     for a, b in zip(jax.tree.leaves(after),
                                     jax.tree.leaves(before), strict=True)])


def grad_norms(ms, rho: float) -> np.ndarray:
    """Per-leaf gradient norms as RMSprop holds them after one step from
    zero state: ``ms = (1 - rho) g**2``."""
    return np.array([math.sqrt(float(np.sum(np.asarray(m, np.float64)))
                               / (1.0 - rho)) for m in jax.tree.leaves(ms)])


def kept(ref_grad: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding."""
    return ref_grad >= NEGLIGIBLE * np.median(ref_grad)


def worst_gap(prog: np.ndarray, ref: np.ndarray,
              keep: Optional[np.ndarray] = None) -> float:
    """Largest gap between the program's and the reference's leaf norms,
    over the larger of the reference's leaf norm and its median leaf."""
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / scale))


# -- readings -----------------------------------------------------------------

def _update(reference, kind: str, task: Tuple, state_at_base,
            precision: str, fault: Optional[str]):
    """What the reference makes of one ticket computed at ``state_at_base``:
    (loss, contribution). A gradient for ``map``, a k-step delta for
    ``local``."""
    if kind == "map":
        _, version, mb = task
        return reference.loss_and_grad(
            state_at_base[0], reference.batch(version, mb, fault=fault),
            precision)
    _, _, start, k = task
    delta, loss = reference.local_delta(state_at_base, start, k, precision,
                                        fault=fault)
    return loss, delta


def _apply(reference, kind: str, state, contribution, weight: float):
    if kind == "map":
        return reference.rmsprop(state, contribution)
    return reference.add_delta(state, contribution, weight)


def _params_change(after, before) -> List[np.ndarray]:
    return [np.asarray(a, np.float64) - np.asarray(b, np.float64)
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before),
                            strict=True)]


def _norms(leaves: List[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(x)) for x in leaves])


def readings(reference, record, commits, sample, fetched, params0, *,
             weight: float, stand_in: Optional[Tuple[str, Optional[str]]] = None
             ) -> Dict[str, float]:
    """The four numbers. Every commit ``c`` is followed from what the
    program published: the reference computes its update from the fetched
    version its volunteer computed at and applies it onto the fetched
    version ``c - 1``, so rounding cannot compound along a chain of the
    reference's own. ``stand_in = (precision, fault)`` puts the reference,
    computed in ``precision`` with ``fault`` planted, in the program's
    place: the control and the planted faults read so."""
    kind = record.kind
    rho = record.config["rho"]
    fetched = dict(fetched)
    fetched[0] = reference.zero_state(params0)

    def follow(c):
        """(reference's state after c, program's state after c, reference's
        loss, program's loss), both from the fetched versions."""
        t = commits[c]
        before, at_base = fetched[c - 1], fetched[t.base]
        loss_r, contrib = _update(reference, kind, t.task, at_base,
                                  "float32", None)
        want = _apply(reference, kind, before, contrib, weight)
        if stand_in is None:
            return want, fetched[c], loss_r, t.loss
        loss_p, alt = _update(reference, kind, t.task, at_base, *stand_in)
        return want, _apply(reference, kind, before, alt, weight), loss_r, \
            loss_p

    loss_gap = 0.0
    ref_change = prog_change = None
    for c in range(1, FIRST + 1):
        want, got, loss_r, loss_p = follow(c)
        loss_gap = max(loss_gap, abs(loss_p - loss_r) / abs(loss_r))
        if c == 1:
            g_ref = grad_norms(want[1]["ms"], rho)
            grad1_gap = worst_gap(grad_norms(got[1]["ms"], rho), g_ref)
        before = fetched[c - 1][0]
        steps = (_params_change(want[0], before), _params_change(got[0], before))
        if ref_change is None:
            ref_change, prog_change = steps
        else:
            ref_change = [a + b for a, b in zip(ref_change, steps[0])]
            prog_change = [a + b for a, b in zip(prog_change, steps[1])]
    change_gap = worst_gap(_norms(prog_change), _norms(ref_change), kept(g_ref))
    step_gap = 0.0
    for c in sample:
        want, got, _, _ = follow(c)
        before = fetched[c - 1][0]
        ref_step = change_norms(want[0], before)
        step_gap = max(step_gap, worst_gap(change_norms(got[0], before),
                                           ref_step, kept(ref_step)))
    return {"loss_gap": loss_gap, "grad1_gap": grad1_gap,
            "change_gap": change_gap, "step_gap": step_gap}


def exact(record, *, staleness: Optional[int], stuck: int) -> Dict[str, int]:
    """The commit log's exact checks: versions published once each and
    without a gap, tickets committed once each, admission within the
    staleness bound, and every volunteer back when the window closed."""
    versions = [t.committed for t in record.tickets if t.committed > 0]
    top = max(versions, default=0)
    gaps = (top - len(set(versions))) + (len(versions) - len(set(versions)))
    tasks = [t.task for t in record.tickets if t.committed > 0]
    over = 0
    if staleness is not None:
        over = sum(t.committed - 1 - t.base > staleness
                   for t in record.tickets if t.committed > 0)
    return {"version_gaps": gaps, "ticket_repeats": len(tasks) - len(set(tasks)),
            "over_stale": over, "stuck": stuck}


def judge(values: Dict[str, Any], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, Any]]:
    """Every number beside its limit, ``ok`` where it is within it (a NaN
    never is). The exact checks have the limit 0; a number without an
    entry in ``values`` is not judged."""
    out = {}
    for name in EXACT + NUMBERS:
        if name not in values:
            continue
        limit = 0 if name in EXACT else limits[name]
        value = values[name]
        ok = not math.isnan(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": ok}
    return out


def check(reference, record, commits, sample, fetched, params0, *,
          staleness: Optional[int], weight: float, stuck: int,
          limits: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """The exact checks and the four numbers of the program's run, each
    judged against its limit."""
    values: Dict[str, Any] = dict(exact(record, staleness=staleness,
                                        stuck=stuck))
    if all(c in commits for c in range(1, FIRST + 1)):
        values.update(readings(reference, record, commits, sample, fetched,
                               params0, weight=weight))
    else:
        values.update({n: math.inf for n in NUMBERS})
    return judge(values, limits)
