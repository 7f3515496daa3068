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

Every version the reference starts from is fetched back over the wire
into a ``Versions`` store on disk: versions 1 to 3 during set-up, once
version 3 is published and before the window opens (the reference starts
from version 0 as the seed makes it); the window's after it has closed,
from the commits whose versions ``c - 1``, ``c`` and base the server still
holds then. The check keeps its own versions, and reads only those of the
commit it follows, so its host memory does not grow with the model's
retention or with ``checked_commits``. The exact check ``unchecked``
counts the sampled commits, and the final version, that the program's
retention left out.

Norms are compared by leaf: the gap between the program's norm and the
reference's, over the reference's norm of that leaf or of the median leaf,
whichever is larger. Leaves whose reference gradient (in the window: whose
reference step) is under a thousandth of the median leaf's are left out of
the changes. Exact checks of
the commit log (every version published once, every ticket committed
once, no update admitted beyond the staleness bound, no volunteer stuck,
every sampled commit checkable) have the limit 0.
"""
from __future__ import annotations

import math
import os
import sys
import tempfile
from collections.abc import Mapping
from pathlib import Path
from typing import AbstractSet, Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

#: leaves whose reference gradient norm is under this share of the median
#: leaf's move by round-off alone and are left out of the changes
NEGLIGIBLE = 1e-3
#: what the numbers of the first part follow
FIRST = 3
#: the versions they are followed from, fetched during set-up: commit c <=
#: FIRST was computed at a base before c, and version 0 is the seed's
FIRST_VERSIONS = tuple(range(1, FIRST + 1))
NUMBERS = ("loss_gap", "grad1_gap", "change_gap", "step_gap")
EXACT = ("version_gaps", "ticket_repeats", "over_stale", "stuck",
         "unchecked")


class Versions(Mapping):
    """Published versions fetched for the check, version -> host pytree,
    kept on disk leaf by leaf as each arrives, in a temporary directory
    under ``tempfile``'s (``TMPDIR``) that ``close()`` removes, logging
    the bytes spilled and the type of the filesystem they went to.
    Reading a version loads it anew, so the check holds only the versions
    it is reading."""

    def __init__(self):
        self._dir = tempfile.TemporaryDirectory(prefix="bench-versions-")
        self._trees: Dict[int, Any] = {}         # version -> tree structure
        self.bytes = 0                           # written to the directory

    def put(self, version: int, tree) -> None:
        leaves, treedef = jax.tree.flatten(tree)
        for i, leaf in enumerate(leaves):
            path = self._path(version, i)
            np.save(path, np.asarray(leaf), allow_pickle=False)
            self.bytes += path.stat().st_size
        self._trees[version] = treedef

    def _path(self, version: int, leaf: int) -> Path:
        return Path(self._dir.name) / f"{version}.{leaf}.npy"

    def __getitem__(self, version: int):
        treedef = self._trees[version]
        return jax.tree.unflatten(treedef, [
            np.load(self._path(version, i), allow_pickle=False)
            for i in range(treedef.num_leaves)])

    def __contains__(self, version) -> bool:
        return version in self._trees

    def __iter__(self):
        return iter(sorted(self._trees))

    def __len__(self) -> int:
        return len(self._trees)

    def close(self) -> None:
        print(f"versions spilled: {len(self._trees)} versions, {self.bytes} B "
              f"to {self._dir.name} ({filesystem(self._dir.name)})",
              file=sys.stderr, flush=True)
        self._trees.clear()
        self._dir.cleanup()


def filesystem(path) -> str:
    """The type of the filesystem ``path`` lies on, as ``/proc/mounts``
    gives it (``unknown`` where it cannot be read)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, fstype = line.split()[:3]
                mount = mount.replace("\\040", " ")
                inside = path == mount or path.startswith(
                    mount.rstrip("/") + "/")
                if inside and len(mount) >= len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind


def commit_log(tickets) -> Dict[int, Any]:
    """Committed version -> its ticket (the last one, if one repeats)."""
    return {t.committed: t for t in tickets if t.committed > 0}


def _needs(commits: Dict[int, Any], c: int) -> set:
    """The fetched versions commit ``c`` is followed from: ``c - 1``, ``c``
    and its volunteer's base, but for version 0, which is the seed's."""
    return {c - 1, c, commits[c].base} - {0}


def sample_commits(record, sample_seed: int, n: int, held: AbstractSet[int]
                   ) -> Tuple[List[int], int]:
    """``n`` commits whose reply reached their volunteer in the window,
    drawn from the seed, and the final version. A commit can be drawn only
    where ``held``, the versions the server still holds once the window
    has closed, holds the commit's versions; the final version's too.
    Returns the sample and its shortfall: how many of the final version
    and of the ``n`` (or of the window's commits, where it made fewer)
    could not be drawn for want of their versions."""
    commits = commit_log(record.tickets)

    def checkable(c: int) -> bool:
        return _needs(commits, c) <= held

    inside = sorted(t.committed for t in record.tickets
                    if t.committed > 0 and record.in_window(t.t_reply))
    population = [c for c in inside if checkable(c)]
    rng = np.random.default_rng(sample_seed)
    picked = rng.choice(population, size=min(n, len(population)),
                        replace=False) if population else []
    final = max(t.committed for t in record.tickets)
    shortfall = min(n, len(inside)) - len(picked) + (not checkable(final))
    return sorted({int(c) for c in picked}
                  | ({final} if checkable(final) else set())), shortfall


def versions_needed(commits: Dict[int, Any], sample: Iterable[int]) -> List[int]:
    """The fetched versions the window's sample is followed from."""
    want = set()
    for c in sample:
        want |= _needs(commits, c)
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
    zero = reference.zero_state(params0)

    def version(v):
        return zero if v == 0 else fetched[v]

    def follow(c):
        """(program's parameters before c, reference's state after c,
        program's state after c, reference's loss, program's loss), all from
        the fetched versions, each read once."""
        t = commits[c]
        before = version(c - 1)
        at_base = before if t.base == c - 1 else version(t.base)
        loss_r, contrib = _update(reference, kind, t.task, at_base,
                                  "float32", None)
        want = _apply(reference, kind, before, contrib, weight)
        if stand_in is None:
            return before[0], want, version(c), loss_r, t.loss
        loss_p, alt = _update(reference, kind, t.task, at_base, *stand_in)
        return before[0], want, _apply(reference, kind, before, alt, weight), \
            loss_r, loss_p

    loss_gap = 0.0
    ref_change = prog_change = None
    for c in range(1, FIRST + 1):
        before, want, got, loss_r, loss_p = follow(c)
        loss_gap = max(loss_gap, abs(loss_p - loss_r) / abs(loss_r))
        if c == 1:
            g_ref = grad_norms(want[1]["ms"], rho)
            grad1_gap = worst_gap(grad_norms(got[1]["ms"], rho), g_ref)
        ref_step = _params_change(want[0], before)
        prog_step = _params_change(got[0], before)
        if ref_change is None:
            ref_change, prog_change = ref_step, prog_step
        else:
            for total, step in zip(ref_change + prog_change,
                                   ref_step + prog_step):
                total += step
        # one commit's versions and changes at a time
        del before, want, got, ref_step, prog_step
    change_gap = worst_gap(_norms(prog_change), _norms(ref_change), kept(g_ref))
    step_gap = 0.0
    for c in sample:
        before, want, got, _, _ = follow(c)
        ref_step = change_norms(want[0], before)
        step_gap = max(step_gap, worst_gap(change_norms(got[0], before),
                                           ref_step, kept(ref_step)))
        del before, want, got
    return {"loss_gap": loss_gap, "grad1_gap": grad1_gap,
            "change_gap": change_gap, "step_gap": step_gap}


def exact(record, *, staleness: Optional[int], stuck: int,
          unchecked: int) -> Dict[str, int]:
    """The commit log's exact checks: versions published once each and
    without a gap, tickets committed once each, admission within the
    staleness bound, every volunteer back when the window closed, and
    ``unchecked``, the sample's shortfall (``sample_commits``)."""
    versions = [t.committed for t in record.tickets if t.committed > 0]
    top = max(versions, default=0)
    gaps = (top - len(set(versions))) + (len(versions) - len(set(versions)))
    tasks = [t.task for t in record.tickets if t.committed > 0]
    over = 0
    if staleness is not None:
        over = sum(t.committed - 1 - t.base > staleness
                   for t in record.tickets if t.committed > 0)
    return {"version_gaps": gaps, "ticket_repeats": len(tasks) - len(set(tasks)),
            "over_stale": over, "stuck": stuck, "unchecked": unchecked}


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
          limits: Dict[str, float], unchecked: int
          ) -> Dict[str, Dict[str, Any]]:
    """The exact checks and the four numbers of the program's run, each
    judged against its limit. Without the first commits or their versions
    (the program no longer held them when set-up fetched them) the numbers
    are infinite."""
    values: Dict[str, Any] = dict(exact(record, staleness=staleness,
                                        stuck=stuck, unchecked=unchecked))
    if all(c in commits and c in fetched for c in range(1, FIRST + 1)):
        values.update(readings(reference, record, commits, sample, fetched,
                               params0, weight=weight))
    else:
        values.update({n: math.inf for n in NUMBERS})
    return judge(values, limits)
