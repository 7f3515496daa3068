#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, one run of the cell in this process (set-up, a short window
at the cell's own load, the check), printing one JSON line with the
program's readings of every compared number and, for the same commits,
those of the reference put in the program's place: computed with three
bfloat16 passes per product (the control one step below the float32 the
configuration states), with one pass, and in float32 with half of each
mini-batch left out or one label altered (planted faults). Each is judged
against the cell's committed limits as the program's run is.

``--look`` adds, for each checked commit, the worst leaf of its one-step
change and the elements that differ most there, with the reference's
RMSprop state beside them. ``--keep-trace PATH`` makes the first seed's
run a traced one and copies its profile to ``PATH``. The benchmark's own
runs never run this. Where JAX finds no TPU it exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

STAND_INS = (("high", None), ("bfloat16", None), ("float32", "half"),
             ("float32", "token"))


def look(reference, record, commits, sample, fetched, params0, weight):
    """The worst leaf of each checked commit's one-step change, and its
    five elements whose change differs most from the reference's."""
    import jax
    import numpy as np
    from jsdoop_bench import correctness

    fetched = dict(fetched)
    fetched[0] = reference.zero_state(params0)
    out = []
    for c in sorted(set(range(1, correctness.FIRST + 1)) | set(sample)):
        t = commits[c]
        before, at_base = fetched[c - 1], fetched[t.base]
        loss, grad = correctness._update(reference, record.kind, t.task,
                                         at_base, "float32", None)
        want = correctness._apply(reference, record.kind, before, grad,
                                  weight)
        ref_step = correctness.change_norms(want[0], before[0])
        prog_step = correctness.change_norms(fetched[c][0], before[0])
        keep = correctness.kept(ref_step)
        scale = np.maximum(ref_step, np.median(ref_step[keep]))
        gap = np.where(keep, np.abs(prog_step - ref_step) / scale, 0.0)
        i = int(np.argmax(gap))
        leaf = lambda tree: np.asarray(jax.tree.leaves(tree)[i],
                                       np.float64).ravel()
        d_prog = leaf(fetched[c][0]) - leaf(before[0])
        d_ref = leaf(want[0]) - leaf(before[0])
        top = np.argsort(-np.abs(d_prog - d_ref))[:5]
        out.append({
            "commit": c, "base": t.base, "leaf": i,
            "leaf_size": int(d_ref.size), "gap": float(gap[i]),
            "ref_norm": float(ref_step[i]), "prog_norm": float(prog_step[i]),
            "median_norm": float(np.median(ref_step)),
            "elements": [{
                "prog_step": float(d_prog[j]), "ref_step": float(d_ref[j]),
                "grad": float(leaf(grad)[j]),
                "ms_before": float(leaf(before[1]["ms"])[j]),
                "ms_ref": float(leaf(want[1]["ms"])[j]),
                "ms_prog": float(leaf(fetched[c][1]["ms"])[j])}
                for j in top]})
    print(json.dumps({"look": out}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--look", action="store_true")
    ap.add_argument("--keep-trace")
    ap.add_argument("--trace-seconds", type=float)
    args = ap.parse_args(argv)

    from jsdoop_bench import device, driver
    from jsdoop_bench.spec import load_cell

    cell = load_cell(args.workload)
    if args.trace_seconds:
        cell.traffic = dict(cell.traffic, trace_seconds=args.trace_seconds)
    import jax
    devices = device.require_tpu(jax, cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)
    driver.enable_compile_cache(jax)
    t_start = T_START
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        traced = bool(args.keep_trace) and n == 0
        res = driver.run_cell(cell, seed, args.seconds, traced,
                              t_start=t_start, devices=devices[:cell.chips],
                              peaks=peaks, stand_ins=STAND_INS,
                              keep_trace=args.keep_trace if traced else None,
                              look=look if args.look else None)
        line = {"workload": args.workload, "seed": seed,
                "correct": res["correct"],
                "program": {n: c["value"] for n, c in res["checks"].items()}}
        for name, judged in res["stand_in_readings"].items():
            line[name] = {"correct": all(c["ok"] for c in judged.values()),
                          **{n: c["value"] for n, c in judged.items()}}
        line["metrics"] = res["metrics"]
        print(json.dumps(line), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
