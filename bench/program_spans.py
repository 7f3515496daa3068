#!/usr/bin/env python3
"""Runs of one cell with the program's own spans (``repro.obs``) switched
on, and the numbers that read them.

    python3 bench/program_spans.py --workload <cell> --seeds 1,2 --seconds 51 --trace 1

Each seed is one run of the cell in this process, as ``run.py`` makes it,
except that ``repro.obs.enable()`` comes before the gateway is built (so
its dispatch lock is timed too). Each prints one JSON line: ``correct``,
the cell's end-to-end metrics, the per-layer metrics that read the spans
(``lock_wait_p95_ms``, ``lock_held_share``, ``fetch_serve_ms``,
``codec_ms_per_update``, ``drain_host_us_per_update``, ``step_host_ms``),
``fetch``: what a ``FetchModel`` call is made of (``spans.fetch_coverage``)
beside the mean time at the volunteer's port, ``under``: the self times
under the fetch serves and the drains, and with ``--trace 1`` the cell's
other per-layer metrics and ``breakdown`` with ``idle_by_program_span``.
Standard error also carries the table of every span begun in the window.

``--obs 0`` leaves the spans off: the same runs, to weigh what the spans
cost. A program without ``repro.obs`` runs as with ``--obs 0``.
``--keep-trace PATH`` copies the first traced run's profile to ``PATH``;
``--trace-seconds`` shortens its traced stretch. The benchmark's own runs
(``run.py``) never run this. Where JAX finds no TPU it exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

SPAN_METRICS = ("lock_wait_p95_ms", "lock_held_share", "fetch_serve_ms",
                "codec_ms_per_update", "drain_host_us_per_update",
                "step_host_ms")


def _obs(on: bool):
    """``repro.obs``, switched on, or None where it is off or the program
    has none."""
    if not on:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    obs.enable()
    obs.take()
    return obs


def one_run(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
            devices, peaks, on: bool = True, keep_trace=None, log=None):
    """One run of ``cell``; returns its JSON line (see the module
    docstring)."""
    from jsdoop_bench import driver, spans
    from jsdoop_bench.spec import load_reader

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    obs = _obs(on)
    kept = None
    if trace:
        fd, kept = tempfile.mkstemp(suffix=".xplane.pb")
        os.close(fd)
    got = {}
    try:
        res = driver.run_cell(
            cell, seed, seconds, trace, t_start=t_start, devices=devices,
            peaks=peaks, keep_trace=kept,
            look=lambda *a: got.update(record=a[1]))
        record = got["record"]
        record.spans = obs.take() if obs is not None else None
        line = {"workload": cell.name, "seed": seed, "correct": res["correct"],
                "obs": obs is not None, "device": res["device"]}
        metrics = {m["name"]: load_reader(m["name"])(record)
                   for m in cell.end_to_end}
        if trace:
            metrics.update({k: v["value"] for k, v in res["metrics"].items()})
        metrics.update({m: load_reader(m)(record) for m in SPAN_METRICS})
        line["metrics"] = metrics
        port = [c.dt for c in record.window_calls("FetchModel")]
        if record.spans is not None:
            w = spans.window_ns(record)
            line["fetch"] = dict(spans.fetch_coverage(record.spans, w),
                                 port_mean_ms=sum(port) / len(port) * 1e3
                                 if port else None)
            line["under"] = _under(record, w)
            log("program spans begun in the window (name, count, total s, "
                "p50 ms, p95 ms, self s):")
            for row in spans.table(record.spans, w):
                log("  " + " ".join(f"{x:.6g}" if isinstance(x, float)
                                    else str(x) for x in row))
        if trace:
            line["breakdown"] = dict(
                res.get("breakdown", {}),
                idle_by_program_span=spans.idle_by_program_span_file(kept))
            if keep_trace:
                shutil.copyfile(kept, keep_trace)
        return line
    finally:
        if obs is not None:
            obs.disable()
            obs.take()
        if kept:
            os.unlink(kept)


def _under(record, window):
    """Self seconds by span name under the ``FetchModel`` serves and under
    the drains begun in the window, per fetch served and per update
    applied."""
    import numpy as np
    from jsdoop_bench import spans

    tree = spans.Tree(record.spans)
    inside = (tree.start >= window[0]) & (tree.start < window[1])
    out = {}
    serves = record.spans.get("repro.serve")
    if serves is not None:
        fetch = set(serves.id[serves.attrs["type"] == "FetchModel"].tolist())
        roots = np.nonzero(inside & np.isin(tree.id, list(fetch)))[0]
        if len(roots):
            out["fetch_serve_ms"] = {k: v / len(roots) * 1e3 for k, v in
                                     tree.under(roots).items()}
    roots = np.nonzero(inside & (tree.name == "repro.drain"))[0]
    applied = record.counter_delta("applied")
    if len(roots) and applied:
        out["drain_us_per_update"] = {k: v / applied * 1e6 for k, v in
                                      tree.under(roots).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
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
        line = one_run(cell, seed, args.seconds, bool(args.trace),
                       t_start=t_start, devices=devices[:cell.chips],
                       peaks=peaks, on=bool(args.obs),
                       keep_trace=args.keep_trace if n == 0 else None)
        print(json.dumps(line), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
