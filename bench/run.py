#!/usr/bin/env python3
"""The chip benchmark of the served training path: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout's root, on a machine with the chips the cell asks
for. The cell, its model configuration, traffic mix, correctness limits
and metrics are found by the names in ``BENCHMARK.json``. Standard error
carries the set-up, the compiles inside the window, device memory and,
last, every number the correctness check compared beside its limit. The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.

Where JAX finds no TPU, or fewer chips than the cell asks for, or the
program is not beside the benchmark, it exits non-zero and prints no
result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from jsdoop_bench import device, driver
    from jsdoop_bench.spec import load_cell

    cell = load_cell(args.workload)
    import jax
    import repro.core.gateway  # noqa: F401  (the program under test)
    devices = device.require_tpu(jax, cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)
    driver.enable_compile_cache(jax)
    result = driver.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START, devices=devices[:cell.chips],
                             peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
