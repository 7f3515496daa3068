"""The chip a run is on, and its published peaks."""
from __future__ import annotations

import json
from typing import Dict, List

from jsdoop_bench.spec import BENCH_DIR


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(jax, chips: int) -> List:
    """The TPU devices JAX found, or ``NoAccelerator``. There is no CPU
    fallback: a number from a CPU run is never reported as a device's."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` from ``peaks.json``. A device
    that is not in the table is an error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json (have {sorted(table)})")
    return table[device_kind]
