#!/usr/bin/env python3
"""Chip smoke test: the served training path and every Pallas kernel, once,
on one TPU chip, in one process.

    python chip_smoke.py

Phases:

1. served path — the paper's 2x50-cell LSTM at its published width and
   training parameters, on a seeded synthetic corpus, served by an
   in-process ``GatewayServer(real_apply=True)`` on a loopback port:
   (a) one real-compute volunteer commits 32 updates over TCP, the final
   model is fetched over the wire and compared with ``sequential_async``
   run on the same chip; (b) three racing volunteers finish the same run.
   The applier's hot state must live on the chip.
2. kernels — each Pallas kernel of ``repro.kernels.ops``, compiled
   (``interpret=False``) at a width its callers use, against
   ``repro.kernels.ref``.

Earlier lines report each phase's wall and compile seconds; they are a
smoke test's log, not measurements. The last line of stdout is one JSON
object naming the device. Any failed phase exits non-zero, and so does a
host where JAX finds no TPU: there is no CPU fallback.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import place_compile_cache  # noqa: E402
from repro.core.gateway import serve_real_run  # noqa: E402
from repro.core.mapreduce import TrainingProblem, sequential_async  # noqa: E402
from repro.data.text import synthetic_corpus  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

SEED = 0
POLICY = "staleness:2"
N_VERSIONS = 2                  # x 16 mini-batches = 32 committed updates

# Pallas TPU dots on f32 operands may run as bf16 MXU passes, so float
# outputs are held to the bf16 bound the CPU kernel tests use for bf16.
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)


def require_tpu():
    """The devices JAX found, or exit non-zero when they are not TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); this test has no CPU "
                         f"fallback")
    return devices


def build_problem(d_model=None) -> TrainingProblem:
    """The paper problem (2 layers, paper TrainParams) on a seeded corpus,
    so its shapes never depend on files on disk; ``d_model=None`` keeps the
    published 50 cells."""
    return TrainingProblem.paper_problem(
        seed=SEED, corpus=synthetic_corpus(50_000, seed=SEED),
        d_model=d_model)


def _max_abs_diff(a, b) -> float:
    diffs = jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64)
                                         - np.asarray(y, np.float64)),
                                  initial=0.0)), a, b))
    return max(diffs)


def _bit_equal(a, b) -> bool:
    return jax.tree.all(jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b))


def served_path(problem: TrainingProblem, platform: str) -> dict:
    """Phase 1: serve the run twice (one volunteer, then three racing) and
    check it against the same-process ``sequential_async`` reference.
    ``platform`` is where the applier's hot state must live."""
    server, results, blob = serve_real_run(problem, ["v0"],
                                           n_versions=N_VERSIONS,
                                           policy=POLICY)
    n_updates = server.n_updates
    if results["v0"][0] != n_updates or server.observed_version != n_updates:
        raise AssertionError(f"served run ended at {results['v0'][0]} "
                             f"(server v{server.observed_version}), "
                             f"expected v{n_updates}")
    carry = jax.tree.leaves(server.applier.backend._carry)
    where = {d.platform for leaf in carry for d in leaf.devices()}
    if where != {platform}:
        raise AssertionError(f"applier state lives on {where}, "
                             f"not {platform}")
    ref_p, ref_s, _ = sequential_async(problem, n_updates=n_updates)
    # The applier folds a drain with one lax.scan over the flat (params,
    # optimizer state) carry; sequential_async applies apply_one per pytree
    # leaf. They are separately compiled programs, yet the served model is
    # bit-equal to the reference on the CPU and on a v5e chip alike, so bits
    # are what is checked: no tolerance.
    diff = _max_abs_diff(blob, (ref_p, ref_s))
    if not _bit_equal(blob, (ref_p, ref_s)):
        raise AssertionError(f"served model is not bit-equal to "
                             f"sequential_async (max |diff| {diff!r})")
    _, race, _ = serve_real_run(problem, ["r0", "r1", "r2"],
                                n_versions=N_VERSIONS, policy=POLICY)
    finals = sorted(v[0] for v in race.values())
    if finals != [n_updates] * 3:
        raise AssertionError(f"racing volunteers ended at {finals}, "
                             f"expected v{n_updates}")
    return {"updates": n_updates, "max_abs_diff": diff, "race_finals": finals,
            "applier_platform": platform}


def _keys(n: int, salt: int):
    return jax.random.split(jax.random.PRNGKey(SEED * 1000 + salt), n)


# Each case builds seeded inputs and returns (kernel, reference, inputs);
# the kernel takes ``interpret`` as its keyword.
def _lstm_case(B, Din, H):
    ks = _keys(5, Din + H)
    args = (jax.random.normal(ks[0], (B, Din)),
            jax.random.normal(ks[1], (B, H)),
            jax.random.normal(ks[2], (B, H)),
            # Glorot-scaled like models/lstm.py, so the gates' pre-activations
            # stay O(1) at every width
            jax.random.normal(ks[3], (Din + H, 4 * H)) * (Din + H) ** -0.5,
            jax.random.normal(ks[4], (4 * H,)) * 0.1)
    return ops.lstm_cell, ref.lstm_cell, args


def _rmsnorm_case(rows, D):
    ks = _keys(2, D)
    args = (jax.random.normal(ks[0], (rows, D), jnp.bfloat16),
            jax.random.normal(ks[1], (D,), jnp.bfloat16))
    return ops.rmsnorm, ref.rmsnorm, args


def _flash_case(B, S, H, Kv, hd):
    ks = _keys(3, S + H)
    args = tuple((jax.random.normal(kk, (B, S, n, hd)) * 0.5)
                 .astype(jnp.bfloat16) for kk, n in zip(ks, (H, Kv, Kv)))
    return ops.flash_attention, ref.flash_attention, args


def _ternary_kernel(g, s, *, interpret):
    packed = ops.ternary_encode(g, s, interpret=interpret)
    return packed, ops.ternary_decode(packed, s, interpret=interpret)


def _ternary_ref(g, s):
    t = ref.ternary_encode(g, s)
    return ref.ternary_pack(t), t.astype(jnp.float32) * s


def _ternary_case(n):
    g = jax.random.normal(_keys(1, n)[0], (n,))
    return _ternary_kernel, _ternary_ref, (g, jnp.max(jnp.abs(g)))


_CASES = {"lstm_cell": _lstm_case, "rmsnorm": _rmsnorm_case,
          "flash_attention": _flash_case, "ternary": _ternary_case}


def real_width_cases(vocab: int):
    """``(label, kernel, shape)`` at the widths the kernels' callers use:
    the paper's own LSTM cell (B=8, Din=vocab, H=50), a 512-cell LSTM, a
    2048-wide bf16 norm, GQA 8/4 attention at S=2048 with 128-wide heads,
    and ternary codes for a 64k-element gradient."""
    return [
        ("lstm_cell paper", "lstm_cell", dict(B=8, Din=vocab, H=50)),
        ("lstm_cell 512", "lstm_cell", dict(B=128, Din=512, H=512)),
        ("rmsnorm", "rmsnorm", dict(rows=4096, D=2048)),
        ("flash_attention", "flash_attention",
         dict(B=1, S=2048, H=8, Kv=4, hd=128)),
        ("ternary", "ternary", dict(n=65536)),
    ]


def kernels(cases, interpret: bool = False) -> list:
    """Phase 2: each case's kernel output against its reference, at full
    f32 matmul precision; ternary codes must match exactly. Prints one line
    per case and raises once every case has run if any failed. Returns
    ``[(label, max_abs_err)]``."""
    report, failed = [], []
    for label, name, shape in cases:
        kernel, reference, args = _CASES[name](**shape)
        out = jax.block_until_ready(kernel(*args, interpret=interpret))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(reference(*args))
        err, verdict = 0.0, "ok"
        try:
            for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
                o, w = np.asarray(o), np.asarray(w)
                if o.shape != w.shape or o.dtype != w.dtype:
                    raise AssertionError(f"{o.shape}/{o.dtype} vs reference "
                                         f"{w.shape}/{w.dtype}")
                err = max(err, _max_abs_diff(o, w))
                if name == "ternary":
                    np.testing.assert_array_equal(o, w)
                else:
                    np.testing.assert_allclose(o.astype(np.float32),
                                               w.astype(np.float32),
                                               **KERNEL_TOL)
        except AssertionError as e:
            failed.append(f"{label}: {e}")
            verdict = "FAILED"
        print(f"kernel {label} {shape}: max |kernel - ref| {err!r} "
              f"{verdict}", flush=True)
        report.append((label, err))
    if failed:
        raise AssertionError("kernels differ from kernels/ref.py:\n"
                             + "\n".join(failed))
    return report


class CompileMeter:
    """Seconds JAX spent in backend compiles (a persistent-cache hit counts
    only its read) and the number of cache hits, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    t0, c0, h0 = time.perf_counter(), meter.seconds, meter.hits
    yield
    print(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, compile "
          f"{meter.seconds - c0:.3f} s, persistent cache hits "
          f"{meter.hits - h0}", flush=True)


def main() -> int:
    devices = require_tpu()
    cache = place_compile_cache()
    meter = CompileMeter()
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)
    with phase("build", meter):
        problem = build_problem()
    print(f"model: {problem.cfg.n_layers} x {problem.cfg.d_model} LSTM, "
          f"vocab {problem.cfg.vocab}, "
          f"{sum(x.size for x in jax.tree.leaves(problem.params0))} "
          f"parameters", flush=True)
    with phase("served path", meter):
        facts = served_path(problem, "tpu")
    print(f"served path: {facts['updates']} updates through the gateway; "
          f"bit-equal to sequential_async (max |diff| "
          f"{facts['max_abs_diff']!r}); racing volunteers ended at "
          f"{facts['race_finals']}; applier state on "
          f"{facts['applier_platform']}", flush=True)
    with phase("kernels", meter):
        kernels(real_width_cases(problem.cfg.vocab))
    print(f"compile total {meter.seconds:.3f} s, persistent cache hits "
          f"{meter.hits}", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
