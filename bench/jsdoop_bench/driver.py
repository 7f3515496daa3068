"""One run of one cell: set-up, the measured window, then the check.

The entry the window drives is the program's served training path in this
one process, which holds the chip: a ``GatewayServer(real_apply=True)`` on
loopback, and the traffic mix's volunteers as threads that speak the wire
protocol through the program's own client transports and drive the
program's ``VolunteerSession``. Each volunteer computes its update with the
program's ``TrainingProblem`` on the chip and leases its next ticket as soon
as its commit reply arrives (plus the mix's think time), so the loop is
closed.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from jsdoop_bench import correctness, trace as tracemod
from jsdoop_bench.inputs import Seeds, corpus
from jsdoop_bench.spec import ROOT, Cell, load_family, load_reader

#: device memory the run may fill with published versions before the
#: schedule of tickets runs out (the served path keeps every version)
RETAIN_BUDGET_BYTES = 12 * 2**30
#: and at most this many tickets, whose set-up the gateway pays at start
MAX_TICKETS = 50_000
#: fixed, inside the checkout: the cache's path is part of its key
CACHE_DIR = ROOT / ".bench_jax_cache"
WARMUP_TIMEOUT_S = 600.0
JOIN_TIMEOUT_S = 120.0


class RunFailed(RuntimeError):
    """The run could not be measured; it prints no result."""


# ---------------------------------------------------------------------------
# what the volunteers record
# ---------------------------------------------------------------------------

@dataclass
class Call:
    name: str           # request type
    t0: float           # host clock at the request
    dt: float           # seconds until its reply
    sent: int           # bytes written to the socket
    received: int       # bytes read from it


@dataclass
class Ticket:
    vid: str
    t_lease: float                      # host clock at the LeaseReq
    t_reply: float = math.nan           # host clock at the SubmitUpdate reply
    task: Tuple = ()                    # ("map", version, mb) | ("local", slot, start, k)
    base: int = -1                      # model version the update was computed at
    committed: int = -1                 # version it published; -1 if stale
    loss: float = math.nan              # loss the volunteer reported
    t_compute: float = math.nan         # host clock when its compute began
    calls: List[Call] = field(default_factory=list)


class CountingSocket:
    """A client socket that counts the bytes it moves."""

    def __init__(self, sock):
        self._sock = sock
        self.sent = 0
        self.received = 0

    def sendall(self, data) -> None:
        self._sock.sendall(data)
        self.sent += len(data)

    def recv(self, n: int) -> bytes:
        data = self._sock.recv(n)
        self.received += len(data)
        return data

    def __getattr__(self, name):
        return getattr(self._sock, name)


class MeteredPort:
    """The port a ``VolunteerSession`` calls: the program's transport, with
    each call timed and its bytes counted."""

    def __init__(self, transport):
        self.transport = transport
        self.sock = CountingSocket(transport.sock)
        transport.sock = self.sock
        self.calls: List[Call] = []

    def call(self, msg):
        s0, r0 = self.sock.sent, self.sock.received
        t0 = time.perf_counter()
        reply = self.transport.call(msg)
        self.calls.append(Call(type(msg).__name__, t0,
                               time.perf_counter() - t0,
                               self.sock.sent - s0, self.sock.received - r0))
        return reply


class CompileMeter:
    """Backend compiles as JAX reports them: when each ended, how long it
    took, and persistent-cache hits."""

    def __init__(self, jax):
        self.ends: List[float] = []
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.ends.append(time.perf_counter())
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.ends)


# ---------------------------------------------------------------------------
# the record the metric readers read
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    cell: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    kind: str                           # "map" (gradients) | "local" (deltas)
    setup_s: float
    window: Tuple[float, float]
    tickets: List[Ticket]
    counters: Dict[str, Dict[str, int]]  # applier counters at "t0" and "t1"
    peaks: Optional[Dict[str, float]]
    flops_per_update: float
    gradients_per_ticket: int
    applier_bytes_per_update: float
    trace: Optional[tracemod.TraceSummary] = None
    trace_window: Optional[Tuple[float, float]] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def window_tickets(self) -> List[Ticket]:
        """Every ticket leased in the window (its reply may come later)."""
        return [t for t in self.tickets if self.in_window(t.t_lease)]

    @property
    def window_commits(self) -> int:
        """Commits whose reply reached a volunteer in the window."""
        return sum(t.committed > 0 and self.in_window(t.t_reply)
                   for t in self.tickets)

    def window_calls(self, name: str) -> List[Call]:
        return [c for t in self.tickets for c in t.calls
                if c.name == name and self.in_window(c.t0)]

    def counter_delta(self, key: str, a: str = "t0", b: str = "t1") -> int:
        return self.counters[b][key] - self.counters[a][key]

    @property
    def trace_gradients(self) -> int:
        """Mini-batch gradients whose compute began in the traced window."""
        t0, t1 = self.trace_window
        per = self.gradients_per_ticket
        return sum(per for t in self.tickets if t0 <= t.t_compute < t1)

    @property
    def trace_commits(self) -> int:
        t0, t1 = self.trace_window
        return sum(t.committed > 0 and t0 <= t.t_reply < t1
                   for t in self.tickets)


def applier_counters(applier) -> Dict[str, int]:
    return {"applied": applier.applied, "rejected": applier.rejected,
            "batches": applier.batches,
            "batched_updates": applier.batched_updates}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache(jax, path=CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path, keeping every program: the
    served programs compile in under the default one-second threshold and
    would otherwise be compiled again by every run."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def ticket_cap(model_bytes: int) -> int:
    """Tickets to schedule: what fits in ``RETAIN_BUDGET_BYTES`` when every
    published version stays on the device, counted twice for the copy a
    fetch materializes, up to ``MAX_TICKETS``. Running out fails the run."""
    return min(MAX_TICKETS, RETAIN_BUDGET_BYTES // (2 * model_bytes))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, peaks: Optional[Dict[str, float]],
             tamper: Optional[Callable[[Any], None]] = None,
             stand_ins: Tuple[Tuple[str, Optional[str]], ...] = (),
             keep_trace: Optional[str] = None,
             look: Optional[Callable[..., Any]] = None) -> Dict[str, Any]:
    """Run ``cell`` once and return its result line (see ``run.py``).

    ``tamper(problem)`` breaks the program before the run (the tests plant
    faults so); each ``(precision, fault)`` of ``stand_ins`` also reads the
    check's numbers with the reference put in the program's place, judged
    against the cell's limits as the program's are, which is how the
    control and the planted faults are read for the limits.
    ``keep_trace`` is a path to copy the traced window's profile to, and
    ``look(reference, record, commits, sample, fetched, params0, weight)``
    is called once the check has run, to look into its numbers."""
    import jax
    from repro.core.aggregation import LocalSteps, make_policy
    from repro.core.gateway import GatewayServer, SocketTransport, \
        WsClientTransport
    from repro.core.protocol import (FetchModel, LocalWork, MapWork, NoTask,
                                     VolunteerSession)

    cfg, mix = cell.config, cell.traffic
    # the precision the configuration states, for every product the
    # program computes in this process
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    meter = CompileMeter(jax)
    family = load_family(cfg["family"])
    ref = family.REFERENCE
    seeds = Seeds.of(seed)
    policy = make_policy(mix["policy"])
    kind = "local" if isinstance(policy, LocalSteps) else "map"
    per_ticket = policy.k if kind == "local" else 1
    n_vol = int(mix["volunteers"])
    transport_cls = {"tcp": SocketTransport, "ws": WsClientTransport}[mix["dialect"]]
    model_bytes = 2 * 4 * ref.param_count(cfg)          # params + RMSprop ms
    n_mb = cfg["mini_batches_per_version"]
    n_versions = math.ceil(ticket_cap(model_bytes) * per_ticket / n_mb)

    text = corpus(cfg["corpus_chars"], seeds.text)
    params0 = jax.jit(lambda k: ref.init_params(cfg, k))(
        jax.random.PRNGKey(seeds.weights))
    problem = family.build_problem(cfg, text, seeds.schedule, params0,
                                   n_versions)
    if tamper is not None:
        tamper(problem)
    host_params0 = jax.tree.map(np.asarray, params0)
    if kind == "map":
        # every drain length the window can see: one submit per volunteer
        rows_of = np.zeros((n_vol, problem.pack_grads(params0).size),
                           np.float32)
        for n in range(1, n_vol + 1):
            carry = problem.flat_carry(problem.params0, problem.opt_state0)
            _, steps = problem.apply_batch_flat(carry, rows_of[:n], donate=True)
            jax.block_until_ready(problem.unflatten_step(steps, n - 1))
    server = GatewayServer(problem, n_versions=n_versions, policy=policy,
                           real_apply=True)
    applier = server.applier
    if trace:
        _annotate_server(server, jax)
    server.start()

    tickets: List[Ticket] = []
    done_by: Dict[str, int] = {}
    errors: List[BaseException] = []
    stop = threading.Event()
    think_s = float(mix["think_ms"]) / 1000.0
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: nullcontext())

    def volunteer(vid: str) -> None:
        try:
            tr = transport_cls("127.0.0.1", server.port, vid)
            port = MeteredPort(tr)
            sess = VolunteerSession(vid, port, policy=policy)
            try:
                while not stop.is_set():
                    t = Ticket(vid, time.perf_counter())
                    n_calls = len(port.calls)
                    with span("bench.lease"):
                        if isinstance(sess.lease(0.0), NoTask):
                            raise RunFailed("the schedule of tickets ran out")
                    with span("bench.fetch"):
                        out = sess.advance(0.0)
                    task = out.task
                    t.t_compute = time.perf_counter()
                    with span("bench.grad"):
                        if isinstance(out, MapWork):
                            t.task = ("map", task.version, task.mb_index)
                            g, loss = problem.map_compute(
                                out.model[0], task.version, task.mb_index)
                            res = sess.grad_result(g, problem.grad_bytes, loss)
                        elif isinstance(out, LocalWork):
                            t.task = ("local", task.slot, task.start, task.k)
                            delta, loss = problem.local_compute(
                                out.model[0], out.model[1], task.start, task.k)
                            res = sess.delta_result(delta, problem.model_bytes,
                                                    loss)
                        else:
                            raise RunFailed(f"{vid}: unexpected {out!r}")
                    t.base, t.loss = out.base_version, loss
                    with span("bench.submit"):
                        upd = sess.submit_update(res)
                    t.t_reply = time.perf_counter()
                    t.committed = -1 if upd.stale else upd.version
                    t.calls = port.calls[n_calls:]
                    tickets.append(t)
                    done_by[vid] = done_by.get(vid, 0) + 1
                    if think_s:
                        time.sleep(think_s)
                sess.bye()
            finally:
                tr.close()
        except BaseException as e:          # re-raised on the main thread
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=volunteer, args=(f"vol{i}",),
                                daemon=True) for i in range(n_vol)]
    for th in threads:
        th.start()
    deadline = time.perf_counter() + WARMUP_TIMEOUT_S
    while not errors and (len(done_by) < n_vol
                          or min(done_by.values()) < 2
                          or sum(t.committed > 0 for t in tickets)
                          < mix["warmup_commits"]):
        if time.perf_counter() > deadline:
            errors.append(RunFailed("warm-up did not finish"))
            break
        time.sleep(0.005)

    # everything set-up made lives on through the window: left out of the
    # collector's passes there, so that a full pass scans only what the
    # window allocates and weighs alike in every run
    gc.collect()
    gc.freeze()
    counters: Dict[str, Dict[str, int]] = {}
    summary = trace_window = None
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    counters["t0"] = applier_counters(applier)
    logdir = None
    if not errors and trace:
        logdir, trace_window = _traced(jax, float(mix["trace_seconds"]))
    t1 = t0 + seconds
    pauses = HostPauses()
    while not errors and time.perf_counter() < t1:
        pauses.nap(min(0.01, max(0.0, t1 - time.perf_counter())))
    counters["t1"] = applier_counters(applier)
    pauses.close()
    stop.set()
    gc.unfreeze()
    for th in threads:
        th.join(JOIN_TIMEOUT_S)
    stuck = sum(th.is_alive() for th in threads)
    if errors:
        server.close()
        raise errors[0]
    compiles = meter.between(t0, t1)
    if logdir is not None:
        try:
            path = tracemod.find_trace(logdir)
            if keep_trace:
                shutil.copyfile(path, keep_trace)
            summary = tracemod.reduce_file(path, family.PROGRAMS)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    stats = devices[0].memory_stats() or {}
    latest = server.ds.latest_version
    _log(f"setup {setup_s:.3f} s (compile {meter.seconds:.3f} s, persistent "
         f"cache hits {meter.hits}); window {seconds} s")
    _log(f"compiles inside the window: {compiles}")
    _log(f"device memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
         f"bytes_in_use {stats.get('bytes_in_use')}")
    _log(f"retained: {latest + 1} published versions x {model_bytes} B = "
         f"{(latest + 1) * model_bytes} B of parameters and RMSprop state "
         f"(every version stays on the device)")
    _log(pauses.summary())
    _log("commit_p95_ms by fifths of the window: " + " ".join(
        f"{v:.1f}" for v in _p95_by_part(tickets, t0, t1, 5)))

    record = RunRecord(
        cell=cell.name, config=cfg, traffic=mix, kind=kind, setup_s=setup_s,
        window=(t0, t1), tickets=sorted(tickets, key=lambda t: t.t_lease),
        counters=counters, peaks=peaks,
        flops_per_update=per_ticket * ref.flops_per_gradient(cfg),
        gradients_per_ticket=per_ticket,
        applier_bytes_per_update=ref.applier_bytes_per_update(cfg, kind),
        trace=summary, trace_window=trace_window)

    # -- the check, once the window has closed ------------------------------
    commits = correctness.commit_log(record.tickets)
    sample = correctness.sample_commits(record, seeds.sample,
                                        mix["checked_commits"])
    wanted = correctness.versions_needed(commits, sample)
    checker = SocketTransport("127.0.0.1", server.port, "checker")
    try:
        fetched = {}
        for v in wanted:
            reply = checker.call(FetchModel(v))
            if not reply.present:
                raise RunFailed(f"published version {v} is not fetchable")
            fetched[v] = jax.tree.map(np.asarray, tuple(reply.blob))
    finally:
        checker.close()
    server.close()
    del server, applier, problem, params0
    gc.collect()
    reference = ref.Reference(cfg, text, seeds.schedule, n_versions)
    weight = getattr(policy, "weight", 1.0)
    checks = correctness.check(reference, record, commits, sample, fetched,
                               host_params0,
                               staleness=getattr(policy, "staleness", None),
                               weight=weight, stuck=stuck, limits=cell.limits)
    extra = {f"{p}/{f}": correctness.judge(correctness.readings(
        reference, record, commits, sample, fetched, host_params0,
        weight=weight, stand_in=(p, f)), cell.limits) for p, f in stand_ins}
    if look is not None:
        look(reference, record, commits, sample, fetched, host_params0, weight)

    metric_specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_specs:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result: Dict[str, Any] = {
        "correct": all(c["ok"] for c in checks.values()),
        # a volunteer still out at the close holds a ticket with no reply
        "attempted": len(record.window_tickets) + stuck,
        "failed": stuck,
        "metrics": metrics, "device": device}
    if summary is not None:
        _log("device seconds by layer in the traced window: " + ", ".join(
            f"{k} {v!r}" for k, v in sorted(summary.layers.items())))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    for name, c in checks.items():
        _log(f"check {name} {c['value']!r} limit {c['limit']!r} "
             f"{'ok' if c['ok'] else 'FAILED'}")
    if extra:
        result["stand_in_readings"] = extra
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                        for n, c in checks.items()}
    return result


class HostPauses:
    """What held the host up inside the window: the main thread's longest
    oversleep (the host or the GIL kept it from running) and the garbage
    collector's passes. Logged only, to explain runs that read far off."""

    def __init__(self):
        self.oversleep = 0.0
        self.gc: List[Tuple[int, float]] = []   # (generation, seconds)
        self._gc_t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc.append((info["generation"],
                            time.perf_counter() - self._gc_t0))

    def nap(self, s: float) -> None:
        t = time.perf_counter()
        time.sleep(s)
        self.oversleep = max(self.oversleep, time.perf_counter() - t - s)

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> str:
        full = [s for g, s in self.gc if g == 2]
        return (f"host pauses in the window: longest oversleep "
                f"{self.oversleep * 1e3:.1f} ms; gc {len(self.gc)} passes, "
                f"{sum(s for _, s in self.gc) * 1e3:.1f} ms, longest "
                f"{max((s for _, s in self.gc), default=0.0) * 1e3:.1f} ms; "
                f"{len(full)} full, {sum(full) * 1e3:.1f} ms")


def _p95_by_part(tickets: List[Ticket], t0: float, t1: float,
                 parts: int) -> List[float]:
    """The commit wait's 95th percentile over tickets leased in each of
    ``parts`` equal stretches of the window, in ms (nan where none)."""
    from jsdoop_bench.stats import percentile
    step = (t1 - t0) / parts
    out = []
    for i in range(parts):
        a, b = t0 + i * step, t0 + (i + 1) * step
        waits = [t.t_reply - t.t_lease for t in tickets
                 if a <= t.t_lease < b and not math.isnan(t.t_reply)]
        out.append(percentile(waits, 95) * 1e3 if waits else math.nan)
    return out


def _traced(jax, seconds: float):
    """Profile ``seconds`` of the window into a temporary directory.
    Returns the directory and the traced window on the host clock; the
    trace is read once the window has closed."""
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN):
            w0 = time.perf_counter()
            time.sleep(seconds)
            w1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    return logdir, (w0, w1)


def _annotate_server(server, jax) -> None:
    """Host spans around the gateway's drain and request dispatch, from
    outside the program: the endpoint's two entry points, wrapped."""
    endpoint = server.endpoint
    submit_batch, handle = endpoint.submit_batch, endpoint.handle

    def drained(msgs):
        with jax.profiler.TraceAnnotation("bench.drain"):
            return submit_batch(msgs)

    def handled(msg):
        with jax.profiler.TraceAnnotation(
                "bench.serve_fetch" if type(msg).__name__ == "FetchModel"
                else "bench.serve"):
            return handle(msg)

    endpoint.submit_batch, endpoint.handle = drained, handled
