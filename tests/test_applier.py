"""Batched real-JAX server applier: bit-exactness of the drained fast path,
drain-edge semantics (rejection ordering, gc interplay, empty drains),
measured publish sizes, lazy blob materialization, and the simulator's
dispatch-cost pipeline.

The load-bearing contract: ``submit_batch`` over a ``make_real_applier``
must land on the SAME BITS as ``sequential_async`` / chained ``apply_one``
for every drain split and both applier modes — batching is a pure latency
optimization, invisible in replies and in model bytes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_lstm import TrainParams
from repro.core.aggregation import make_policy
from repro.core.applier import LazyModelBlob, RealApplier, make_real_applier
from repro.core.dataserver import DataServer
from repro.core.mapreduce import (TrainingProblem, sequential_async,
                                  sequential_local)
from repro.core.protocol import (FetchModel, ModelBlob, ServerEndpoint,
                                 SubmitUpdate, UpdateCommitted,
                                 UpdateRejected, wire_size)
from repro.core.queue import QueueServer
from repro.core.simulator import (CostModel, Simulator, SyntheticProblem,
                                  VolunteerSpec)
from repro.core.tasks import DeltaResult, GradResult, INITIAL_QUEUE
from repro.data.text import synthetic_corpus
from repro.optim import rmsprop

N = 12  # updates per staged chain — enough for multi-segment drains


@pytest.fixture(scope="module")
def problem():
    tp = TrainParams(batch_size=32, examples_per_epoch=256, num_epochs=1,
                     sample_len=40, mini_batch_size=8,
                     mini_batches_to_accumulate=4)
    return TrainingProblem.paper_problem(corpus=synthetic_corpus(20_000),
                                         tp=tp, seed=0, d_model=8)


@pytest.fixture(scope="module")
def grads(problem):
    """g_i computed at params_i along the reference chain, as numpy (the
    wire-deserialized form the server actually sees)."""
    p, s = problem.params0, problem.opt_state0
    out = []
    for i in range(N):
        v, mb = problem.stream_slot(i)
        g, _ = problem.map_compute(p, v, mb)
        out.append(jax.tree.map(np.asarray, g))
        p, s = problem.apply_one(p, s, g)
    return out


@pytest.fixture(scope="module")
def ref(problem):
    p, s, _ = sequential_async(problem, n_updates=N)
    return p, s


def bit_eq(a, b) -> bool:
    return bool(jax.tree.all(jax.tree.map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)))


def fresh_endpoint(problem, *, batch, policy="staleness:2", gc_keep=None):
    qs, ds = QueueServer(), DataServer()
    qs.declare(INITIAL_QUEUE, timeout=float("inf"))
    ds.publish_model(0, (problem.params0, problem.opt_state0), nbytes=0)
    applier = make_real_applier(problem, make_policy(policy), batch=batch,
                                gc_keep=gc_keep)
    return ServerEndpoint(qs, ds, applier=applier), qs, ds, applier


def submit(endpoint, qs, results, *, split):
    """Drive ``results`` through ``submit_batch`` in drains of the given
    sizes, leasing a real ticket per message."""
    replies = []
    it = iter(results)
    for size in split:
        msgs = []
        for r in (next(it) for _ in range(size)):
            qs.publish(INITIAL_QUEUE, "t")
            tag, _ = qs.lease(INITIAL_QUEUE, "w", 0.0)
            msgs.append(SubmitUpdate(INITIAL_QUEUE, tag, r))
        replies.extend(endpoint.submit_batch(msgs))
    return replies


def grad_results(grads):
    return [GradResult(version=i, mb_index=0, payload=g, computed_at=i)
            for i, g in enumerate(grads)]


# ---------------------------------------------------------------------------
# bit-exactness matrix: drain splits x applier modes == sequential_async
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("split", [[1] * N, [4] * (N // 4), [N],
                                   [1, 2, 3, 6], [5, 7]],
                         ids=["ones", "fours", "whole", "ragged", "two"])
@pytest.mark.parametrize("batch", [False, True], ids=["plain", "batched"])
def test_drained_grads_bit_match_sequential(problem, grads, ref, split,
                                            batch):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch)
    replies = submit(endpoint, qs, grad_results(grads), split=split)
    assert [r.version for r in replies] == list(range(1, N + 1))
    assert all(isinstance(r, UpdateCommitted) for r in replies)
    blob = endpoint.handle(FetchModel(N)).blob
    assert bit_eq(blob, ref)
    assert ap.applied == N and ap.rejected == 0
    if batch:
        expect = sum(1 for s in split if s >= 2)
        assert ap.batches == expect
        assert ap.batched_updates == sum(s for s in split if s >= 2)
    else:
        assert ap.batches == 0 and ap.batched_updates == 0


def test_intermediate_versions_bit_match_sequential(problem, grads):
    """EVERY published version — not just the last — matches the reference
    prefix chain, whichever drain split produced it."""
    p, s = problem.params0, problem.opt_state0
    prefixes = []
    for g in grads[:6]:
        p, s = problem.apply_one(p, s, g)
        prefixes.append((p, s))
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    submit(endpoint, qs, grad_results(grads[:6]), split=[2, 4])
    for v in range(1, 7):
        assert bit_eq(endpoint.handle(FetchModel(v)).blob, prefixes[v - 1])


def test_delta_chain_bit_matches_sequential_local(problem):
    k, n_rounds = 4, 4
    refp, refs, _ = sequential_local(problem, k=k, n_updates=n_rounds)
    p, s = problem.params0, problem.opt_state0
    deltas = []
    for slot in range(n_rounds):
        d, _ = problem.local_compute(p, s, slot * k, k)
        deltas.append(jax.tree.map(np.asarray, d))
        p, s = problem.apply_delta(p, s, d)
    results = [DeltaResult(slot=i, computed_at=i, payload=d)
               for i, d in enumerate(deltas)]
    for batch in (False, True):
        endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch)
        submit(endpoint, qs, results, split=[n_rounds])
        assert bit_eq(endpoint.handle(FetchModel(n_rounds)).blob,
                      (refp, refs))


def test_mixed_grad_delta_drain_segments(problem, grads):
    """A drain mixing result kinds splits into homogeneous segments; only
    the grad segment (>= 2 elements) rides the batched dispatch, and the
    result bit-matches the fully sequential chain."""
    p, s = problem.params0, problem.opt_state0
    for g in grads[:3]:
        p, s = problem.apply_one(p, s, g)
    d, _ = problem.local_compute(p, s, 0, 2)
    p_ref, s_ref = problem.apply_delta(p, s, d)
    results = grad_results(grads[:3]) + [
        DeltaResult(slot=0, computed_at=3, payload=jax.tree.map(np.asarray, d))]
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    replies = submit(endpoint, qs, results, split=[4])
    assert all(isinstance(r, UpdateCommitted) for r in replies)
    assert bit_eq(endpoint.handle(FetchModel(4)).blob, (p_ref, s_ref))
    assert ap.batches == 1 and ap.batched_updates == 3  # grads only


# ---------------------------------------------------------------------------
# drain-edge semantics
# ---------------------------------------------------------------------------

def test_rejection_mid_drain_nacks_front_in_order(problem, grads):
    """Element i is admitted against the version it would have observed
    sequentially; a rejected element reports that version, its ticket goes
    back to the FRONT of the queue, and later elements still commit."""
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True,
                                          policy="staleness:0")
    results = grad_results(grads[:4])
    # stale second element: computed_at=0 but it would apply onto v1
    results[1] = dataclasses.replace(results[1], computed_at=0)
    results[0] = dataclasses.replace(results[0], computed_at=0)
    results[2] = dataclasses.replace(results[2], computed_at=1)
    results[3] = dataclasses.replace(results[3], computed_at=2)
    replies = submit(endpoint, qs, results, split=[4])
    assert isinstance(replies[0], UpdateCommitted) and replies[0].version == 1
    assert isinstance(replies[1], UpdateRejected) and replies[1].latest == 1
    assert isinstance(replies[2], UpdateCommitted) and replies[2].version == 2
    assert isinstance(replies[3], UpdateCommitted) and replies[3].version == 3
    assert ap.applied == 3 and ap.rejected == 1
    # the nacked ticket is back at the front, ahead of anything later
    qs.publish(INITIAL_QUEUE, "later")
    tag, body = qs.lease(INITIAL_QUEUE, "w2", 0.0)
    assert body == "t"
    # and the committed chain is still the exact sequential one (the stale
    # gradient was dropped, not misapplied)
    p, s = problem.params0, problem.opt_state0
    for g in (grads[0], grads[2], grads[3]):
        p, s = problem.apply_one(p, s, g)
    assert bit_eq(endpoint.handle(FetchModel(3)).blob, (p, s))


def test_all_rejected_drain_publishes_nothing(problem, grads):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True,
                                          policy="staleness:0")
    # advance to v1 so computed_at=0 submissions are all stale
    submit(endpoint, qs, grad_results(grads[:1]), split=[1])
    writes_before, latest_before = ds.writes, ds.latest_version
    stale = [dataclasses.replace(r, computed_at=0)
             for r in grad_results(grads[1:4])]
    replies = submit(endpoint, qs, stale, split=[3])
    assert all(isinstance(r, UpdateRejected) for r in replies)
    assert all(r.latest == 1 for r in replies)
    assert ds.writes == writes_before and ds.latest_version == latest_before
    assert ap.applied == 1 and ap.rejected == 3
    assert ap.batches == 0  # no admitted run, no dispatch


def test_empty_drain_is_a_noop(problem):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    writes_before = ds.writes
    assert endpoint.submit_batch([]) == []
    assert ds.writes == writes_before and ap.applied == 0


def test_gc_keep_prunes_same_survivors_as_sequential(problem, grads):
    """gc runs ONCE at drain end; the surviving version set must equal the
    sequential (gc-after-every-publish) endpoint's, and the kept blobs must
    be fetchable (a drain must never publish an already-donated buffer)."""
    survivors = {}
    for batch, split in ((False, [1] * 6), (True, [6])):
        endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch,
                                              gc_keep=2)
        submit(endpoint, qs, grad_results(grads[:6]), split=split)
        survivors[batch] = sorted(ds._models)
        for v in survivors[batch]:
            blob = endpoint.handle(FetchModel(v)).blob
            jax.block_until_ready(jax.tree.leaves(blob))
    assert survivors[False] == survivors[True] == [5, 6]


def test_gc_keep_across_multiple_drains(problem, grads):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True, gc_keep=3)
    submit(endpoint, qs, grad_results(grads[:8]), split=[4, 4])
    assert sorted(ds._models) == [6, 7, 8]


# ---------------------------------------------------------------------------
# measured publish sizes (satellite: model_nbytes measured on each publish)
# ---------------------------------------------------------------------------

def test_model_nbytes_measured_matches_wire_encoding(problem, grads, ref):
    for batch in (False, True):
        endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch)
        assert ap.model_nbytes == 0  # nothing measured yet
        bytes_before = ds.bytes_written
        submit(endpoint, qs, grad_results(grads[:4]), split=[4])
        blob = endpoint.handle(FetchModel(4)).blob
        expect = wire_size(ModelBlob(0, True, blob))
        assert ap.model_nbytes == expect > 0
        # every one of the 4 publishes was accounted at the measured size
        assert ds.bytes_written - bytes_before == 4 * expect


def test_measured_nbytes_identical_across_modes(problem, grads):
    sizes = []
    for batch in (False, True):
        endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch)
        submit(endpoint, qs, grad_results(grads[:2]), split=[2])
        sizes.append(ap.model_nbytes)
    assert sizes[0] == sizes[1]


# ---------------------------------------------------------------------------
# lazy blob materialization
# ---------------------------------------------------------------------------

def test_batched_publishes_are_lazy_and_fetch_materializes(problem, grads):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    submit(endpoint, qs, grad_results(grads[:4]), split=[4])
    stored = [ds._models[v] for v in (2, 3)]
    assert all(isinstance(b, LazyModelBlob) for b in stored)
    reply = endpoint.handle(FetchModel(3))
    assert not isinstance(reply.blob, LazyModelBlob)
    p, s = reply.blob
    assert jax.tree.leaves(p)  # a real params pytree


def test_snapshot_solidifies_lazy_blobs(problem, grads):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    submit(endpoint, qs, grad_results(grads[:3]), split=[3])
    snap = ds.snapshot()
    for v, blob in snap["models"]:
        assert not isinstance(blob, LazyModelBlob)
    ds2 = DataServer()
    ds2.restore(snap)
    assert ds2.latest_version == 3


def test_reseed_restores_applier_state(problem, grads, ref):
    """Snapshot-restore path: reseeding from the stored latest blob lets the
    applier continue the chain bit-exactly."""
    for batch in (False, True):
        endpoint, qs, ds, ap = fresh_endpoint(problem, batch=batch)
        submit(endpoint, qs, grad_results(grads[:6]), split=[3, 3])
        backend2 = RealApplier(problem, batch=batch)
        backend2.reseed(ds.get_model(6), 6)
        blobs = backend2._advance(
            [GradResult(version=6 + i, mb_index=0, payload=g,
                        computed_at=6 + i)
             for i, g in enumerate(grads[6:])], 6)
        last = blobs[-1]
        last = last.materialize() if isinstance(last, LazyModelBlob) else last
        assert bit_eq(last, ref)


# ---------------------------------------------------------------------------
# flat-batch kernel: donation + packing + step unflatten
# ---------------------------------------------------------------------------

def test_apply_batch_matches_chained_apply_one(problem, grads):
    p, s = problem.params0, problem.opt_state0
    outs = problem.apply_batch(p, s, grads[:5])
    assert len(outs) == 5
    for i in range(5):
        p, s = problem.apply_one(p, s, grads[i])
        assert bit_eq(outs[i], (p, s))


def test_donated_apply_one_matches_plain(problem, grads):
    p0, s0 = problem.params0, problem.opt_state0
    plain = problem.apply_one(p0, s0, grads[0])
    # donate from an owned copy (donating problem.params0 would destroy it)
    own = jax.tree.map(lambda x: x + 0, (p0, s0))
    donated = problem.apply_one(own[0], own[1], grads[0], donate=True)
    assert bit_eq(plain, donated)


def test_pack_grad_rows_matches_per_row_pack(problem, grads):
    rows = problem.pack_grad_rows(grads[:5])
    expect = np.stack([problem.pack_grads(g) for g in grads[:5]])
    assert rows.shape == expect.shape
    assert np.array_equal(rows, expect)


def _eager_row(problem, steps, i):
    fp_s, vec_s, scal_s = steps
    return problem.unflatten_carry(
        (fp_s[i], {k: v[i] for k, v in vec_s.items()},
         {k: v[i] for k, v in scal_s.items()}))


@pytest.mark.parametrize("n,i", [(n, i) for n in (1, 3, 8) for i in range(n)])
def test_unflatten_step_matches_eager_slice(problem, grads, n, i):
    """A version copied to the host in one transfer: read-only NumPy leaves,
    bit-equal to the device's own slice of the row, the step still int32."""
    carry = problem.flat_carry(problem.params0, problem.opt_state0)
    rows = problem.pack_grad_rows(grads[:n])
    _, steps = problem.apply_batch_flat(carry, rows, donate=False)
    got = problem.unflatten_step(steps, i)
    assert bit_eq(got, _eager_row(problem, steps, i))
    for leaf in jax.tree.leaves(got):
        assert type(leaf) is np.ndarray and not leaf.flags.writeable
    step = got[1]["step"]
    assert step.dtype == np.int32 and int(step) == i + 1


def test_unflatten_step_packs_narrower_words(problem, grads):
    """bfloat16 weights and state beside the int32 step pack into 16-bit
    words, and come back bit for bit."""
    opt = rmsprop(0.1, state_dtype=jnp.bfloat16)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), problem.params0)
    narrow = dataclasses.replace(problem, optimizer=opt, params0=p16,
                                 opt_state0=opt.init(p16))
    assert narrow._row_layout[1] == np.uint16
    carry = narrow.flat_carry(narrow.params0, narrow.opt_state0)
    _, steps = narrow.apply_batch_flat(carry, narrow.pack_grad_rows(grads[:3]),
                                       donate=False)
    for i in range(3):
        got = narrow.unflatten_step(steps, i)
        assert bit_eq(got, _eager_row(narrow, steps, i))
        assert jax.tree.leaves(got[0])[0].dtype == jnp.bfloat16


def test_host_copies_count_distinct_versions_fetched(problem, grads):
    endpoint, qs, ds, ap = fresh_endpoint(problem, batch=True)
    submit(endpoint, qs, grad_results(grads[:4]), split=[4])
    backend = ap.backend
    # measuring the first publish's wire size copied version 1
    assert backend.host_copies == 1
    # version 0 was published eager and needs no copy
    for v, copies in [(3, 2), (3, 2), (2, 3), (1, 3), (0, 3), (2, 3),
                      (4, 4)]:
        endpoint.handle(FetchModel(v))
        assert backend.host_copies == copies


def test_warmed_drain_length_fetches_without_a_compile(problem, grads):
    """Warming ``unflatten_step`` at drain length n, as the benchmark's
    set-up does, leaves nothing to compile when a version from a drain of
    that length is first fetched; a length left unwarmed compiles then."""
    fresh = dataclasses.replace(problem)        # jit caches of its own
    rows = np.zeros((3, fresh.pack_grads(fresh.params0).size), np.float32)
    for n, warm_fetch in [(3, True), (2, False)]:
        carry = fresh.flat_carry(fresh.params0, fresh.opt_state0)
        _, steps = fresh.apply_batch_flat(carry, rows[:n], donate=True)
        if warm_fetch:
            jax.block_until_ready(fresh.unflatten_step(steps, n - 1))
    endpoint, qs, ds, ap = fresh_endpoint(fresh, batch=True)
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        submit(endpoint, qs, grad_results(grads[:3]), split=[3])
        endpoint.handle(FetchModel(3))
        assert compiles == []
        submit(endpoint, qs, [GradResult(version=3 + i, mb_index=0,
                                         payload=g, computed_at=3 + i)
                              for i, g in enumerate(grads[3:5])], split=[2])
        assert compiles == []
        endpoint.handle(FetchModel(5))
        assert compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert ap.backend.host_copies == 3        # versions 1 (measured), 3, 5


def test_flat_carry_round_trips(problem):
    carry = problem.flat_carry(problem.params0, problem.opt_state0)
    p, s = problem.unflatten_carry(carry)
    assert bit_eq((p, s), (problem.params0, problem.opt_state0))


def test_supports_flat_apply_gates_batch_mode(problem):
    assert problem.supports_flat_apply
    assert make_real_applier(problem, make_policy("staleness:2"),
                             batch=True).backend.batch is True
    off = make_real_applier(problem, make_policy("staleness:2"), batch=False)
    assert off.backend.batch is False and off.apply_batch is None


def test_applier_refuses_version_skew(problem, grads):
    backend = RealApplier(problem, batch=True)
    with pytest.raises(ValueError, match="only writer"):
        backend._advance(grad_results(grads[:2]), 5)


# ---------------------------------------------------------------------------
# simulator dispatch-cost pipeline
# ---------------------------------------------------------------------------

def _sim(server_apply, dispatch_cost=0.0, k=3):
    problem = SyntheticProblem(n_versions=4, n_mb=6, model_bytes=1.0e6,
                               grad_bytes=1.0e5)
    specs = [VolunteerSpec(f"v{i}", speed=1.0 + 0.1 * i) for i in range(k)]
    cost = CostModel(dispatch_cost=dispatch_cost)
    return Simulator(problem, specs, cost=cost, policy="staleness:2",
                     server_apply=server_apply)


def test_zero_dispatch_cost_is_bit_identical():
    """dispatch_cost=0.0 (the default) must leave server-applied runs
    untouched — same result dataclass, no dispatch accounting."""
    base = _sim(True).run()
    sim = _sim(True, dispatch_cost=0.0)
    again = sim.run()
    assert dataclasses.asdict(base) == dataclasses.asdict(again)
    assert sim.apply_dispatches == 0 and sim.batched_dispatch_credits == 0


def test_positive_dispatch_cost_pools_commits():
    """With a serial dispatch cost, concurrent arrivals pool into pending
    dispatches (batched credits) and the makespan stretches, but the run
    still completes every update."""
    sim = _sim(True, dispatch_cost=0.05, k=6)
    res = sim.run()
    assert res.final_version == 24
    assert sim.apply_dispatches > 0
    assert sim.batched_dispatch_credits > 0
    assert res.makespan > _sim(True, k=6).run().makespan
