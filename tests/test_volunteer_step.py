"""The volunteer step's gradient on the host: for parameters on the host,
as a model fetched over the wire, ``map_compute`` brings the loss and every
gradient leaf over in ONE device-to-host transfer of one packed buffer, bit
for bit what the device computed, so the bytes of a ``SubmitUpdate`` are
those of the per-leaf device gradient; for parameters on the device, and in
``local_compute``, the gradient stays on the device."""
from __future__ import annotations

import dataclasses
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_lstm import TrainParams
from repro.core.mapreduce import TrainingProblem, _pack_grad
from repro.core.protocol import SubmitUpdate, encode_message
from repro.core.tasks import GradResult
from repro.data.text import synthetic_corpus
from repro.optim import rmsprop


@pytest.fixture(scope="module")
def problem():
    tp = TrainParams(batch_size=32, examples_per_epoch=256, num_epochs=1,
                     sample_len=40, mini_batch_size=8,
                     mini_batches_to_accumulate=4)
    return TrainingProblem.paper_problem(corpus=synthetic_corpus(20_000),
                                         tp=tp, seed=0, d_model=8)


@pytest.fixture(scope="module")
def host_params(problem):
    """The initial parameters as a volunteer has them: fetched, on the host."""
    return jax.device_get(problem.params0)


def _root(a: np.ndarray) -> np.ndarray:
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a


def _device_step(problem, params, slot):
    return problem._grad_fn(params, problem.minibatch(*problem.stream_slot(
        slot)))


@pytest.mark.parametrize("slot", [0, 1, 6, 13])
def test_map_compute_is_the_device_step_bit_for_bit(problem, host_params,
                                                    slot):
    """The loss and every leaf equal the device's own, as read-only NumPy
    views of one buffer; the encoded ``SubmitUpdate`` is byte-identical to
    the one the per-leaf device gradient makes."""
    params = host_params
    loss_d, grads_d = _device_step(problem, params, slot)
    grads, loss = problem.map_compute(params, *problem.stream_slot(slot))
    assert type(loss) is float and loss == float(loss_d)
    assert np.float32(loss).tobytes() == np.asarray(loss_d).tobytes()
    leaves = jax.tree.leaves(grads)
    assert jax.tree.structure(grads) == jax.tree.structure(grads_d)
    for got, want in zip(leaves, jax.tree.leaves(grads_d)):
        want = np.asarray(want)
        assert type(got) is np.ndarray and not got.flags.writeable
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    assert len({id(_root(x)) for x in leaves}) == 1

    def submit(payload):
        v, mb = problem.stream_slot(slot)
        return encode_message(SubmitUpdate("q", 7, GradResult(
            v, mb, payload, problem.grad_bytes, loss, "v0", computed_at=v)))

    assert submit(grads) == submit(grads_d)


@pytest.fixture
def spans():
    """The program's spans on for one test; off, and nothing kept, after."""
    obs.take()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.take()


def test_one_transfer_per_map_compute_and_none_in_local_compute(
        problem, host_params, spans):
    """One ``repro.grad_to_host`` per ``map_compute`` on host parameters,
    inside its step; none across a ``local_compute`` of k=4 steps, whose
    gradients never leave the device."""
    for slot in range(3):
        problem.map_compute(host_params, *problem.stream_slot(slot))
    got = obs.take()
    assert len(got["repro.grad_to_host"]) == len(got["repro.step"]) == 3
    assert set(got["repro.grad_to_host"].parent_name) == {"repro.step"}
    problem.local_compute(host_params, problem.opt_state0, 0, 4)
    got = obs.take()
    assert len(got["repro.step"]) == 4 and "repro.grad_to_host" not in got


def test_threads_sharing_a_problem_get_their_own_gradients(problem,
                                                           host_params):
    """Volunteers on threads share one problem: each gradient comes back
    whole, in a buffer of its own, when more threads than cores switch as
    often as they can."""
    threads, each = 12, 3
    slots = range(threads * each)
    want = {i: problem.map_compute(host_params, *problem.stream_slot(i))
            for i in slots}
    got, errors = {}, []

    def volunteer(t):
        try:
            for i in range(t * each, (t + 1) * each):
                got[i] = problem.map_compute(host_params,
                                             *problem.stream_slot(i))
        except BaseException as e:      # re-raised below, in the test
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=volunteer, args=(t,))
                   for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert not any(w.is_alive() for w in workers) and not errors, errors
    roots = set()
    for i in slots:
        (g, loss), (g_want, loss_want) = got[i], want[i]
        assert loss == loss_want
        leaves = jax.tree.leaves(g)
        for x, y in zip(leaves, jax.tree.leaves(g_want)):
            assert x.tobytes() == y.tobytes()
        roots |= {id(_root(x)) for x in leaves}
    assert len(roots) == len(slots)


def test_local_compute_matches_chained_map_compute(problem):
    """k local steps on device gradients land on the bits of k chained
    ``map_compute`` + ``apply_one`` steps, and on the same mean loss."""
    p0, s0 = problem.params0, problem.opt_state0
    p, s, losses = p0, s0, []
    for j in range(4):
        g, l = problem.map_compute(p, *problem.stream_slot(5 + j))
        p, s = problem.apply_one(p, s, g)
        losses.append(l)
    want = jax.tree.map(lambda a, b: a - b, (p, s), (p0, s0))
    delta, loss = problem.local_compute(p0, s0, 5, 4)
    assert loss == float(np.mean(losses))
    assert jax.tree.all(jax.tree.map(
        lambda x, y: np.asarray(x).tobytes() == np.asarray(y).tobytes(),
        delta, want))


def test_narrow_gradient_packs_into_16_bit_words(problem):
    """bfloat16 gradient leaves beside a float32 loss pack into 16-bit
    words, and come back bit for bit."""
    opt = rmsprop(0.1, state_dtype=jnp.bfloat16)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), problem.params0)
    narrow = dataclasses.replace(problem, optimizer=opt, params0=p16,
                                 opt_state0=opt.init(p16))
    assert narrow.supports_flat_apply
    loss_d, grads_d = _device_step(narrow, p16, 2)
    grads, loss = narrow.map_compute(jax.device_get(p16),
                                     *narrow.stream_slot(2))
    assert loss == float(loss_d)
    leaves = jax.tree.leaves(grads)
    assert _root(leaves[0]).dtype == np.uint16
    for got, want in zip(leaves, jax.tree.leaves(grads_d)):
        assert got.dtype == jnp.bfloat16
        assert got.tobytes() == np.asarray(want).tobytes()


def test_problem_without_flat_spec_packs_its_gradient_too(problem):
    """The gradient's packing does not depend on the optimizer state's
    layout: where that has no flat form, the gradient still arrives bit
    for bit, as read-only views of one buffer."""
    odd = dataclasses.replace(problem, opt_state0=[problem.opt_state0])
    assert not odd.supports_flat_apply
    loss_d, grads_d = _device_step(odd, odd.params0, 3)
    grads, loss = odd.map_compute(jax.device_get(odd.params0),
                                  *odd.stream_slot(3))
    assert loss == float(loss_d)
    leaves = jax.tree.leaves(grads)
    for got, want in zip(leaves, jax.tree.leaves(grads_d)):
        assert type(got) is np.ndarray and not got.flags.writeable
        assert got.tobytes() == np.asarray(want).tobytes()
    assert len({id(_root(x)) for x in leaves}) == 1


def test_device_params_keep_a_device_gradient(problem, spans):
    """A caller that holds its parameters on the device, and applies the
    gradient in this process, gets the device's own arrays: nothing is
    packed or copied but the loss."""
    loss_d, grads_d = _device_step(problem, problem.params0, 4)
    grads, loss = problem.map_compute(problem.params0,
                                      *problem.stream_slot(4))
    assert type(loss) is float and loss == float(loss_d)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_d)):
        assert isinstance(got, jax.Array)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    got = obs.take()
    assert len(got["repro.step"]) == 1 and "repro.grad_to_host" not in got


def test_grad_pack_program_has_its_own_name(problem):
    """A profile names a device program after the jitted function: the
    gradient's packing program shows as ``jit__pack_grad``, not as an
    anonymous ``jit__lambda`` or an eager ``jit_concatenate``."""
    loss, grads = _device_step(problem, problem.params0, 0)
    text = _pack_grad.lower(loss, jax.tree.leaves(grads)).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == "jit__pack_grad"
