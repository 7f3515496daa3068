"""The map/reduce compute of JSDoop's distributed SGD (paper §IV.G, Fig. 3).

map(version, mb)   = gradient of the mini-batch loss at model version v
reduce(version, *) = mean of the n_mb gradients (sorted by mb_index so the sum
                     order — and hence the floats — are independent of which
                     volunteer computed what, making the paper's Table-4
                     invariance an exact, testable equality), then the RMSprop
                     apply, producing model version v+1.

``TrainingProblem`` packages the model, optimizer, data schedule and jitted
compute; the Initiator, Coordinator and Simulator all consume it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.paper_lstm import CONFIG as LSTM_CONFIG, TrainParams, PAPER_PARAMS
from repro.data.text import TextTask
from repro.models import model as M
from repro.models.runtime import Runtime
from repro.optim import Optimizer, rmsprop, dense_bytes


@dataclass
class TrainingProblem:
    cfg: Any                     # ArchConfig (vocab resolved)
    rt: Runtime
    tp: TrainParams
    data: TextTask
    optimizer: Optimizer
    params0: Any
    opt_state0: Any

    _grad_fn: Callable = field(default=None, repr=False)
    _acc_apply_fn: Callable = field(default=None, repr=False)

    # ------------------------------------------------------------------ build
    @classmethod
    def paper_problem(cls, *, seed: int = 0, corpus: Optional[str] = None,
                      tp: TrainParams = PAPER_PARAMS,
                      rt: Runtime = Runtime(remat=False),
                      lr: Optional[float] = None,
                      d_model: Optional[int] = None) -> "TrainingProblem":
        data = TextTask.build(corpus, sample_len=tp.sample_len, seed=seed + 99)
        cfg = LSTM_CONFIG.replace(vocab=data.vocab.size)
        if d_model is not None:
            # shrunk variants for overhead-dominated benchmarks (the paper's
            # browser-device regime); same family, same data, fewer cells
            cfg = cfg.replace(d_model=d_model)
        params0 = M.init_params(cfg, jax.random.PRNGKey(seed))
        opt = rmsprop(lr if lr is not None else tp.learning_rate)
        opt_state0 = opt.init(params0)
        return cls(cfg, rt, tp, data, opt, params0, opt_state0)

    def __post_init__(self):
        cfg, rt = self.cfg, self.rt

        def loss(params, batch):
            return M.loss_fn(params, cfg, rt, batch)[0]

        self._grad_fn = jax.jit(jax.value_and_grad(loss))

        def acc_apply(params, opt_state, grads_stacked):
            g_mean = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads_stacked)
            return self.optimizer.update(params, opt_state, g_mean)

        # per-policy apply fns (repro.core.aggregation): SyncBSP reduces the
        # stacked mini-batch gradients; BoundedStaleness applies ONE gradient
        # per commit; LocalSteps adds a weighted (params, opt_state) delta.
        self._acc_apply_fn = jax.jit(acc_apply)
        self._apply_one_fn = jax.jit(self.optimizer.update)
        # donated variant: params/opt_state buffers are consumed and reused
        # for the outputs instead of copied. ONLY safe when the caller owns
        # them exclusively (the server-side applier's hot state) — donating a
        # DataServer-stored blob destroys it for every later reader.
        self._apply_one_don_fn = jax.jit(self.optimizer.update,
                                         donate_argnums=(0, 1))

        def delta_apply(blob, delta, weight):
            return jax.tree.map(
                lambda c, d: (c + weight * d).astype(c.dtype), blob, delta)

        self._delta_apply_fn = jax.jit(delta_apply)
        self._delta_apply_don_fn = jax.jit(delta_apply, donate_argnums=(0,))
        self._apply_batch_fns: Dict[bool, Callable] = {}

    # ------------------------------------------------------------------ schedule
    @property
    def n_versions(self) -> int:
        return self.tp.num_epochs * self.tp.batches_per_epoch

    def version_to_epoch_batch(self, version: int) -> Tuple[int, int]:
        return divmod(version, self.tp.batches_per_epoch)

    def minibatch(self, version: int, mb_index: int) -> Dict[str, np.ndarray]:
        e, b = self.version_to_epoch_batch(version)
        return self.data.minibatch(e, b, self.tp.batch_size, mb_index,
                                   self.tp.mini_batch_size)

    def stream_slot(self, i: int) -> Tuple[int, int]:
        """The global mini-batch stream shared by every aggregation policy:
        slot i -> (version, mb_index), wrapping at the problem horizon (a
        LocalSteps tail slot may run past n_versions * n_mb)."""
        n_mb = self.tp.mini_batches_to_accumulate
        return divmod(i % (self.n_versions * n_mb), n_mb)

    # ------------------------------------------------------------------ compute
    def map_compute(self, params, version: int, mb_index: int):
        """Returns (grads, loss)."""
        with obs.span("repro.step"):
            batch = self.minibatch(version, mb_index)
            loss, grads = self._grad_fn(params, batch)
            return grads, float(loss)

    def reduce_compute(self, params, opt_state, grads_by_mb: Dict[int, Any]):
        """grads_by_mb: mb_index -> grads. Deterministic order via sort."""
        ordered = [grads_by_mb[i] for i in sorted(grads_by_mb)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ordered)
        return self._acc_apply_fn(params, opt_state, stacked)

    def apply_one(self, params, opt_state, grads, *, donate: bool = False):
        """BoundedStaleness commit: apply one (possibly stale) gradient.

        ``donate=True`` reuses the params/opt_state buffers for the outputs
        (no copy). The inputs are INVALIDATED — only pass buffers the caller
        owns exclusively, never a blob other readers may still fetch."""
        fn = self._apply_one_don_fn if donate else self._apply_one_fn
        return fn(params, opt_state, grads)

    def local_compute(self, params, opt_state, start: int, k: int):
        """LocalSteps ticket: k local optimizer steps from stream offset
        ``start``. Returns ((delta_params, delta_opt_state), mean_loss)."""
        p0, s0 = params, opt_state
        losses: List[float] = []
        for j in range(k):
            v, mb = self.stream_slot(start + j)
            g, l = self.map_compute(params, v, mb)
            params, opt_state = self._apply_one_fn(params, opt_state, g)
            losses.append(l)
        delta = jax.tree.map(lambda a, b: a - b, (params, opt_state),
                             (p0, s0))
        return delta, float(np.mean(losses))

    def apply_delta(self, params, opt_state, delta, weight: float = 1.0, *,
                    donate: bool = False):
        """LocalSteps commit: current blob + weight * delta (dtype-preserving,
        so the int32 optimizer step counter survives a fractional weight).

        ``donate=True`` consumes the (params, opt_state) buffers — same
        exclusive-ownership contract as ``apply_one(donate=True)``."""
        fn = self._delta_apply_don_fn if donate else self._delta_apply_fn
        return fn((params, opt_state), delta, weight)

    # ------------------------------------------------------------- flat batch
    # The batched server applier applies a whole drain of gradients in ONE
    # jitted dispatch: params and every params-shaped optimizer-state subtree
    # are packed into single contiguous f32 vectors and a lax.scan(unroll=1)
    # chains the per-update optimizer steps over the stacked gradient rows.
    # Bit-exactness with the chained ``apply_one`` reference holds because
    # (a) flatten/unflatten is pure data movement and (b) the scan body is
    # compiled once and reused for every step — the same property that makes
    # ``sequential_async`` a usable reference. Unrolling (scan unroll>1 or a
    # Python loop inside one jit) is FORBIDDEN: cross-step fusion contracts
    # mul+add into FMA differently per compilation and breaks bit-equality
    # (verified empirically; see tests/test_applier.py).

    @functools.cached_property
    def _flat_spec(self):
        """(treedef, shapes, sizes, dtype, tree_keys, scalar_keys) when the
        problem qualifies for the flat fast path, else None. Qualifying means:
        one shared float dtype across params leaves, and an optimizer state
        that is a dict of params-treedef-mirroring subtrees plus scalars."""
        leaves, treedef = jax.tree.flatten(self.params0)
        if not leaves:
            return None
        dtype = leaves[0].dtype
        if any(l.dtype != dtype for l in leaves):
            return None
        if not isinstance(self.opt_state0, dict):
            return None
        tree_keys, scalar_keys = [], []
        for k in sorted(self.opt_state0):
            v = self.opt_state0[k]
            sl, sdef = jax.tree.flatten(v)
            if sdef == treedef and len(sl) == len(leaves) and \
                    all(a.shape == b.shape and a.dtype == dtype
                        for a, b in zip(sl, leaves)):
                tree_keys.append(k)
            elif len(sl) == 1 and sl[0].ndim == 0:
                scalar_keys.append(k)
            else:
                return None
        shapes = tuple(l.shape for l in leaves)
        sizes = tuple(int(np.prod(s)) for s in shapes)
        return (treedef, shapes, sizes, dtype, tuple(tree_keys),
                tuple(scalar_keys))

    @property
    def supports_flat_apply(self) -> bool:
        return self._flat_spec is not None

    def pack_grads(self, grads) -> np.ndarray:
        """Host-side flatten of a gradient pytree into one contiguous row
        (exact: pure reshape/concat, no arithmetic)."""
        treedef = self._flat_spec[0]
        return np.concatenate(
            [np.ravel(np.asarray(x)) for x in treedef.flatten_up_to(grads)])

    def pack_grad_rows(self, grads_seq) -> np.ndarray:
        """Stacked ``pack_grads`` rows built with ONE concatenate — the hot
        drain path (per-row concat + stack allocates and copies twice)."""
        treedef = self._flat_spec[0]
        return np.concatenate(
            [np.ravel(np.asarray(x)) for g in grads_seq
             for x in treedef.flatten_up_to(g)]).reshape(len(grads_seq), -1)

    def _flatten_tree(self, tree):
        treedef = self._flat_spec[0]
        return jnp.concatenate(
            [jnp.ravel(x) for x in treedef.flatten_up_to(tree)])

    def _unflatten_tree(self, vec):
        treedef, shapes, sizes = self._flat_spec[:3]
        splits = np.cumsum(sizes)[:-1]
        parts = jnp.split(vec, splits)
        return jax.tree.unflatten(
            treedef, [p.reshape(s) for p, s in zip(parts, shapes)])

    def flat_carry(self, params, opt_state):
        """Pack (params, opt_state) into the scan carry. Every array in the
        carry is freshly created (copied), so the caller owns it and may pass
        it to the donating ``apply_batch_flat``."""
        _, _, _, _, tree_keys, scalar_keys = self._flat_spec
        vecs = {k: self._flatten_tree(opt_state[k]) for k in tree_keys}
        scalars = {k: jnp.array(opt_state[k]) for k in scalar_keys}
        return (self._flatten_tree(params), vecs, scalars)

    def _unflatten_carry_impl(self, carry):
        fp, vecs, scalars = carry
        state = {k: self._unflatten_tree(v) for k, v in vecs.items()}
        state.update({k: v for k, v in scalars.items()})
        return self._unflatten_tree(fp), state

    @functools.cached_property
    def _unflatten_fn(self):
        # unflatten is pure data movement (split/reshape), so jitting cannot
        # change bits — and it folds the dozens of eager slice dispatches
        # into ONE (the LocalSteps delta path unflattens the hot carry)
        return jax.jit(self._unflatten_carry_impl)

    def unflatten_carry(self, carry):
        """Inverse of ``flat_carry``: (params, opt_state) pytrees."""
        return self._unflatten_fn(carry)

    def _carry_parts(self, carry):
        """The arrays of a flat carry in one fixed order: the flat params,
        each flat optimizer-state subtree, then each scalar."""
        fp, vecs, scalars = carry
        _, _, _, _, tree_keys, scalar_keys = self._flat_spec
        return ([fp] + [vecs[k] for k in tree_keys]
                + [scalars[k] for k in scalar_keys])

    @functools.cached_property
    def _row_layout(self):
        """((dtype, shape) of each carry part, the unsigned word of a packed
        row). The word is as wide as the narrowest part, so every part
        bit-casts into whole words: uint32 for float32 weights and an int32
        step."""
        parts = self._carry_parts(jax.eval_shape(
            self.flat_carry, self.params0, self.opt_state0))
        word = np.dtype(f"uint{8 * min(p.dtype.itemsize for p in parts)}")
        return tuple((np.dtype(p.dtype), p.shape) for p in parts), word

    def _pack_row(self, steps, i):
        word = self._row_layout[1]
        return jnp.concatenate(
            [jax.lax.bitcast_convert_type(a[i], word).ravel()
             for a in self._carry_parts(steps)])

    @functools.cached_property
    def _pack_step_fn(self):
        # one program slices row ``i`` of every part and bit-casts it into
        # one buffer of words: a bit-cast is no float operation, so no
        # integer's bits pass through float arithmetic and no denormal is
        # flushed. ``i`` traces as a dynamic scalar, so one compilation
        # serves every step index (retraced only per drain length)
        return jax.jit(lambda steps, i: self._pack_row(steps, i))

    def _unpack_row(self, words: np.ndarray):
        """(params, opt_state) as read-only NumPy views of a packed row:
        split, bit-cast back and reshaped, so every bit is the device's."""
        treedef, shapes, sizes, _, tree_keys, scalar_keys = self._flat_spec
        layout, word = self._row_layout
        words.flags.writeable = False
        counts = [int(np.prod(shape)) * dt.itemsize // word.itemsize
                  for dt, shape in layout]
        parts = [seg.view(dt).reshape(shape) for seg, (dt, shape) in
                 zip(np.split(words, np.cumsum(counts)[:-1]), layout)]
        leaf_ends = np.cumsum(sizes)[:-1]

        def tree(vec):
            return jax.tree.unflatten(treedef, [
                x.reshape(s) for x, s in zip(np.split(vec, leaf_ends), shapes)])

        state = {k: tree(v) for k, v in zip(tree_keys, parts[1:])}
        state.update(zip(scalar_keys, parts[1 + len(tree_keys):]))
        return tree(parts[0]), state

    def unflatten_step(self, steps, i: int):
        """(params, opt_state) at row ``i`` of a scan's stacked step outputs,
        as host NumPy arrays: one device program packs the row into one
        buffer, ONE device-to-host transfer brings it over, and the pytree
        is read-only views of that buffer (what a lazily-published version
        becomes when it is fetched, measured or snapshotted)."""
        return self._unpack_row(np.asarray(self._pack_step_fn(steps, i)))

    def _flat_step(self, carry, g):
        fp, vecs, scalars = carry
        # single-leaf trees are wrapped in LISTS: the optimizers unzip their
        # per-leaf pair results with is_leaf=isinstance(tuple), which a
        # tuple-wrapped container would defeat
        state = {k: [v] for k, v in vecs.items()}
        state.update(scalars)
        new_p, new_s = self.optimizer.update([fp], state, [g])
        new_carry = (new_p[0],
                     {k: new_s[k][0] for k in vecs},
                     {k: new_s[k] for k in scalars})
        return new_carry, new_carry

    def apply_batch_flat(self, carry, grad_rows, *, donate: bool = True):
        """Apply ``B`` stacked flat gradient rows in ONE jitted dispatch.

        Returns ``(carry', steps)`` where ``steps`` mirrors the carry with a
        leading length-B axis — row i is the full flat model/optimizer state
        after update i (needed because a drain publishes every intermediate
        version). ``donate=True`` consumes the carry buffers (the applier owns
        its hot state, so each drain reuses them in place)."""
        fn = self._apply_batch_fns.get(donate)
        if fn is None:
            fn = jax.jit(
                lambda c, gs: jax.lax.scan(self._flat_step, c, gs),
                donate_argnums=(0,) if donate else ())
            self._apply_batch_fns[donate] = fn
        return fn(carry, grad_rows)

    def apply_batch(self, params, opt_state, grads_seq):
        """Pytree-level batched apply: one scan dispatch over a sequence of
        gradient pytrees. Returns the list of per-step (params, opt_state),
        as host arrays, bit-identical to folding ``apply_one`` over
        ``grads_seq``."""
        if not grads_seq:
            return []
        rows = jnp.asarray(self.pack_grad_rows(grads_seq))
        carry = self.flat_carry(params, opt_state)
        _, steps = self.apply_batch_flat(carry, rows, donate=True)
        return [self.unflatten_step(steps, i) for i in range(len(grads_seq))]

    # ------------------------------------------------------------------ sizes
    @functools.cached_property
    def grad_bytes(self) -> int:
        return dense_bytes(self.params0)

    @functools.cached_property
    def model_bytes(self) -> int:
        return dense_bytes(self.params0) + dense_bytes(self.opt_state0)

    def flops_per_map(self) -> float:
        """Analytic cost of one mini-batch fwd+bwd (simulator cost model)."""
        n = M.param_count(self.cfg)
        tokens = self.tp.mini_batch_size * self.tp.sample_len
        return 6.0 * n * tokens

    def flops_per_reduce(self) -> float:
        n = M.param_count(self.cfg)
        return 8.0 * n * self.tp.mini_batches_to_accumulate


# ---------------------------------------------------------------------------
# sequential references (paper §V.C)
# ---------------------------------------------------------------------------

def sequential_accumulated(problem: TrainingProblem, *, n_versions=None,
                           record_every: int = 1):
    """The distributed algorithm run on one in-process worker (exact reference
    for worker-count invariance: must bit-match any Coordinator run)."""
    params, opt_state = problem.params0, problem.opt_state0
    losses: List[float] = []
    n = n_versions if n_versions is not None else problem.n_versions
    for v in range(n):
        grads_by_mb, ls = {}, []
        for mb in range(problem.tp.mini_batches_to_accumulate):
            g, l = problem.map_compute(params, v, mb)
            grads_by_mb[mb] = g
            ls.append(l)
        params, opt_state = problem.reduce_compute(params, opt_state, grads_by_mb)
        if (v % record_every) == 0:
            losses.append(float(np.mean(ls)))
    return params, opt_state, losses


def sequential_async(problem: TrainingProblem, *, n_updates=None):
    """BoundedStaleness run on ONE worker (every gradient is perfectly
    fresh): plain minibatch SGD over the global mini-batch stream. The exact
    reference for ``Coordinator(policy=BoundedStaleness(...))`` — the
    Coordinator's round-robin scheduler serializes barrierless tickets, so
    ANY worker count must bit-match this."""
    params, opt_state = problem.params0, problem.opt_state0
    n_mb = problem.tp.mini_batches_to_accumulate
    n = n_updates if n_updates is not None else problem.n_versions * n_mb
    losses: List[float] = []
    for i in range(n):
        v, mb = problem.stream_slot(i)
        g, l = problem.map_compute(params, v, mb)
        params, opt_state = problem.apply_one(params, opt_state, g)
        losses.append(l)
    return params, opt_state, losses


def sequential_local(problem: TrainingProblem, *, k: int = 4,
                     weight: float = 1.0, n_updates=None):
    """LocalSteps run on ONE worker: k local optimizer steps per round, the
    round's delta applied through the same jitted ``apply_delta`` the
    distributed commit uses (so a 1-worker Coordinator bit-matches)."""
    params, opt_state = problem.params0, problem.opt_state0
    total = problem.n_versions * problem.tp.mini_batches_to_accumulate
    n = n_updates if n_updates is not None else -(-total // k)
    losses: List[float] = []
    for slot in range(n):
        delta, l = problem.local_compute(params, opt_state, slot * k, k)
        params, opt_state = problem.apply_delta(params, opt_state, delta,
                                                weight)
        losses.append(l)
    return params, opt_state, losses


def sequential_fullbatch(problem: TrainingProblem, *, batch_size=None,
                         n_versions=None):
    """TFJS-Sequential-N: plain minibatch SGD at the given batch size (128 for
    the paper's headline sequential baseline, 8 for TFJS-Sequential-8)."""
    tp = problem.tp
    bs = batch_size or tp.batch_size
    params, opt_state = problem.params0, problem.opt_state0
    losses: List[float] = []
    n = n_versions if n_versions is not None else problem.n_versions
    steps_per_version = tp.batch_size // bs
    for v in range(n):
        e, b = problem.version_to_epoch_batch(v)
        starts = problem.data.starts(e, b, tp.batch_size)
        for s in range(steps_per_version):
            batch = problem.data.make_batch(starts[s * bs:(s + 1) * bs])
            loss, grads = problem._grad_fn(params, batch)
            params, opt_state = problem.optimizer.update(params, opt_state, grads)
            losses.append(float(loss))
    return params, opt_state, losses
