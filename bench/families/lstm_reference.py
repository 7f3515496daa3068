"""Plain float32 reference of the LSTM character model and its training
step, written from the model's description alone.

Nothing here imports the program. It covers what the served path computes
for one update: the mini-batch a ticket names, the loss and its gradient
(two stacked LSTM layers of Keras gate order i, f, c, o on one-hot
characters, a dense head on the last step, mean cross-entropy), the
RMSprop step of Keras/TF (eps outside the square root, state in float32),
and a LocalSteps contribution (k local steps, then the weighted delta of
parameters and optimizer state). The matrix products run at ``highest``
precision, so that a float32 reference is float32 on a TPU too. Two
lower precisions can stand in the program's place: ``"high"``, three
bfloat16 passes per product (the control one step below float32 at
``highest``), and ``"bfloat16"``, one pass (a TPU's default for float32).
Both are written out here with their own backward products, so that they
read the same on any platform.

It also makes the initial weights, from a seed, in the layout the program
takes, and counts an update's FLOPs and bytes from the shapes.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "high", "bfloat16")


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Parameter shapes, in the program's pytree layout."""
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_layers"]
    layers, d_in = [], V
    for _ in range(L):
        layers.append({"kernel": (d_in + H, 4 * H), "bias": (4 * H,)})
        d_in = H
    return {"layers": layers, "head": {"w": (H, V), "b": (V,)}}


def param_count(cfg: Dict[str, Any]) -> int:
    return int(sum(np.prod(s) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))))


def init_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """Glorot-scaled truncated normals for every kernel, zero biases with
    a unit forget gate. Trace it under ``jax.jit``: the weights are then
    made in one call, on the device."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]

    def normal(k, shape, fan):
        return (2.0 / fan) ** 0.5 * jax.random.truncated_normal(
            k, -2.0, 2.0, shape, jnp.float32)

    keys = jax.random.split(key, cfg["num_layers"] + 1)
    layers, d_in = [], V
    for i in range(cfg["num_layers"]):
        kernel = normal(keys[i], (d_in + H, 4 * H), d_in + 4 * H)
        bias = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
        layers.append({"kernel": kernel, "bias": bias})
        d_in = H
    return {"layers": layers,
            "head": {"w": normal(keys[-1], (H, V), H + V),
                     "b": jnp.zeros((V,), jnp.float32)}}


def flops_per_gradient(cfg: Dict[str, Any]) -> float:
    """Model FLOPs of one mini-batch gradient: the forward pass's matrix
    products (one per LSTM step and layer, the head once), times three for
    forward and backward. Elementwise work is left out."""
    B, T = cfg["mini_batch_size"], cfg["sample_len"]
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    fwd, d_in = 0.0, V
    for _ in range(cfg["num_layers"]):
        fwd += T * 2.0 * B * (d_in + H) * 4 * H
        d_in = H
    fwd += 2.0 * B * H * V
    return 3.0 * fwd


def applier_bytes_per_update(cfg: Dict[str, Any], kind: str) -> float:
    """Bytes one applied update must move at the least: a gradient reads
    parameters, optimizer state and gradient and writes the first two
    (5 P words); a LocalSteps delta also reads the state's delta (6 P)."""
    words = {"map": 5, "local": 6}[kind]
    return words * param_count(cfg) * 4.0


class Reference:
    """One configuration's reference: data, loss, gradient and steps."""

    def __init__(self, cfg: Dict[str, Any], text: str, schedule_seed: int,
                 n_versions: int):
        self.cfg = cfg
        self.chars = "".join(sorted(set(text)))
        if len(self.chars) != cfg["vocab_size"]:
            raise ValueError(f"corpus has {len(self.chars)} characters, the "
                             f"configuration {cfg['vocab_size']}")
        table = {c: i for i, c in enumerate(self.chars)}
        self.ids = np.asarray([table[c] for c in text], np.int32)
        self.schedule_seed = schedule_seed
        self.n_versions = n_versions
        self.batch_size = cfg["mini_batch_size"] * cfg["mini_batches_per_version"]
        self.batches_per_epoch = cfg["examples_per_epoch"] // self.batch_size

    # -- data ---------------------------------------------------------------
    def batch(self, version: int, mb_index: int, fault: Optional[str] = None):
        """Mini-batch ``mb_index`` of the batch that ``version`` names:
        window starts drawn from a hash of (seed, epoch, batch), one-hot
        inputs and the next character as label. ``fault`` plants one of the
        faults the check must catch: ``"half"`` keeps the first half of the
        rows, ``"token"`` alters the first row's label."""
        epoch, b = divmod(version, self.batches_per_epoch)
        h = hashlib.sha256(f"{self.schedule_seed}:{epoch}:{b}".encode()).digest()
        rng = np.random.RandomState(int.from_bytes(h[:4], "little"))
        T, V = self.cfg["sample_len"], self.cfg["vocab_size"]
        starts = rng.randint(0, len(self.ids) - T - 1, size=self.batch_size)
        mb = self.cfg["mini_batch_size"]
        starts = starts[mb_index * mb:(mb_index + 1) * mb].astype(np.int64)
        if fault == "half":
            starts = starts[:mb // 2]
        x_ids = self.ids[starts[:, None] + np.arange(T)[None, :]]
        x = np.zeros((len(starts), T, V), np.float32)
        np.put_along_axis(x, x_ids[..., None], 1.0, axis=-1)
        y = self.ids[starts + T].astype(np.int32)
        if fault == "token":
            y[0] = (y[0] + 1) % V
        return {"x": x, "y": y}

    def stream_slot(self, i: int) -> Tuple[int, int]:
        n_mb = self.cfg["mini_batches_per_version"]
        return divmod(i % (self.n_versions * n_mb), n_mb)

    # -- model --------------------------------------------------------------
    @functools.cached_property
    def _value_and_grad(self):
        return {p: jax.jit(jax.value_and_grad(functools.partial(_loss, p)))
                for p in PRECISIONS}

    def loss_and_grad(self, params, batch, precision: str = "float32"):
        loss, grads = self._value_and_grad[precision](params, batch)
        return float(loss), grads

    def rmsprop(self, state, grads):
        """One RMSprop step on ``state = (params, {"ms", "step"})``."""
        return _rmsprop(state, grads, self.cfg["learning_rate"],
                        self.cfg["rho"], self.cfg["epsilon"])

    def local_delta(self, state, start: int, k: int,
                    precision: str = "float32", fault: Optional[str] = None):
        """A LocalSteps contribution from ``state``: ``k`` steps over the
        stream slots ``start..start+k-1``. Returns (delta, mean loss)."""
        s, losses = state, []
        for j in range(k):
            loss, g = self.loss_and_grad(s[0], self.batch(*self.stream_slot(
                start + j), fault=fault), precision)
            s = self.rmsprop(s, g)
            losses.append(loss)
        delta = jax.tree.map(lambda a, b: a - b, s, state)
        return delta, float(np.mean(losses))

    @staticmethod
    def add_delta(state, delta, weight: float):
        return jax.tree.map(
            lambda c, d: (jnp.asarray(c) + weight * jnp.asarray(d)).astype(
                jnp.asarray(c).dtype), state, delta)

    @staticmethod
    def zero_state(params):
        """``params`` with the optimizer state RMSprop starts from."""
        return (params, {"ms": jax.tree.map(jnp.zeros_like, params),
                         "step": jnp.zeros((), jnp.int32)})


def _bf16_dot(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _split(a):
    """``a`` as ``hi + lo``, both bfloat16. ``hi`` is rounded by
    ``reduce_precision``: XLA may drop a float32 -> bfloat16 -> float32
    round trip of converts (excess precision), which leaves ``lo`` nought."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _high_dot(a, b):
    """Three bfloat16 passes, float32 accumulation: the ``lo @ lo`` pass of
    a full float32 product is left out."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return _bf16_dot(ah, bh) + _bf16_dot(ah, bl) + _bf16_dot(al, bh)


def _with_own_backward(dot):
    """``dot`` whose gradient products are computed by ``dot`` too (plain
    autodiff would round the cotangents to bfloat16)."""
    @jax.custom_vjp
    def f(a, b):
        return dot(a, b)

    def fwd(a, b):
        return dot(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return dot(g, b.T), dot(a.T, g)

    f.defvjp(fwd, bwd)
    return f


_DOTS = {
    "float32": lambda a, b: jnp.dot(a, b,
                                    precision=jax.lax.Precision.HIGHEST),
    "high": _with_own_backward(_high_dot),
    "bfloat16": _with_own_backward(_bf16_dot),
}


def _loss(precision: str, params, batch):
    dot = _DOTS[precision]
    xs = jnp.moveaxis(jnp.asarray(batch["x"], jnp.float32), 1, 0)  # [T, B, V]
    B = xs.shape[1]
    for layer in params["layers"]:
        H = layer["kernel"].shape[1] // 4

        def step(hc, x, layer=layer, H=H):
            h, c = hc
            z = dot(jnp.concatenate([x, h], -1), layer["kernel"]) \
                + layer["bias"]
            i, f, g, o = (z[:, j * H:(j + 1) * H] for j in range(4))
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        zeros = jnp.zeros((B, H), jnp.float32)
        _, xs = jax.lax.scan(step, (zeros, zeros), xs)
    logits = dot(xs[-1], params["head"]["w"]) + params["head"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], -1))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _rmsprop(state, grads, lr, rho, eps):
    params, opt = state
    ms = jax.tree.map(lambda m, g: rho * m + (1.0 - rho) * g * g,
                      opt["ms"], grads)
    params = jax.tree.map(lambda p, g, m: p - lr * g / (jnp.sqrt(m) + eps),
                          params, grads, ms)
    return params, {"ms": ms, "step": opt["step"] + 1}
