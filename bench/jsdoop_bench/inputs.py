"""Everything a run makes from ``--seed``: the sub-seeds, and the text the
model trains on."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# The corpus generator of the program's hermetic tests
# (``repro.data.text.synthetic_corpus``), copied so that the benchmark's
# inputs do not change when the program does.
_WORDS = ("const", "let", "function", "return", "tensor", "model", "train",
          "gradient", "queue", "task", "reduce", "map", "worker", "async",
          "await", "batch", "epoch", "loss", "browser", "volunteer")


def corpus(n_chars: int, seed: int) -> str:
    """Seeded pseudo-code text: words, calls with numbers, newlines."""
    rng = np.random.RandomState(seed)
    out, n = [], 0
    while n < n_chars:
        w = _WORDS[rng.randint(len(_WORDS))]
        frag = f"{w}({rng.randint(100)});\n" if rng.rand() < 0.3 else f"{w} "
        out.append(frag)
        n += len(frag)
    return "".join(out)[:n_chars]


@dataclass(frozen=True)
class Seeds:
    """Independent sub-seeds of one ``--seed`` (any whole number >= 0,
    also past 32 bits)."""
    weights: int        # the model's initial weights (a JAX key's seed)
    text: int           # the corpus
    schedule: int       # the mini-batch schedule's hash seed
    sample: int         # which window commits correctness checks

    @classmethod
    def of(cls, seed: int) -> "Seeds":
        if seed < 0:
            raise ValueError(f"--seed must be >= 0, got {seed}")
        w, t, s, c = np.random.SeedSequence(seed).generate_state(4)
        return cls(int(w) >> 1, int(t), int(s), int(c))
