"""The LSTM character model family (the program's ``models/lstm.py``):
how a configuration file becomes the program's ``TrainingProblem``.

The reference it is checked against is ``lstm_reference.py`` beside this
file, which imports nothing of the program.
"""
from __future__ import annotations

import math
import pathlib
from typing import Any, Dict

from jsdoop_bench.spec import load_module, SpecError

#: the layer of each device program the served path runs, by the name the
#: profiler gives it. The volunteer step: ``jax.jit(jax.value_and_grad(
#: loss))``, and for ``local:k`` the optimizer's ``update`` and the delta's
#: subtraction. The applier: the scan of ``apply_batch_flat`` and the
#: slice of ``unflatten_step`` (both jitted lambdas), and for ``local:k``
#: ``unflatten_carry``, ``delta_apply`` and the repacking concatenate.
PROGRAMS = {
    "jit_loss": "volunteer step",
    "jit_update": "volunteer step",
    "jit_subtract": "volunteer step",
    "jit__lambda": "applier",
    "jit__unflatten_carry_impl": "applier",
    "jit_delta_apply": "applier",
    "jit_concatenate": "applier",
}

REFERENCE = load_module(pathlib.Path(__file__).with_name("lstm_reference.py"),
                        "bench_family_lstm_reference")


def build_problem(cfg: Dict[str, Any], text: str, schedule_seed: int,
                  params0, n_versions: int):
    """The program's training problem for ``cfg``: its own data pipeline
    over ``text``, RMSprop with the configuration's hyperparameters, and
    ``params0`` (made by the benchmark) as version 0. ``n_versions`` sizes
    the schedule of mini-batches."""
    from repro.configs.paper_lstm import CONFIG, TrainParams
    from repro.core.mapreduce import TrainingProblem
    from repro.data.text import TextTask
    from repro.models.runtime import Runtime
    from repro.optim import rmsprop

    if (cfg["dtype"], cfg["matmul_precision"], cfg["optimizer"]) != \
            ("float32", "highest", "rmsprop"):
        raise SpecError(f"{cfg['name']}: the program trains float32 weights "
                        f"with RMSprop; the run sets float32 products "
                        f"(matmul precision highest)")
    mb, n_mb = cfg["mini_batch_size"], cfg["mini_batches_per_version"]
    batch_size = mb * n_mb
    per_epoch = cfg["examples_per_epoch"] // batch_size
    tp = TrainParams(batch_size=batch_size,
                     examples_per_epoch=cfg["examples_per_epoch"],
                     learning_rate=cfg["learning_rate"],
                     num_epochs=math.ceil(n_versions / per_epoch),
                     sample_len=cfg["sample_len"], mini_batch_size=mb,
                     mini_batches_to_accumulate=n_mb)
    data = TextTask.build(text, sample_len=cfg["sample_len"],
                          seed=schedule_seed)
    if data.vocab.size != cfg["vocab_size"]:
        raise SpecError(f"{cfg['name']}: corpus vocabulary {data.vocab.size}, "
                        f"configuration {cfg['vocab_size']}")
    arch = CONFIG.replace(name=cfg["name"], n_layers=cfg["num_layers"],
                          d_model=cfg["hidden_size"], vocab=cfg["vocab_size"])
    opt = rmsprop(cfg["learning_rate"], rho=cfg["rho"], eps=cfg["epsilon"])
    problem = TrainingProblem(arch, Runtime(remat=False), tp, data, opt,
                              params0, opt.init(params0))
    if problem.n_versions < n_versions:
        raise SpecError(f"schedule holds {problem.n_versions} versions, "
                        f"{n_versions} asked")
    return problem
