"""Real training driver (runs on whatever devices exist — 1 CPU here).

Two modes:
- ``--paper``: the paper's exact experiment — queue-scheduled distributed
  training of the 2x50 LSTM on this repo's own source text (JSDoop §V),
  through the L1 Coordinator with K simulated volunteers.
- ``--arch <id>``: the L2 SPMD path — train a (reduced by default) assigned
  architecture with the sharded train_step on the host mesh, synthetic
  token stream, versioned checkpoints.

Examples:
  PYTHONPATH=src python -m repro.launch.train --paper --workers 4 --versions 8
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.checkpoint.store import CheckpointStore
from repro.compile_cache import place_compile_cache
from repro.configs.base import InputShape
from repro.distributed import steps as ST
from repro.distributed import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.runtime import Runtime
from repro.optim import make as make_opt


def run_paper(args) -> int:
    from repro.core.coordinator import Coordinator
    from repro.core.mapreduce import TrainingProblem
    prob = TrainingProblem.paper_problem(seed=args.seed)
    n_versions = args.versions or prob.n_versions
    print(f"[paper] vocab={prob.cfg.vocab} params={prob.grad_bytes // 4} "
          f"versions={n_versions} workers={args.workers}")
    t0 = time.time()
    coord = Coordinator(prob, n_workers=args.workers, n_versions=n_versions)
    res = coord.run()
    dt = time.time() - t0
    print(f"[paper] done v{res.final_version} in {dt:.1f}s; "
          f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}; "
          f"requeues={res.requeues}")
    return 0


def run_arch(args) -> int:
    cfg = C.get(args.arch) if args.full else C.get_smoke(args.arch)
    if cfg.family == "rnn":
        raise SystemExit("use --paper for the LSTM workload")
    mesh = make_host_mesh()
    rt = Runtime(remat=not args.no_remat)
    shape = InputShape("cli", args.seq, args.batch, "train")
    opt = make_opt(args.optimizer, args.lr)
    bound = ST.bind_train(mesh, cfg, rt, opt, shape,
                          num_microbatches=args.micro)

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    opt_state = opt.init(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"[arch] {cfg.name} ({'full' if args.full else 'smoke'}) "
          f"params={n_params:,} micro={bound['n_micro']} mesh={mesh.devices.shape}")

    store = CheckpointStore(args.ckpt) if args.ckpt else None
    rng = np.random.RandomState(args.seed)
    spec = bound["batch_shape"]

    def sample_batch():
        out = {}
        for k, s in spec.items():
            if s.dtype == jnp.int32:
                out[k] = jnp.asarray(
                    rng.randint(0, cfg.vocab, size=s.shape), jnp.int32)
            else:
                out[k] = jnp.asarray(rng.randn(*s.shape), s.dtype)
        return out

    t0 = time.time()
    losses = []
    for step in range(args.steps):
        params, opt_state, mets = bound["step"](params, opt_state,
                                                sample_batch())
        losses.append(float(mets["loss"]))
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss {losses[-1]:.4f} "
                  f"({(time.time() - t0) / (step + 1):.2f}s/step)")
        if store and (step + 1) % args.ckpt_every == 0:
            v = (store.latest() or 0) + 1
            store.save(v, {"params": params, "opt": opt_state},
                       meta={"step": step + 1})
            print(f"  checkpoint v{v}")
    assert np.isfinite(losses).all(), "NaN/inf loss"
    print(f"[arch] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"in {time.time() - t0:.1f}s")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--arch", default=None, choices=C.ARCH_IDS)
    ap.add_argument("--full", action="store_true",
                    help="full published config (NOT for 1-CPU containers)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["rmsprop", "sgd", "adamw"])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--versions", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)
    place_compile_cache()
    if args.paper:
        return run_paper(args)
    if not args.arch:
        raise SystemExit("need --paper or --arch <id>")
    return run_arch(args)


if __name__ == "__main__":
    raise SystemExit(main())
