import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
combination with ShapeDtypeStruct inputs (no allocation), then extract the
roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out EXPERIMENTS_dryrun.jsonl

Per combination we record:
  - memory_analysis (bytes per device: args/outputs/temps -> "does it fit"),
  - cost_analysis flops / bytes accessed (per-device, post-partitioning),
  - collective bytes by op kind, parsed from the optimized HLO,
  - the three roofline terms against v5e peaks and the dominant one.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

import repro.configs as C
from repro.launch import hlo_cost
from repro.launch import mesh as MESH
from repro.models import model as M
from repro.models.runtime import Runtime
from repro.distributed import steps as ST
from repro.distributed import sharding as SH
from repro.optim import rmsprop, adamw


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op in the optimized HLO.

    The result shape is the per-device payload after the collective: for
    all-gather it's the gathered (larger) buffer, for reduce-scatter the
    scattered shard, for all-reduce the reduced buffer — a uniform,
    reproducible proxy for bytes-on-the-wire per device.
    ``-done`` ops are skipped so async pairs aren't double counted.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done" in line:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
        count[m.group(2)] += 1
    out_all = dict(out)
    out_all["_counts"] = count
    out_all["total"] = sum(out[k] for k in _COLLECTIVES)
    return out_all


# ---------------------------------------------------------------------------
# lowering one combination
# ---------------------------------------------------------------------------

def input_specs(cfg, shape, kind: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this combination."""
    if kind == "train":
        return {"batch": M.train_batch_spec(cfg, shape)}
    if kind == "prefill":
        return {"batch": ST.prefill_batch_spec(cfg, shape)}
    # decode
    tok, pos = M.decode_spec(cfg, shape)
    return {"token": tok, "pos": pos}


def _abstract(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def default_runtime(cfg, shape) -> Runtime:
    return Runtime()


def resolve_cfg(arch: str, shape_name: str):
    """Apply per-shape architectural adjustments (DESIGN §5):
    dense archs decode long_500k with a sliding window (sub-quadratic)."""
    cfg = C.get(arch)
    shape = C.get_shape(shape_name)
    if shape_name == "long_500k" and cfg.family in ("dense", "vlm", "encdec") \
            and not cfg.sliding_window:
        cfg = cfg.replace(sliding_window=8192)
    return cfg, shape


def lower_one(arch: str, shape_name: str, mesh, *, rt: Optional[Runtime] = None,
              policy_kw: Optional[dict] = None, num_microbatches: int = 0,
              optimizer: str = "rmsprop", cfg_overrides: Optional[dict] = None):
    """Lower + compile one (arch, shape, mesh). Returns the record dict."""
    cfg, shape = resolve_cfg(arch, shape_name)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    rt = rt or default_runtime(cfg, shape)
    policy = SH.ShardingPolicy.for_mesh(mesh, **(policy_kw or {}))
    t0 = time.time()

    opt = rmsprop(0.1) if optimizer == "rmsprop" else adamw(1e-4)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            b = ST.bind_train(mesh, cfg, rt, opt, shape, policy=policy,
                              num_microbatches=num_microbatches, donate=False)
            args = (_abstract(b["params_shape"]), _abstract(b["opt_shape"]),
                    _abstract(b["batch_shape"]))
            lowered = b["step"].lower(*args)
            extra = {"n_micro": b["n_micro"]}
        elif shape.kind == "prefill":
            b = ST.bind_prefill(mesh, cfg, rt, shape, policy=policy)
            args = (_abstract(b["params_shape"]), _abstract(b["batch_shape"]),
                    _abstract(b["cache_shape"]))
            lowered = b["step"].lower(*args)
            extra = {}
        else:  # decode
            b = ST.bind_decode(mesh, cfg, rt, shape, policy=policy)
            args = (_abstract(b["params_shape"]), _abstract(b["cache_shape"]),
                    b["token_shape"], b["pos_shape"])
            lowered = b["step"].lower(*args)
            extra = {}
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware re-analysis: XLA's cost_analysis counts while bodies once
    # (see hlo_cost docstring); ours scales by known_trip_count.
    la = hlo_cost.analyze(hlo)
    coll = la["collective_bytes"]

    n_chips = int(np.prod(mesh.devices.shape))
    flops_dev = float(la["flops"])
    # HBM-traffic estimate: fusion-optimistic (TPU-like) = dot traffic +
    # one-time arg/output traffic; the all-ops sum is the pessimistic bound.
    mem_d = _mem_dict(mem)
    bytes_opt = (float(la["dot_bytes"]) + mem_d["argument_size_in_bytes"]
                 + mem_d["output_size_in_bytes"])
    bytes_dev = float(la["bytes"])
    # with the L3 Pallas flash kernel the attention score/context tensors
    # never leave VMEM; this is the memory term a kernel-enabled build sees
    bytes_kernel = bytes_opt - float(la["flash_dot_bytes"])
    terms = roofline_terms(flops_dev, bytes_opt, coll["total"])

    rec = dict(
        arch=arch, shape=shape_name,
        mesh="x".join(map(str, mesh.devices.shape)), chips=n_chips,
        kind=shape.kind,
        params=cfg.param_count(), active_params=cfg.active_param_count(),
        flops_per_device=flops_dev, bytes_per_device=bytes_opt,
        bytes_per_device_pessimistic=bytes_dev,
        t_memory_kernel=bytes_kernel / MESH.HBM_BW,
        xla_flops_raw=float(cost.get("flops", 0.0)),
        collective_bytes=coll, memory=mem_d,
        n_while_loops=len(la["while_loops"]),
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        **terms, **extra,
    )
    rec.update(model_flops_terms(cfg, shape, rec))
    return rec


def roofline_terms(flops_dev: float, bytes_dev: float, coll_bytes: int
                   ) -> Dict[str, float]:
    """Per-device seconds for each roofline term (v5e)."""
    t_c = flops_dev / MESH.PEAK_FLOPS_BF16
    t_m = bytes_dev / MESH.HBM_BW
    t_x = coll_bytes / MESH.ICI_BW
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    return dict(t_compute=t_c, t_memory=t_m, t_collective=t_x, bottleneck=dom)


def model_flops_terms(cfg, shape, rec) -> Dict[str, float]:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (fwd); MoE uses active params.
    Ratio over compiled per-device flops * chips = useful-compute fraction."""
    n = rec["active_params"] if cfg.moe.num_experts else rec["params"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = 2.0 * n * tokens
    else:
        tokens = shape.global_batch  # one token per request
        mf = 2.0 * n * tokens
    hlo_total = rec["flops_per_device"] * rec["chips"]
    return dict(model_flops=mf,
                useful_fraction=(mf / hlo_total) if hlo_total else 0.0)


def _mem_dict(mem) -> Dict[str, int]:
    return {k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def elastic_plan(arch: str, shape_name: str, *, steps=((4, 16), (8, 16),
                                                       (16, 16))):
    """The TPU-idiomatic form of JSDoop's elastic membership (DESIGN §3):
    when "volunteers" (slices) join or leave, the driver re-lowers the same
    train_step for the new data-parallel size. This dry-runs the re-mesh
    sequence and reports per-step compile cost + roofline terms, proving the
    schedule is valid at every membership size.
    """
    recs = []
    for shape_dp in steps:
        mesh = jax.make_mesh(shape_dp, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rec = lower_one(arch, shape_name, mesh)
        print(f"[elastic] dp={shape_dp[0]:3d} x tp={shape_dp[1]} "
              f"compile={rec['compile_s']:.1f}s "
              f"t_c={rec['t_compute']:.2e} t_x={rec['t_collective']:.2e} "
              f"bottleneck={rec['bottleneck']}")
        recs.append(rec)
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (see configs)")
    ap.add_argument("--shape", default=None, choices=list(C.INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) combination")
    ap.add_argument("--elastic-plan", action="store_true",
                    help="re-lower the same step across growing data-parallel"
                         " sizes (elastic membership, DESIGN §3)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--fsdp-pod", action="store_true",
                    help="extend the FSDP domain over the pod axis")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="TP-only weight sharding (serving-friendly)")
    ap.add_argument("--no-hd-fallback", action="store_true",
                    help="replicate qkv instead of sharding head_dim when "
                         "heads don't divide the TP axis")
    ap.add_argument("--grad-accum-dtype", default="float32")
    ap.add_argument("--pad-vocab", type=int, default=0,
                    help="pad vocab to a multiple (enables vocab TP)")
    ap.add_argument("--moe-shard", action="store_true",
                    help="pin MoE dispatch buffers to expert/data axes")
    ap.add_argument("--micro", type=int, default=0,
                    help="requested grad-accumulation microbatches (default 16)")
    ap.add_argument("--optimizer", default="rmsprop",
                    choices=["rmsprop", "adamw"])
    args = ap.parse_args(argv)

    if args.elastic_plan:
        elastic_plan(args.arch or "stablelm-1.6b",
                     args.shape or "train_4k")
        return 0

    combos = []
    archs = C.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(C.INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    ok = fail = 0
    for arch, shape_name, mp in combos:
        mesh = MESH.make_production_mesh(multi_pod=mp)
        policy_kw = dict(seq_parallel=args.seq_parallel,
                         grad_accum_dtype=args.grad_accum_dtype)
        if args.fsdp_pod and mp:
            policy_kw["fsdp_axes"] = ("pod", "data")
        if args.no_fsdp:
            policy_kw["fsdp_axes"] = ()
        if args.no_hd_fallback:
            policy_kw["attn_hd_fallback"] = False
        cfg_overrides = {}
        if args.pad_vocab:
            cfg_overrides["vocab_pad_to"] = args.pad_vocab
        rt = None
        if args.moe_shard:
            tok = ("pod", "data") if mp else ("data",)
            if args.seq_parallel:
                # residual stream is (batch, seq)-sharded; the flattened token
                # dim of the MoE sees both axes
                tok = tok + ("model",)
            rt = Runtime(moe_expert_axis="model", moe_token_axes=tok)
        tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
        try:
            rec = lower_one(arch, shape_name, mesh, policy_kw=policy_kw,
                            num_microbatches=args.micro, rt=rt,
                            optimizer=args.optimizer,
                            cfg_overrides=cfg_overrides or None)
            ok += 1
            print(f"[ok]   {tag}: bottleneck={rec['bottleneck']} "
                  f"t_c={rec['t_compute']:.3e}s t_m={rec['t_memory']:.3e}s "
                  f"t_x={rec['t_collective']:.3e}s "
                  f"temp={rec['memory']['temp_size_in_bytes']/2**30:.2f}GiB "
                  f"args={rec['memory']['argument_size_in_bytes']/2**30:.2f}GiB")
        except Exception as e:  # noqa: BLE001 — a dry-run failure IS the signal
            fail += 1
            rec = dict(arch=arch, shape=shape_name,
                       mesh="2x16x16" if mp else "16x16",
                       error=f"{type(e).__name__}: {e}")
            print(f"[FAIL] {tag}: {rec['error'][:300]}")
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"\n{ok} ok / {fail} failed / {len(combos)} total")
    return 1 if fail else 0


if __name__ == "__main__":
    sys.exit(main())
