import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes with ShapeDtypeStruct stand-ins (no allocation), then
record memory/cost analysis + the collective schedule for §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]

The XLA_FLAGS line above MUST run before any jax import (device count locks
on first init) — that is why it is the first statement of this module.
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs import (ARCHS, SHAPES, SKIPS, FedConfig, get_arch,
                           get_shape)
from repro.core import init_server_state, make_federated_round
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.roofline.analysis import model_flops_per_round, roofline_terms
from repro.roofline.live import compiled_cost_summary

SDS = jax.ShapeDtypeStruct

# archs whose parameter count forces the client-sequential cohort strategy
SCAN_THRESHOLD = 20e9


def pick_strategy(arch_cfg) -> str:
    return "scan" if arch_cfg.param_count() > SCAN_THRESHOLD else "vmap"


def fed_for(arch_cfg, mesh, *, algorithm="uga", meta=True,
            strategy: Optional[str] = None, local_steps=2,
            agg_dtype="float32") -> FedConfig:
    strategy = strategy or pick_strategy(arch_cfg)
    if strategy == "vmap":
        cohort = shd.specs.axis_size(mesh, shd.batch_axes(mesh))
    else:
        cohort = 16
    return FedConfig(algorithm=algorithm, meta=meta, cohort=cohort,
                     local_steps=local_steps, cohort_strategy=strategy,
                     grad_agg_dtype=agg_dtype)


def decode_window_for(arch_cfg, shape) -> int:
    """long_500k uses the sliding-window variant for dense/VLM/moe attention
    archs; jamba/mamba2 use their native constant-state / full-cache path."""
    if shape.name == "long_500k" and arch_cfg.family not in ("ssm", "hybrid"):
        return arch_cfg.sliding_window
    return 0


def _token_sds(shape, n, seq):
    return SDS((n, seq), jnp.int32)


def _enc_sds(arch_cfg, lead):
    e = arch_cfg.encoder
    return SDS(tuple(lead) + (e.enc_len, e.enc_dim), jnp.dtype(arch_cfg.dtype))


def build_train_lowerable(arch_cfg, shape, mesh, fed: FedConfig,
                          loss_chunk: int = 2048):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    model = build_model(arch_cfg, loss_chunk=loss_chunk)
    spmd_axes = (tuple(shd.batch_axes(mesh))
                 if fed.cohort_strategy == "vmap" else None)
    grad_sh = None
    if fed.cohort_strategy == "vmap":
        params_shape = jax.eval_shape(model.init, SDS((2,), jnp.uint32))
        grad_sh = shd.specs.cohort_grad_shardings(params_shape, mesh,
                                                  fed.cohort_strategy)
    round_fn = make_federated_round(model, fed, spmd_axis_name=spmd_axes,
                                    grad_shardings=grad_sh)
    cohort = fed.cohort
    per_client = shape.global_batch // cohort
    assert per_client >= fed.local_steps, (
        f"{arch_cfg.name}/{shape.name}: per-client batch {per_client} < "
        f"local_steps {fed.local_steps}")
    seq = shape.seq_len

    rng_sds = SDS((2,), jnp.uint32)
    state_shape = jax.eval_shape(
        lambda k: init_server_state(model, fed, k), rng_sds)
    state_sh = shd.state_shardings(state_shape, mesh, fed.cohort_strategy)

    cohort_batch = {"tokens": SDS((cohort, per_client, seq + 1), jnp.int32)}
    meta_batch = {"tokens": SDS((64, seq + 1), jnp.int32)}
    if arch_cfg.encoder is not None:
        cohort_batch["enc_embeds"] = _enc_sds(arch_cfg, (cohort, per_client))
        meta_batch["enc_embeds"] = _enc_sds(arch_cfg, (64,))
    cb_sh = shd.cohort_batch_shardings(cohort_batch, mesh,
                                       fed.cohort_strategy)
    mb_sh = shd.simple_batch_shardings(meta_batch, mesh)
    w_sds = SDS((cohort,), jnp.float32)
    w_sh = (shd.cohort_batch_shardings({"w": SDS((cohort, 1), jnp.float32)},
                                       mesh, fed.cohort_strategy)["w"]
            if fed.cohort_strategy == "vmap"
            else NamedSharding(mesh, P()))
    if fed.cohort_strategy == "vmap":
        w_sh = NamedSharding(mesh, P(shd.batch_axes(mesh)))
    rng_sh = NamedSharding(mesh, P())

    metrics_shape = jax.eval_shape(
        round_fn, state_shape, cohort_batch, meta_batch, w_sds, rng_sds)[1]
    fn = jax.jit(round_fn,
                 in_shardings=(state_sh, cb_sh, mb_sh, w_sh, rng_sh),
                 out_shardings=(state_sh, shd.replicated(metrics_shape, mesh)),
                 donate_argnums=(0,))
    return fn, (state_shape, cohort_batch, meta_batch, w_sds, rng_sds)


def build_prefill_lowerable(arch_cfg, shape, mesh):
    model = build_model(arch_cfg)
    B, seq = shape.global_batch, shape.seq_len
    params_shape = jax.eval_shape(model.init, SDS((2,), jnp.uint32))
    p_sh = shd.param_shardings(params_shape, mesh, "vmap")
    batch = {"tokens": _token_sds(shape, B, seq)}
    if arch_cfg.encoder is not None:
        batch["enc_embeds"] = _enc_sds(arch_cfg, (B,))
    b_sh = shd.simple_batch_shardings(batch, mesh)

    def prefill(params, batch):
        return model.prefill(params, batch)

    # the output KV cache must shard like the decode cache — otherwise it
    # is materialized replicated (~100 GB/chip at 32k, §Perf it.8)
    out_shape = jax.eval_shape(prefill, params_shape, batch)
    logits_sh = shd.simple_batch_shardings({"l": out_shape[0]}, mesh)["l"]
    cache_sh = shd.cache_shardings(out_shape[1], mesh)
    fn = jax.jit(prefill, in_shardings=(p_sh, b_sh),
                 out_shardings=(logits_sh, cache_sh))
    return fn, (params_shape, batch)


def build_decode_lowerable(arch_cfg, shape, mesh, *, window: int = 0):
    model = build_model(arch_cfg, decode_window=window)
    B, seq = shape.global_batch, shape.seq_len
    params_shape = jax.eval_shape(model.init, SDS((2,), jnp.uint32))
    p_sh = shd.param_shardings(params_shape, mesh, "vmap")
    cache_shape = jax.eval_shape(lambda: model.make_cache(B, seq))
    c_sh = shd.cache_shardings(cache_shape, mesh)
    toks = SDS((B,), jnp.int32)
    t_sh = shd.simple_batch_shardings({"t": toks}, mesh)["t"]

    def decode(params, tokens, cache):
        return model.decode(params, tokens, cache)

    fn = jax.jit(decode, in_shardings=(p_sh, t_sh, c_sh),
                 out_shardings=(None, c_sh), donate_argnums=(2,))
    return fn, (params_shape, toks, cache_shape)


def run_one(arch_name: str, shape_name: str, *, multi_pod: bool = False,
            algorithm: str = "uga", strategy: Optional[str] = None,
            local_steps: int = 2, agg_dtype: str = "float32",
            loss_chunk: int = 2048, expert_axis: Optional[str] = None,
            act_spec: str = "on", moe_impl: str = "einsum",
            verbose: bool = True) -> Dict[str, Any]:
    arch_cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "algorithm": algorithm,
    }
    fed = None
    from repro.models import moe as moe_lib
    from repro.models import transformer as tf_lib
    # expert-axis wsc hint: measured neutral-to-negative at baseline
    # (EXPERIMENTS.md §Perf) — off by default, flip via --expert-axis
    moe_lib.set_expert_axis(expert_axis)
    moe_lib.set_moe_impl(moe_impl)
    # activation-sharding hint (§Perf it.5): per-client batch over "model"
    # for the client-parallel train path (GSPMD loses it through
    # vmap+scan+custom_vjp and replicates compute otherwise)
    if shape.kind == "train" and act_spec != "off":
        strat = strategy or pick_strategy(arch_cfg)
        # vmap: per-client slice (b, S, d) -> b over "model" (cohort already
        # owns data/pod).  scan: the whole client batch (b=16, S, d) is the
        # activation -> b over "data" and S over "model" (sequence sharding;
        # b alone is not divisible by data*model).
        tf_lib.set_activation_spec(
            P("model", None, None) if strat == "vmap"
            else P("data", None, None))
    else:
        tf_lib.set_activation_spec(None)
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            fed = fed_for(arch_cfg, mesh, algorithm=algorithm,
                          strategy=strategy, local_steps=local_steps,
                          agg_dtype=agg_dtype)
            rec["cohort_strategy"] = fed.cohort_strategy
            rec["cohort"] = fed.cohort
            fn, args = build_train_lowerable(arch_cfg, shape, mesh, fed,
                                             loss_chunk=loss_chunk)
        elif shape.kind == "prefill":
            fn, args = build_prefill_lowerable(arch_cfg, shape, mesh)
        else:
            window = decode_window_for(arch_cfg, shape)
            rec["decode_window"] = window
            fn, args = build_decode_lowerable(arch_cfg, shape, mesh,
                                              window=window)
        lowered = fn.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    # one compiled-program cost pass shared with the trainer's live
    # roofline hook (repro.roofline.live) — trip-count-aware HLO walk,
    # collective schedule, memory_analysis sizes
    s = compiled_cost_summary(compiled)
    if s["memory"]:
        rec["memory"] = s["memory"]
    cost = compiled.cost_analysis() or {}
    rec["cost"] = {k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float)) and (
                       "flops" in k or "bytes" in k or "utilization" not in k)}
    flops, bytes_acc = s["xla_flops"], s["xla_bytes_accessed"]

    coll = s["collectives"]
    rec["collectives"] = coll
    coll_bytes = sum(v for k, v in coll.items() if not k.startswith("_"))
    mf = model_flops_per_round(arch_cfg, shape, fed)
    rl = roofline_terms(flops, bytes_acc, coll_bytes,
                        model_flops_global=mf, chips=chips)
    rec["roofline_raw"] = rl.to_dict()
    # trip-count-aware cost model (XLA cost_analysis counts while bodies
    # once — see roofline/hlo_cost.py); this is the table-of-record.  The
    # memory term uses bytes_est: raw cost_analysis bytes scaled by the
    # flops correction ratio (same loop structure), keeping fusion-level
    # granularity
    rec["hlo_cost"] = {"flops": s["hlo_flops"],
                       "bytes_written": s["hlo_bytes_written"],
                       "collective_bytes": s["collective_bytes"],
                       "per_collective": s["per_collective"],
                       "loop_ratio": s["loop_ratio"]}
    rl2 = roofline_terms(s["hlo_flops"], s["bytes_est"],
                         s["collective_bytes"], model_flops_global=mf,
                         chips=chips)
    rec["roofline"] = rl2.to_dict()
    if verbose:
        print(f"[dryrun] {arch_name} x {shape_name} mesh={rec['mesh']} "
              f"lower={rec['lower_s']}s compile={rec['compile_s']}s "
              f"flops/chip={flops:.3e} bytes/chip={bytes_acc:.3e} "
              f"coll/chip={coll_bytes:.3e} bottleneck={rl.bottleneck}")
        if "memory" in rec:
            print(f"         memory_analysis={rec['memory']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--algorithm", default="uga",
                    choices=["uga", "fedavg", "fedprox"])
    ap.add_argument("--strategy", default=None, choices=[None, "vmap", "scan"])
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--agg-dtype", default="float32")
    ap.add_argument("--loss-chunk", type=int, default=2048)
    ap.add_argument("--expert-axis", default=None)
    ap.add_argument("--act-spec", default="on", choices=["on", "off"])
    ap.add_argument("--moe-impl", default="einsum",
                    choices=["gather", "einsum"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    pairs = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                if (a, s) not in SKIPS:
                    pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for mp in meshes:
        for a, s in pairs:
            tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip existing {tag}")
                continue
            try:
                rec = run_one(a, s, multi_pod=mp, algorithm=args.algorithm,
                              strategy=args.strategy,
                              local_steps=args.local_steps,
                              agg_dtype=args.agg_dtype,
                              loss_chunk=args.loss_chunk,
                              expert_axis=args.expert_axis,
                              act_spec=args.act_spec,
                              moe_impl=args.moe_impl)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((tag, repr(e)))
                print(f"[dryrun] FAIL {tag}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
