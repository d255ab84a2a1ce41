"""Cohort scaling: chunked streaming aggregation holds peak memory flat.

The chunked cohort executor streams `cohort_chunk`-client slices through
the Pallas FMA accumulators, so the per-round scratch footprint is ONE
chunk of gradients no matter the cohort.  This bench measures exactly
that, with XLA's compiled-memory accounting (``benchmarks.common.
peak_memory_bytes``): the jitted round program's temp bytes at
cohort = 64 / 256 / 1024 with ``cohort_chunk`` fixed, next to the vmap
executor (the same core at chunk = cohort), whose one chunk holds the whole
cohort's gradients and so grows linearly.

Gates (exit non-zero on failure — CI runs ``--fast``):
  * flat memory: cohort=1024 temp bytes <= 1.3x the cohort=64 run at the
    same ``cohort_chunk`` (and the 1024-client round actually executes,
    finite loss);
  * bit identity: chunk in {1, 8, 24 (ragged), cohort} agree bitwise —
    the streaming core accumulates in global client order, so the chunk
    size can never change a round — and chunk=1 reproduces the scan
    executor's round bit-for-bit;
  * vmap agreement: chunk = cohort matches the vmap executor's round
    <= 1e-6 (both run the one streaming core at the same chunk, so the
    bench reports a distance of 0).
  * hypergradients: two-tier sharded through_aggregation ctrl state
    matches the vmap path <= 1e-5 after a round.

Usage:  PYTHONPATH=src python benchmarks/cohort_scaling.py [--fast]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import init_server_state, make_federated_round
from repro.launch.mesh import make_debug_mesh
from repro.sharding.specs import cohort_grad_shardings
from common import (bench_tracker, peak_memory_bytes,  # noqa: E402
                    write_bench_report)
from round_latency import make_mlp_model, D, CLASSES

BATCH, LOCAL_STEPS, CHUNK = 8, 2, 8


def make_fed(cohort: int, chunk=None, **kw) -> FedConfig:
    return FedConfig(algorithm="uga", meta=kw.pop("meta", False),
                     cohort=cohort, local_steps=LOCAL_STEPS,
                     client_lr=0.05, server_lr=0.1, clip_norm=1.0,
                     fused_update=True, cohort_chunk=chunk, **kw)


def make_inputs(cohort: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    batch = {"x": jnp.asarray(rng.normal(0, 1, (cohort, BATCH, D)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, CLASSES, (cohort, BATCH)),
                              jnp.int32)}
    wts = jnp.asarray(rng.uniform(1.0, 5.0, cohort), jnp.float32)
    return batch, wts


def round_args(model, fed, cohort: int, *, seed: int = 0, meta=None):
    batch, wts = make_inputs(cohort, seed)
    state = init_server_state(model, fed, jax.random.PRNGKey(1))
    return state, batch, meta, wts, jax.random.PRNGKey(7)


def temp_bytes(model, fed, cohort: int, **round_kw) -> int:
    rf = make_federated_round(model, fed, **round_kw)
    mem = peak_memory_bytes(rf, *round_args(model, fed, cohort))
    return mem["temp_bytes"]


def run_round(model, fed, cohort: int, **round_kw):
    rf = jax.jit(make_federated_round(model, fed, **round_kw))
    args = round_args(model, fed, cohort)
    t0 = time.perf_counter()
    state, m = rf(*args)
    jax.block_until_ready(state["params"])
    return state, m, time.perf_counter() - t0


def states_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def params_max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in
               zip(jax.tree.leaves(a["params"]), jax.tree.leaves(b["params"])))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the vmap contrast sweep (CI smoke)")
    ap.add_argument("--out", default="BENCH_cohort_scaling.json")
    ap.add_argument("--run-dir", default=None,
                    help="jsonl tracker dir (default: "
                         "benchmarks/runs/cohort_scaling)")
    args = ap.parse_args()
    trk = bench_tracker("cohort_scaling", args.run_dir)

    model = make_mlp_model()

    # --- memory sweep: chunked temp bytes must stay flat in the cohort ---
    trk.log_event("arm_start", {"arm": "memory_sweep"})
    cohorts = (64, 256, 1024)
    chunked_mem = {c: temp_bytes(model, make_fed(c, CHUNK), c)
                   for c in cohorts}
    mem_available = all(v >= 0 for v in chunked_mem.values())
    mem_ratio = (chunked_mem[1024] / max(chunked_mem[64], 1)
                 if mem_available else -1.0)
    # contrast: the vmap executor materialises the whole stacked cohort
    vmap_mem = ({c: temp_bytes(model, make_fed(c), c) for c in (64, 256)}
                if not args.fast else {})

    # --- the cohort=1024 round actually runs ---
    _, m1024, wall_1024 = run_round(model, make_fed(1024, CHUNK), 1024)
    loss_1024 = float(m1024["client_loss"])

    # --- bit identity at cohort=64 ---
    c = 64
    s_vmap, _, _ = run_round(model, make_fed(c), c)
    s_scan, _, _ = run_round(model, make_fed(c, cohort_strategy="scan"), c)
    s_full, _, _ = run_round(model, make_fed(c, c), c)      # chunk = cohort
    s_one, _, _ = run_round(model, make_fed(c, 1), c)
    s_mid, _, _ = run_round(model, make_fed(c, CHUNK), c)
    s_rag, _, _ = run_round(model, make_fed(c, 24), c)      # ragged 64 % 24
    bit_chunks = (states_equal(s_one, s_mid) and states_equal(s_mid, s_full)
                  and states_equal(s_rag, s_mid))
    bit_scan = states_equal(s_one, s_scan)
    vmap_err = params_max_abs_diff(s_full, s_vmap)

    # --- two-tier sharded through_aggregation ctrl vs vmap ---
    tc = 16
    fed_ta = make_fed(tc, meta=True, meta_mode="through_aggregation")
    meta_b = {"x": make_inputs(tc, 3)[0]["x"][0],
              "y": make_inputs(tc, 3)[0]["y"][0]}
    mesh = make_debug_mesh(1, 1)
    gs = cohort_grad_shardings(
        jax.eval_shape(model.init, jax.random.PRNGKey(1)), mesh)
    fed_ta_c = make_fed(tc, 4, meta=True, meta_mode="through_aggregation")

    def run_ta(fed, **kw):
        rf = jax.jit(make_federated_round(model, fed, **kw))
        state, m = rf(*round_args(model, fed, tc, meta=meta_b))
        return state

    ctrl_v = run_ta(fed_ta)["ctrl"]
    ctrl_s = run_ta(fed_ta_c, grad_shardings=gs)["ctrl"]
    hg_err = max(float(jnp.max(jnp.abs(ctrl_v[k] - ctrl_s[k])))
                 for k in ctrl_v)

    report = {
        "benchmark": "cohort_scaling",
        "config": {"model": f"mlp {D}x128x{CLASSES}", "client_batch": BATCH,
                   "local_steps": LOCAL_STEPS, "cohort_chunk": CHUNK,
                   "algorithm": "uga", "backend": jax.default_backend()},
        "chunked_temp_bytes": {str(c): chunked_mem[c] for c in cohorts},
        "vmap_temp_bytes": {str(c): v for c, v in vmap_mem.items()},
        "temp_ratio_1024_over_64": round(mem_ratio, 4),
        "round_1024": {"wall_s_incl_compile": round(wall_1024, 2),
                       "client_loss": loss_1024},
        "chunk_eq_cohort_vs_vmap_max_abs_err": vmap_err,
        "hypergrad_ctrl_max_abs_err_sharded_vs_vmap": hg_err,
        "pass_memory_flat_1p3x": bool(mem_available and mem_ratio <= 1.3),
        "pass_round_1024_finite": bool(np.isfinite(loss_1024)),
        "pass_chunk_size_invariant_bitwise": bool(bit_chunks),
        "pass_stream_eq_prerefactor_scan_bitwise": bool(bit_scan),
        "pass_chunk_eq_cohort_vs_vmap_1e6": bool(vmap_err <= 1e-6),
        "pass_hypergrad_1e5": bool(hg_err <= 1e-5),
    }
    trk.log_event("bench_report", report)
    trk.finish()
    report = write_bench_report(args.out, report, bench="cohort_scaling")
    print(json.dumps(report, indent=1))
    if not all(v for k, v in report.items() if k.startswith("pass_")):
        sys.exit(1)


if __name__ == "__main__":
    main()
