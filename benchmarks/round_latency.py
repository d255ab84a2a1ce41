"""Round-engine latency: legacy tree-map server step + per-round dispatch
vs the fused flat-buffer Pallas engine + scanned multi-round driver.

Measures the per-round hot path every benchmark table exercises
(Eq. 14 aggregate -> clip -> server optimizer -> FedMeta step) on the CPU
smoke config, end to end as the drivers actually run it: the legacy
arm dispatches one jitted round per call and syncs metrics to host every
round (exactly the old ``launch/train.py`` loop); the fused arm compiles
``rounds_per_call`` rounds into one donated ``lax.scan`` program and syncs
once per chunk.

A scan-strategy section times the client-sequential cohort (the cohort
core at chunk 1, whose scan carry IS the dtype-group buffers;
kernels/fused_update ``accumulate_pass``) the same two ways: the legacy
tree-map engine with per-round dispatch vs the fused engine under the
scanned driver.

A backward section times the *differentiated* server step — the
meta-through-aggregation hypergradient d(meta loss)/d(client weights,
server lr) — through the fused engine's hand-written custom VJP vs XLA
autodiff through the legacy tree-map path, and gates their agreement.

Emits ``BENCH_round_latency.json``: rounds/s for both arms, speedup,
full-model tree traversals per server step, hypergradient steps/s for
both backward arms, and the fused-vs-legacy numerics agreement for both
directions (forward must be <= 1e-5 relative after a fresh round).

Usage:  PYTHONPATH=src python benchmarks/round_latency.py [--fast]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from common import bench_tracker, write_bench_report
from repro.configs.base import FedConfig
from repro.core import (init_server_state, make_federated_round,
                        RoundFnCache, server_opt, stack_round_inputs)
from repro.core import flat as flat_mod
from repro.kernels.fused_update.ops import (TRAVERSALS_FUSED,
                                            TRAVERSALS_LEGACY,
                                            flat_accumulate,
                                            flat_apply_groups)
from repro.models.model import Model

# CPU smoke config: small enough to run everywhere, large enough that the
# server step and per-round dispatch overheads are both visible.
D, H, CLASSES = 64, 128, 10
COHORT, BATCH, LOCAL_STEPS = 8, 32, 2
SERVER_OPT, CLIP = "adam", 1.0
ROUNDS_PER_CALL = 8


def make_mlp_model():
    def init(k):
        k1, k2 = jax.random.split(k)
        return {"w1": jax.random.normal(k1, (D, H)) * 0.3,
                "w2": jax.random.normal(k2, (H, CLASSES)) * 0.3}

    def loss(w, batch, rng=None):
        logits = jnp.tanh(batch["x"] @ w["w1"]) @ w["w2"]
        l = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), batch["y"][:, None], 1))
        return l, {}

    return Model(name="bench-mlp", init=init, loss=loss)


def make_fed(fused: bool, server_opt: str = SERVER_OPT,
             strategy: str = "vmap") -> FedConfig:
    return FedConfig(algorithm="uga", meta=True, cohort=COHORT,
                     local_steps=LOCAL_STEPS, client_lr=0.05, server_lr=0.1,
                     meta_lr=0.05, server_opt=server_opt, clip_norm=CLIP,
                     cohort_strategy=strategy, fused_update=fused)


def gen_rounds(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    batches, metas = [], []
    for _ in range(n):
        batches.append({
            "x": jnp.asarray(rng.normal(0, 1, (COHORT, BATCH, D)),
                             jnp.float32),
            "y": jnp.asarray(rng.integers(0, CLASSES, (COHORT, BATCH)),
                             jnp.int32)})
        metas.append({"x": batches[-1]["x"][0], "y": batches[-1]["y"][0]})
    wts = jnp.asarray(rng.uniform(1.0, 5.0, COHORT), jnp.float32)
    return batches, metas, wts


def run_legacy(model, rounds: int, strategy: str = "vmap"):
    """One dispatch + one host metric sync per round (the old driver)."""
    fed = make_fed(fused=False, strategy=strategy)
    rf = jax.jit(make_federated_round(model, fed), donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    batches, metas, wts = gen_rounds(rounds)
    state = init_server_state(model, fed, key)
    state, m = rf(state, batches[0], metas[0], wts, key)   # compile
    float(m["client_loss"])
    state = init_server_state(model, fed, key)
    t0 = time.perf_counter()
    for r in range(rounds):
        state, m = rf(state, batches[r], metas[r], wts,
                      jax.random.fold_in(key, r))
        float(m["client_loss"])                            # per-round sync
    jax.block_until_ready(state["params"])
    return rounds / (time.perf_counter() - t0)


def run_fused_scanned(model, rounds: int, strategy: str = "vmap"):
    """Fused server step, K rounds per dispatch, one sync per chunk."""
    assert rounds % ROUNDS_PER_CALL == 0
    fed = make_fed(fused=True, strategy=strategy)
    rf = RoundFnCache(model, fed)(ROUNDS_PER_CALL)
    key = jax.random.PRNGKey(0)
    batches, metas, wts = gen_rounds(rounds)
    K = ROUNDS_PER_CALL
    chunks = [stack_round_inputs(
        batches[r0:r0 + K], metas[r0:r0 + K], [wts] * K,
        [jax.random.fold_in(key, r0 + j) for j in range(K)])
        for r0 in range(0, rounds, K)]
    state = init_server_state(model, fed, key)
    state, m = rf(state, *chunks[0])                       # compile
    float(m["client_loss"][-1])
    state = init_server_state(model, fed, key)
    t0 = time.perf_counter()
    for cb, mb, wK, rngs in chunks:
        state, m = rf(state, cb, mb, wK, rngs)
        float(m["client_loss"][-1])                        # per-chunk sync
    jax.block_until_ready(state["params"])
    return rounds / (time.perf_counter() - t0)


def numerics_agreement(model, server_opt: str, rounds: int = 1,
                       strategy: str = "vmap") -> float:
    """Max relative param error, fused vs legacy, after ``rounds`` rounds
    of the full pipeline (aggregate -> clip -> ``server_opt`` -> meta).

    The engines reduce in different orders (flat buffer vs per-leaf), so
    G agrees to ~1 fp32 ulp; through the smooth optimizers (sgd/sgdm) that
    stays ~1 ulp in the params — the <=1e-5 acceptance gate.  adam/yogi at
    t=1 step by ~lr*sign(g), so an ulp of difference near g=0 flips a sign
    regardless of implementation; their figure is reported informationally
    and their math is unit-tested against the legacy path on identical
    inputs in tests/test_fused_update.py."""
    key = jax.random.PRNGKey(0)
    batches, metas, wts = gen_rounds(rounds, seed=7)
    params = {}
    for fused in (False, True):
        fed = make_fed(fused, server_opt, strategy)
        rf = jax.jit(make_federated_round(model, fed))
        state = init_server_state(model, fed, key)
        for r in range(rounds):
            state, _ = rf(state, batches[r], metas[r], wts,
                          jax.random.fold_in(key, r))
        params[fused] = state["params"]
    return max(
        float(jnp.max(jnp.abs(a - b) / (jnp.abs(b) + 1e-6)))
        for a, b in zip(jax.tree.leaves(params[True]),
                        jax.tree.leaves(params[False])))


def metrics_agreement(model, server_opt: str = SERVER_OPT,
                      strategy: str = "vmap") -> float:
    """Max relative round-metric (client_loss/grad_norm/meta_loss) diff,
    fused vs legacy, one fresh round of the *benchmarked* configuration.
    The metrics are smooth in the parameters, so this gates the timed
    optimizer (adam) without the sign-step amplification above."""
    key = jax.random.PRNGKey(0)
    batches, metas, wts = gen_rounds(1, seed=7)
    out = {}
    for fused in (False, True):
        fed = make_fed(fused, server_opt, strategy)
        rf = jax.jit(make_federated_round(model, fed))
        state = init_server_state(model, fed, key)
        _, out[fused] = rf(state, batches[0], metas[0], wts, key)
    return max(abs(float(out[True][k]) - float(out[False][k]))
               / (abs(float(out[False][k])) + 1e-9)
               for k in out[False])


def _hypergrad_fns(model):
    """Jitted d(meta loss)/d(w_logits, log_lr) through one adam server step
    over a stacked cohort gradient — the through_aggregation hot path —
    via (a) the fused engine's custom VJP and (b) XLA autodiff through the
    legacy tree-map step.  Warm (t=5) state: the t=1 sign-step's weight
    hypergradient is ~0 (scale-invariant in G) and times nothing real."""
    key = jax.random.PRNGKey(11)
    params = model.init(key)
    spec = flat_mod.make_flat_spec(params)
    rng = np.random.default_rng(11)
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(0, 0.5, (COHORT,) + p.shape),
                              jnp.float32), params)
    wts = jnp.asarray(rng.uniform(1.0, 5.0, COHORT), jnp.float32)
    meta = {"x": jnp.asarray(rng.normal(0, 1, (BATCH, D)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, CLASSES, BATCH), jnp.int32)}
    m_tree = jax.tree.map(
        lambda p: jnp.asarray(0.3 * rng.normal(0, 1, p.shape), jnp.float32),
        params)
    v_tree = jax.tree.map(
        lambda p: jnp.asarray(0.1 + np.abs(rng.normal(0, 1, p.shape)),
                              jnp.float32), params)
    t0 = jnp.asarray(5, jnp.int32)

    g_stacks = flat_mod.flatten_stacked(spec, grads)
    accum = flat_accumulate()

    def normalized(w_logits):
        w = wts * jnp.exp(w_logits)
        return w / jnp.maximum(jnp.sum(w), 1e-30)

    def fused_loss(w_logits, log_lr):
        st = {"m": tuple(flat_mod.flatten_tree(spec, m_tree)),
              "v": tuple(flat_mod.flatten_tree(spec, v_tree)), "t": t0}
        wn = normalized(w_logits)
        Gs = flat_mod.zeros_flat(spec)
        for k in range(COHORT):
            Gs = [accum(G, g[k], wn[k]) for G, g in zip(Gs, g_stacks)]
        new_p, _, _ = flat_apply_groups(
            spec, Gs, jnp.sqrt(flat_mod.flat_sq_norm(Gs)), params, st,
            opt=SERVER_OPT, lr=jnp.exp(log_lr), clip_norm=CLIP)
        return model.loss(new_p, meta)[0]

    def legacy_loss(w_logits, log_lr):
        wn = normalized(w_logits)
        G = jax.tree.map(lambda g: jnp.tensordot(wn, g, 1), grads)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(G)))
        s = jnp.minimum(1.0, CLIP / jnp.maximum(gn, 1e-9))
        G = jax.tree.map(lambda x: x * s, G)
        new_p, _ = server_opt.apply(
            SERVER_OPT, {"m": m_tree, "v": v_tree, "t": t0}, params, G,
            jnp.exp(log_lr))
        return model.loss(new_p, meta)[0]

    args = (jnp.zeros((COHORT,), jnp.float32), jnp.log(jnp.float32(0.1)))
    return (jax.jit(jax.grad(fused_loss, argnums=(0, 1))),
            jax.jit(jax.grad(legacy_loss, argnums=(0, 1))), args)


def run_hypergrad(model, iters: int):
    """Time both backward arms; return (per-s fused, per-s legacy,
    agreement rel err scale-normalized over the weight hypergradient)."""
    f_fn, l_fn, args = _hypergrad_fns(model)
    out = {}
    for name, fn in (("fused_vjp", f_fn), ("legacy_autodiff", l_fn)):
        g = fn(*args)
        jax.block_until_ready(g)                       # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            g = fn(*args)
        jax.block_until_ready(g)
        out[name] = iters / (time.perf_counter() - t0)
    (f_wl, f_lr), (l_wl, l_lr) = f_fn(*args), l_fn(*args)
    rel = max(
        float(jnp.max(jnp.abs(f_wl - l_wl))) /
        max(float(jnp.max(jnp.abs(l_wl))), 1e-12),
        abs(float(f_lr) - float(l_lr)) / max(abs(float(l_lr)), 1e-12))
    return out["fused_vjp"], out["legacy_autodiff"], rel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer timed rounds (CI smoke)")
    ap.add_argument("--out", default="BENCH_round_latency.json")
    ap.add_argument("--run-dir", default=None,
                    help="jsonl tracker dir (default: "
                         "benchmarks/runs/round_latency)")
    args = ap.parse_args()
    rounds = 48 if args.fast else 192
    trk = bench_tracker("round_latency", args.run_dir)

    model = make_mlp_model()
    trk.log_event("arm_start", {"arm": "legacy", "rounds": rounds})
    rps_legacy = run_legacy(model, rounds)
    trk.log_event("arm_start", {"arm": "fused_scanned", "rounds": rounds})
    rps_fused = run_fused_scanned(model, rounds)
    rel_err = max(numerics_agreement(model, "sgd"),
                  numerics_agreement(model, "sgdm"),
                  metrics_agreement(model, SERVER_OPT))
    rel_err_adam = numerics_agreement(model, "adam")
    speedup = rps_fused / rps_legacy
    hg_fused, hg_legacy, hg_rel = run_hypergrad(
        model, iters=rounds * 2)

    # scan strategy (client-sequential): fused engine + scanned driver vs
    # the legacy tree-map engine + per-round dispatch
    scan_rounds = max(rounds // 2, ROUNDS_PER_CALL)
    scan_rounds -= scan_rounds % ROUNDS_PER_CALL
    rps_scan_legacy = run_legacy(model, scan_rounds, strategy="scan")
    rps_scan_fused = run_fused_scanned(model, scan_rounds, strategy="scan")
    scan_speedup = rps_scan_fused / rps_scan_legacy
    scan_rel_err = max(numerics_agreement(model, "sgd", strategy="scan"),
                       numerics_agreement(model, "sgdm", strategy="scan"),
                       metrics_agreement(model, SERVER_OPT,
                                         strategy="scan"))

    report = {
        "benchmark": "round_latency",
        "config": {"model": f"mlp {D}x{H}x{CLASSES}", "cohort": COHORT,
                   "client_batch": BATCH, "local_steps": LOCAL_STEPS,
                   "algorithm": "uga+meta", "server_opt": SERVER_OPT,
                   "clip_norm": CLIP, "rounds": rounds,
                   "rounds_per_call": ROUNDS_PER_CALL,
                   "backend": jax.default_backend()},
        "legacy": {"rounds_per_s": round(rps_legacy, 2),
                   "traversals_per_server_step":
                       TRAVERSALS_LEGACY[SERVER_OPT]},
        "fused_scanned": {"rounds_per_s": round(rps_fused, 2),
                          "traversals_per_server_step": TRAVERSALS_FUSED},
        "speedup": round(speedup, 3),
        "numerics_max_rel_err": rel_err,
        "numerics_rel_err_adam_signstep": rel_err_adam,
        # meta-through-aggregation hypergradient (one adam server step +
        # meta loss, d/d(client weights, server lr)); CPU interpret-mode
        # Pallas — the TPU Mosaic timing is a ROADMAP item
        "backward": {
            "hypergrads_per_s_fused_vjp": round(hg_fused, 2),
            "hypergrads_per_s_legacy_autodiff": round(hg_legacy, 2),
            "relative": round(hg_fused / hg_legacy, 3),
            "hypergrad_max_rel_err": hg_rel,
        },
        # client-sequential strategy: both arms stream the same aggregate
        # (K Pallas FMAs into the flat dtype-group buffers); the fused arm
        # then runs clip/opt/write in one pass, the legacy arm as tree-maps,
        # so the numerics gate mirrors the vmap one (smooth opts + adam
        # metrics)
        "scan_strategy": {
            "rounds": scan_rounds,
            "legacy": {"rounds_per_s": round(rps_scan_legacy, 2)},
            "fused_scanned": {"rounds_per_s": round(rps_scan_fused, 2)},
            "speedup": round(scan_speedup, 3),
            "numerics_max_rel_err": scan_rel_err,
            "pass_speedup_1p2x": bool(scan_speedup >= 1.2),
            "pass_numerics_1e5": bool(scan_rel_err <= 1e-5),
        },
        "pass_speedup_1p5x": bool(speedup >= 1.5),
        "pass_numerics_1e5": bool(rel_err <= 1e-5),
        # the scalar d/d(log lr) reduces ~20k elements in fp32; the two
        # engines' reduction orders differ by ~sqrt(N)*eps32 ~ 2e-5, so the
        # scalar gate sits at 5e-5 (the per-leaf weight hypergradients
        # agree to ~1e-7; the tests gate those at 1e-5)
        "pass_hypergrad_numerics_5e5": bool(hg_rel <= 5e-5),
    }
    trk.log_event("bench_report", report)
    trk.finish()
    report = write_bench_report(args.out, report, bench="round_latency")
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
