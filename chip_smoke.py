#!/usr/bin/env python3
"""Smoke run of the federated trainer on a TPU, at smollm-360m's full width.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded executor on four chips

One process, no children.  It drives the trainer's main path,
``repro.launch.train.run_training`` over ``FederatedTrainer``, with random
weights from a seed and synthetic client data, and fails loudly in any
phase:

1. device — JAX's first device must be a TPU; there is no CPU path;
2. main path — 3 rounds of UGA + meta with the fused flat-buffer engine
   and an Adam server optimizer, cohort 4 streamed one client at a time.
   Every round metric must be finite and the compiled round program must
   hold Mosaic kernels (``tpu_custom_call``), not interpreted ones;
3. reference — one SGD round on the fused engine and one on the XLA
   ``legacy_tree`` engine from the same seed: server-parameter deltas
   within relative L2 1e-3, round-0 client loss within 1e-4.  Both
   engines stream the cohort through the same accumulate kernel, so each
   Pallas kernel of these programs is also checked against its jnp
   oracle on one flat group;
4. codec — 2 rounds with the int8 uplink codec, which puts the comm
   kernels on the chip;

then prints ``{"ok": true, "device": {...}}`` as its last line.  Times
printed on the way are smoke readings of one run, not benchmark numbers.

``--four-chips`` runs only the two-tier ``sharded`` executor over a (4, 1)
mesh, cohort 8, and compares it with the same rounds run by the chunked
executor on device 0.  That phase keeps smollm-360m's widths and cuts its
depth to ``FOUR_CHIP_LAYERS`` blocks: it checks where the clients run and
what the mesh adds up, which depth does not change, and each 32-block
program would take over three minutes to compile.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-360m"
SEED = 0
# the trainer's main path: UGA with the post-aggregation meta step, fused
# engine, Adam server, cohort 4 streamed one client at a time
MAIN = dict(algorithm="uga", meta=True, fused=True, server_opt="adam",
            cohort=4, cohort_chunk=1, client_batch=4, seq=256,
            local_steps=2, rounds_per_call=1, seed=SEED, log_every=0)
FOUR_CHIP_LAYERS = 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def require_tpu(count: int):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform} "
          f"({devs[0].device_kind}); this script runs only on a TPU")
    check(len(devs) >= count,
          f"needs {count} TPU chips, JAX sees {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


def train(rounds: int, **overrides):
    """One ``run_training`` call with the roofline hook on: it compiles the
    round program ahead of dispatch, and its event carries the compile
    time, the kernel count and the collectives of that program."""
    from repro.launch.train import run_training
    from repro.obs.trackers import MetricsTracker

    class Recorder(MetricsTracker):
        name = "recorder"

        def __init__(self):
            self.events = []

        def log_metrics(self, round_idx, metrics):
            pass

        def log_event(self, name, data=None):
            self.events.append((name, dict(data or {})))

    rec = Recorder()
    kw = {**MAIN, **overrides}
    state, history = run_training(ARCH, rounds=rounds, tracker=rec,
                                  roofline=True, **kw)
    check(len(history) == rounds,
          f"{len(history)} round records for {rounds} rounds")
    for r in history:
        bad = {k: v for k, v in r.items() if not math.isfinite(v)}
        check(not bad, f"non-finite metrics in round {r['round']}: {bad}")
    roof = [d for n, d in rec.events if n == "roofline"]
    check(len(roof) == 1, f"expected one compiled program, got {len(roof)}")
    wall = {}
    for n, d in rec.events:
        if n == "phase" and d["phase"] in ("dispatch", "device_sync"):
            wall[d["round"]] = wall.get(d["round"], 0.0) + d["dur_s"]
    return state, history, roof[0], [wall[r] for r in sorted(wall)]


def report(tag: str, history, roof, wall) -> None:
    for r in history:
        log(f"{tag} round {int(r['round'])}: " + " ".join(
            f"{k}={v:.6g}" for k, v in sorted(r.items()) if k != "round"))
    mem = {k: v / 2**30 for k, v in roof["memory"].items()}
    log(f"{tag} compile_s={roof['compile_s']:.2f} "
        f"tpu_custom_calls={roof['tpu_custom_calls']} "
        f"round_wall_s(smoke reading)={[round(w, 4) for w in wall]} "
        f"memory_analysis_GiB=" + json.dumps(
            {k: round(v, 3) for k, v in mem.items()}))


def peak_gib(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 2**30


def host_params(state, minus=None):
    """The state's parameter leaves in host memory (float64), minus
    ``minus`` leaf by leaf: the comparisons keep nothing on the chip."""
    leaves = [np.asarray(x, np.float64) for x in jax.tree.leaves(
        state["params"])]
    if minus is not None:
        leaves = [x - y for x, y in zip(leaves, minus)]
    return leaves


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over two lists of host arrays."""
    num = sum(float(np.sum(np.square(x - y))) for x, y in zip(a, b))
    den = sum(float(np.sum(np.square(y))) for y in b)
    return math.sqrt(num / max(den, 1e-300))


def kernel_oracles(rows: int = 2**16) -> None:
    """Each Pallas kernel of the programs above against its jnp oracle on
    one (rows, 128) flat group: the same 256-row tile as at the model's
    width, in a grid short enough that kernel and oracle fit side by side."""
    from repro.kernels.comm import kernel as CK
    from repro.kernels.comm import ref as CR
    from repro.kernels.fused_update import kernel as FK
    from repro.kernels.fused_update import ref as FR

    ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    g, acc, p, m = (jax.random.normal(k, (rows, 128), jnp.float32)
                    for k in ks[:4])
    v = jnp.abs(jax.random.normal(ks[4], (rows, 128), jnp.float32))
    scal = jnp.asarray([[0.7, 0.01, 1.5, 2.0]], jnp.float32)
    amax = jnp.max(jnp.abs(g))
    cases = {
        "accumulate_pass": (FK.accumulate_pass(acc, g, 0.3),
                            FR.accumulate_ref(acc, g, 0.3)),
        "update_pass[adam]": (FK.update_pass(g, p, m, v, scal, opt="adam"),
                              FR.update_ref(g, p, m, v, scal, opt="adam")),
        "quantize_i8_pass": (
            CK.quantize_i8_pass(g, 127.0 / amax, amax / 127.0,
                                with_error=True),
            CR.quantize_i8_ref(g, 127.0 / amax, amax / 127.0,
                               with_error=True)),
    }
    q = cases["quantize_i8_pass"][1][0]
    cases["dequant_i8_fma_pass"] = (CK.dequant_i8_fma_pass(acc, q, 0.02),
                                    CR.dequant_i8_fma_ref(acc, q, 0.02))
    for name, (got, want) in cases.items():
        # error relative to 1 + |oracle|: Adam's step divides by sqrt(v)
        err = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))
                                / (1.0 + jnp.abs(y.astype(jnp.float32)))))
                  for x, y in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want)))
        log(f"kernel {name} vs oracle at ({rows}, 128): max err {err:.3g}")
        check(err <= 1e-5, f"{name} disagrees with its oracle: {err}")


def one_chip() -> None:
    from repro.configs import get_arch
    from repro.models.model import build_model

    dev = jax.devices()[0]

    # 2. main path
    state, hist, roof, wall = train(3)
    report("main", hist, roof, wall)
    check(roof["tpu_custom_calls"] > 0,
          "the fused round program holds no tpu_custom_call: the Pallas "
          "kernels did not go through Mosaic")
    log(f"main peak_bytes_in_use={peak_gib(dev):.3f} GiB")
    del state

    # 3. reference: fused engine vs the legacy_tree engine, SGD server
    model = build_model(get_arch(ARCH), dtype=jnp.float32, loss_chunk=256)
    # the trainer's initial parameters: init_server_state(PRNGKey(seed))
    p0 = host_params({"params": model.init(jax.random.PRNGKey(SEED))})
    st, h_f, roof_f, _ = train(1, server_opt="sgd")
    d_fused = host_params(st, minus=p0)
    del st
    st, h_l, _, _ = train(1, server_opt="sgd", fused=False)
    d_legacy = host_params(st, minus=p0)
    del st
    rel = rel_l2(d_fused, d_legacy)
    l_f, l_l = h_f[0]["client_loss"], h_l[0]["client_loss"]
    rel_loss = abs(l_f - l_l) / max(abs(l_l), 1e-30)
    log(f"reference fused vs legacy_tree: delta rel L2 {rel:.3g}, "
        f"client_loss {l_f:.7g} vs {l_l:.7g} (rel {rel_loss:.3g})")
    check(roof_f["tpu_custom_calls"] > 0, "fused SGD program has no kernel")
    check(rel <= 1e-3, f"server deltas differ: rel L2 {rel}")
    check(rel_loss <= 1e-4, f"round-0 client_loss differs: rel {rel_loss}")
    del d_fused, d_legacy, p0
    kernel_oracles()

    # 4. codec: the int8 uplink puts the comm kernels on the chip
    st, hist, roof, wall = train(2, codec="int8", error_feedback=False)
    report("int8", hist, roof, wall)
    check(roof["tpu_custom_calls"] > 0,
          "the int8 round program holds no tpu_custom_call")
    del st
    log(f"peak_bytes_in_use={peak_gib(dev):.3f} GiB")


def four_chips() -> None:
    devs = jax.devices()
    common = dict(cohort=8, cohort_chunk=1, server_opt="sgd",
                  layers=FOUR_CHIP_LAYERS)
    st, hist, roof, wall = train(2, executor="sharded", mesh_model=1,
                                 **common)
    report("sharded", hist, roof, wall)
    peaks = [peak_gib(d) for d in devs]
    log(f"sharded per-device peak_bytes_in_use GiB: "
        f"{[round(p, 3) for p in peaks]}; "
        f"collectives={json.dumps(roof['per_collective'])}")
    check(roof["per_collective"].get("all-reduce", 0) > 0,
          "the sharded round program has no all-reduce")
    # every shard holds the replicated parameters, so a device below that
    # did no share of the cohort
    param_gib = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(st["params"])) / 2**30
    check(min(peaks) >= param_gib,
          f"a device peaked below the parameter size {param_gib:.3f} GiB: "
          f"{peaks}")
    p_sharded = host_params(st)
    del st
    st, hist_c, roof_c, wall_c = train(2, **common)
    report("chunked", hist_c, roof_c, wall_c)
    rel = rel_l2(p_sharded, host_params(st))
    log(f"sharded vs chunked params rel L2 {rel:.3g}")
    check(rel <= 1e-3, f"sharded and chunked params differ: rel L2 {rel}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded executor on four chips and "
                         "its chunked single-chip comparison")
    args = ap.parse_args(argv)
    require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    log(f"total {time.perf_counter() - t0:.1f} s")
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
