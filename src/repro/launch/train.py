"""End-to-end federated training driver.

Runs REAL federated rounds (host data pipeline -> jitted round_fn) on
whatever devices exist — a debug mesh on CPU, the production mesh on a pod.
This is the driver behind ``examples/federated_lm.py`` and the paper-claim
benchmarks.  The loop itself lives in
:class:`repro.core.trainer.FederatedTrainer`; this module only assembles
(model, FedConfig, FederatedData) from CLI flags.

``--algorithm`` accepts ANY name in the ClientAlgorithm registry
(``repro.core.algorithms``) — the built-ins (uga / fedavg / fedprox /
fednova) plus user plugins: ``--plugin my_module`` imports ``my_module``
(repeatable, importable from PYTHONPATH) BEFORE the remaining flags are
parsed, so a one-file ``register_algorithm`` / ``register_executor`` /
``register_engine`` plugin is selectable by name in the same invocation:

  PYTHONPATH=src:. python -m repro.launch.train --plugin myalgo \
      --algorithm myalgo --arch smollm-360m-smoke --rounds 3 ...

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m-smoke \
      --rounds 50 --cohort 4 --client-batch 8 --seq 128 --algorithm uga --meta
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.comm import available_codecs
from repro.configs import FedConfig, get_arch
from repro.core import FederatedTrainer, available_algorithms
from repro.data.partition import partition_iid
from repro.data.pipeline import FederatedData
from repro.data.synthetic import synthetic_tokens
from repro.models.model import build_model


def build_synthetic_fed_data(cfg, *, num_clients: int, examples: int,
                             seq: int, iid: bool, seed: int = 0,
                             meta_fraction: float = 0.01) -> FederatedData:
    rng = np.random.default_rng(seed)
    ds = synthetic_tokens(rng, n=examples, seq_len=seq + 1,
                          vocab=cfg.vocab_size, num_clients=num_clients)
    arrays = {"tokens": ds.tokens}
    if iid:
        parts = partition_iid(rng, examples, num_clients)
    else:
        parts = [np.where(ds.role == c)[0] for c in range(num_clients)]
        parts = [p if p.size else np.array([0]) for p in parts]
    n_meta = max(int(examples * meta_fraction), 8)
    meta_idx = rng.choice(examples, n_meta, replace=False)
    shared_idx = rng.choice(examples, n_meta, replace=False)
    return FederatedData(arrays=arrays, client_indices=parts,
                         meta_indices=meta_idx, shared_indices=shared_idx,
                         seed=seed)


def run_training(arch: str, *, rounds: int, cohort: int, client_batch: int,
                 seq: int, layers: Optional[int] = None,
                 algorithm: str = "uga", meta: bool = True,
                 share: bool = False, local_steps: int = 2,
                 local_epochs: int = 1, client_lr: float = 0.01,
                 server_lr: Optional[float] = None,
                 meta_lr: Optional[float] = None, server_opt: str = "sgd",
                 meta_mode: str = "post", ctrl_lr: float = 0.01,
                 participation: float = 1.0, codec: str = "none",
                 error_feedback: bool = False, topk_ratio: float = 0.01,
                 num_clients: int = 32, examples: int = 2048,
                 iid: bool = False, seed: int = 0, log_every: int = 10,
                 ckpt_path: Optional[str] = None,
                 resume: Optional[str] = None, strategy: str = "vmap",
                 cohort_chunk: Optional[int] = None,
                 executor: Optional[str] = None, mesh_model: int = 1,
                 dtype=jnp.float32, fused: bool = False,
                 rounds_per_call: int = 1, engine: Optional[str] = None,
                 async_buffer: int = 0, async_capacity: int = 0,
                 async_max_staleness: int = 0,
                 staleness_mode: str = "invsqrt",
                 fault_profile: str = "none", fault_drop: float = -1.0,
                 fault_crash: float = -1.0, fault_delay: float = -1.0,
                 fault_max_delay: int = -1, fault_garble: float = -1.0,
                 fault_garble_scale: float = -1.0,
                 round_deadline: float = 0.0, retry_backoff: int = 0,
                 sanitize: bool = False, tracker: Optional[str] = None,
                 run_dir: Optional[str] = None, profile: int = 0,
                 profile_start: int = 0, trace_summary: bool = False,
                 roofline: bool = False, ckpt_every: int = 0,
                 keep_last: int = 3, keep_every: int = 0):
    """``rounds_per_call=K``: K rounds compile into ONE donated scan program
    and metrics sync to host once per K rounds.  ``fused``: flat-buffer
    Pallas server engine (see kernels/fused_update).  ``resume``: path of a
    full-server-state checkpoint written by ``ckpt_path`` — training
    continues from its round counter toward ``rounds`` total — or
    ``"auto"``: the newest blob in ``run_dir``'s managed checkpoint store.
    ``sanitize``: debug mode — enables ``jax_debug_nans`` and re-jits the
    round under :mod:`jax.experimental.checkify` with NaN/Inf/OOB checks on
    the flat aggregate buffers (see :mod:`repro.core.sanitize`); slower,
    but a poisoned payload fails the round it appears with an error naming
    the flat dtype group.

    Observability (``repro.obs``): ``tracker`` is a registry name or comma
    list (``jsonl,console``) writing under ``run_dir``; ``profile=N``
    captures a JAX trace for rounds ``[profile_start, profile_start+N)``
    into ``run_dir/profile``.  ``trace_summary`` parses that capture
    into a ``profile_summary`` tracker event (top ops by self time,
    busy/gap, per-phase attribution) when the window closes;
    ``roofline`` emits a ``roofline`` event per compiled round program
    (trip-count-aware predicted cost + measured rounds/s — inspect with
    ``python -m repro.roofline.report <run_dir>``).  ``layers``: keep
    only the model's first ``layers`` blocks, every width unchanged (a
    depth cut, for runs that must compile quickly).  With a
    ``run_dir``, the trainer keeps a
    managed checkpoint store in ``run_dir/checkpoints`` (a save every
    ``ckpt_every`` rounds — 0: once at run end — with ``keep_last`` /
    ``keep_every`` retention)."""
    cfg = get_arch(arch)
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    model = build_model(cfg, dtype=dtype, loss_chunk=256)
    fed = FedConfig(
        algorithm=algorithm, meta=meta, share=share, cohort=cohort,
        local_steps=local_steps, local_epochs=local_epochs,
        client_lr=client_lr,
        server_lr=server_lr if server_lr is not None else client_lr,
        meta_lr=meta_lr if meta_lr is not None else client_lr,
        server_opt=server_opt, meta_mode=meta_mode, ctrl_lr=ctrl_lr,
        participation=participation, codec=codec,
        error_feedback=error_feedback, topk_ratio=topk_ratio,
        cohort_strategy=strategy, cohort_chunk=cohort_chunk,
        lr_decay=0.992, fused_update=fused,
        engine=engine, async_buffer=async_buffer,
        async_capacity=async_capacity,
        async_max_staleness=async_max_staleness,
        staleness_mode=staleness_mode, fault_profile=fault_profile,
        fault_drop=fault_drop, fault_crash=fault_crash,
        fault_delay=fault_delay, fault_max_delay=fault_max_delay,
        fault_garble=fault_garble, fault_garble_scale=fault_garble_scale,
        round_deadline=round_deadline, retry_backoff=retry_backoff)
    if sanitize:
        # catch NaNs in UNsanitized code too (jit deoptimizes and re-checks
        # on a NaN output); the checkify probes stay the primary, named
        # diagnostics — debug_nans is the coarse backstop
        jax.config.update("jax_debug_nans", True)
    data = build_synthetic_fed_data(cfg, num_clients=num_clients,
                                    examples=examples, seq=seq, iid=iid,
                                    seed=seed)
    round_kwargs, mesh = {}, None
    if executor == "sharded":
        # two-tier aggregation over every visible device: the cohort axis
        # splits across the mesh data axis, each shard streams its clients
        # through the chunked core, one psum reduces the partials
        from repro.launch.mesh import make_auto_mesh
        from repro.sharding.specs import cohort_grad_shardings
        mesh = make_auto_mesh(mesh_model)
        params_shape = jax.eval_shape(
            model.init, jax.random.PRNGKey(seed))
        round_kwargs["grad_shardings"] = cohort_grad_shardings(
            params_shape, mesh, strategy)
        print(f"[train] sharded executor on mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    elif executor is not None:
        round_kwargs["executor"] = executor
    trainer = FederatedTrainer(
        model, fed, rounds_per_call=rounds_per_call, seed=seed,
        sanitize=sanitize, tracker=tracker, run_dir=run_dir,
        checkpoint_every=ckpt_every if run_dir is not None else None,
        keep_last=keep_last, keep_every=keep_every, profile=profile,
        profile_start=profile_start, trace_summary=trace_summary,
        roofline=roofline, **round_kwargs)
    if resume == "auto":
        if run_dir is None:
            raise ValueError(
                "--resume auto reads the managed checkpoint store and "
                "needs --run-dir; pass an explicit checkpoint path "
                "otherwise")
        step = trainer.resume_latest()
        print(f"[train] resume auto: "
              + (f"round {step} from {run_dir}/checkpoints" if step
                 is not None else "empty store, starting fresh"))
    elif resume:
        extra = trainer.restore(resume)
        print(f"[train] resumed {resume} at round {trainer.round} "
              f"(saved by arch={extra.get('arch')})")
    if mesh is not None:
        # the round program returns the state replicated over the mesh;
        # start it there, so round 0 compiles the program every later
        # round runs
        trainer.state = jax.device_put(
            trainer.state, NamedSharding(mesh, PartitionSpec()))
    meta_bs = min(client_batch * 2, 32)
    history = trainer.run(data, rounds=rounds, cohort=cohort,
                          batch=client_batch, meta_batch=meta_bs,
                          share=share, log_every=log_every)
    if ckpt_path:
        trainer.save(ckpt_path, extra={"arch": arch, "rounds": rounds,
                                       "algorithm": algorithm})
        print(f"[train] saved server state to {ckpt_path}")
    trainer.finish()
    return trainer.state, history


def main():
    # --plugin modules must import (and hit the registries) before the
    # main parser freezes --algorithm's choices
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--plugin", action="append", default=[],
                     help="module to import before parsing the remaining "
                          "flags — its register_algorithm/executor/engine "
                          "calls make the names selectable (repeatable)")
    plug_args, _ = pre.parse_known_args()
    for mod in plug_args.plugin:
        importlib.import_module(mod)

    ap = argparse.ArgumentParser(parents=[pre])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--client-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--algorithm", default="uga",
                    choices=list(available_algorithms()),
                    help="any registered client algorithm "
                         "(repro.core.algorithms)")
    ap.add_argument("--meta", action="store_true")
    ap.add_argument("--no-meta", dest="meta", action="store_false")
    ap.set_defaults(meta=True)
    ap.add_argument("--share", action="store_true")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-epochs", type=int, default=1,
                    help="E: passes over the local microbatch schedule")
    ap.add_argument("--client-lr", type=float, default=0.01)
    ap.add_argument("--server-lr", type=float, default=None,
                    help="eta_g (default: --client-lr); applied for "
                         "true-gradient algorithms (uga/fednova) and any "
                         "non-SGD server optimizer")
    ap.add_argument("--meta-lr", type=float, default=None,
                    help="eta_meta (default: --client-lr)")
    ap.add_argument("--server-opt", default="sgd",
                    choices=["sgd", "sgdm", "adam", "yogi"])
    ap.add_argument("--strategy", default="vmap",
                    help="cohort executor: client-parallel vmap, "
                         "client-sequential scan, or any registered "
                         "executor name")
    ap.add_argument("--cohort-chunk", type=int, default=None,
                    help="stream the cohort through the chunked executor "
                         "in slices of this many clients — peak gradient "
                         "memory is one chunk, results are bit-identical "
                         "for every chunk size")
    ap.add_argument("--executor", default=None,
                    help="cohort-executor registry name; 'sharded' builds "
                         "a (data, model) mesh over all visible devices "
                         "and runs the two-tier shard_map aggregation")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size of the --executor sharded mesh "
                         "(the data axis takes the remaining devices)")
    ap.add_argument("--meta-mode", default="post",
                    choices=["post", "through_aggregation"],
                    help="FedMeta step: post-aggregation parameter step, or "
                         "hypergradients through the aggregation (needs an "
                         "engine with the capability, i.e. --fused)")
    ap.add_argument("--ctrl-lr", type=float, default=0.01,
                    help="controllable-weights step size "
                         "(--meta-mode through_aggregation)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="<1: straggler dropout — per-round probability a "
                         "sampled client reports; dropped clients' weights "
                         "are zeroed inside the aggregation")
    ap.add_argument("--codec", default="none",
                    choices=list(available_codecs()),
                    help="client->server uplink gradient codec "
                         "(repro.comm); lossy codecs need --fused")
    ap.add_argument("--error-feedback", action="store_true",
                    help="keep per-client compression residuals "
                         "(state['comm']) and re-add them before each "
                         "round's encode (needs a lossy --codec)")
    ap.add_argument("--topk-ratio", type=float, default=0.01,
                    help="fraction of elements the 'topk' codec ships")
    ap.add_argument("--num-clients", type=int, default=32)
    ap.add_argument("--log-every", type=int, default=10,
                    help="print a history record every N rounds (0: quiet)")
    ap.add_argument("--examples", type=int, default=2048)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default=None,
                    help="checkpoint written by --ckpt to continue from, "
                         "or 'auto': the newest blob in --run-dir's "
                         "managed store")
    ap.add_argument("--history-out", default=None)
    from repro.obs import available_trackers
    ap.add_argument("--tracker", default=None,
                    help="metrics-tracker registry name or comma list "
                         f"(repro.obs): {', '.join(available_trackers())}; "
                         "file trackers write under --run-dir "
                         "(default: noop)")
    ap.add_argument("--run-dir", default=None,
                    help="run directory for tracker files, profiler "
                         "traces, and the managed checkpoint store")
    ap.add_argument("--profile", type=int, default=0,
                    help="capture a jax.profiler trace for N rounds into "
                         "<run-dir>/profile (0: off)")
    ap.add_argument("--profile-start", type=int, default=0,
                    help="first round of the --profile capture window")
    ap.add_argument("--trace-summary", action="store_true",
                    help="when the --profile window closes, parse the "
                         "trace into a profile_summary tracker event "
                         "(top ops by self time, busy/gap, per-phase "
                         "attribution); needs --profile N")
    ap.add_argument("--roofline", action="store_true",
                    help="emit a roofline tracker event per compiled "
                         "round program: trip-count-aware predicted "
                         "compute/memory/collective cost + measured "
                         "rounds/s (python -m repro.roofline.report "
                         "<run-dir> to inspect)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="managed-store save period in rounds (needs "
                         "--run-dir; 0: one save at run end)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="managed store: newest saves retained")
    ap.add_argument("--keep-every", type=int, default=0,
                    help="managed store: steps divisible by N are kept "
                         "forever (0: off)")
    ap.add_argument("--fused", action="store_true",
                    help="fused flat-buffer Pallas server engine")
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="scan K rounds into one compiled program")
    from repro.core import available_engines
    from repro.sim.faults import FAULT_PROFILES
    ap.add_argument("--engine", default=None,
                    choices=list(available_engines()),
                    help="server-engine registry name (default derives "
                         "legacy_tree/fused_flat from --fused); "
                         "'buffered_async' selects the fault-tolerant "
                         "buffered asynchronous runtime")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="buffered_async: server steps every K arrived "
                         "deltas (0: cohort)")
    ap.add_argument("--async-capacity", type=int, default=0,
                    help="buffered_async: delta-pool slots (0: 2*cohort)")
    ap.add_argument("--async-max-staleness", type=int, default=0,
                    help="buffered_async: evict deltas staler than this "
                         "many server versions (0: unbounded)")
    ap.add_argument("--staleness-mode", default="invsqrt",
                    choices=["none", "inv", "invsqrt"],
                    help="flush-weight discount of stale deltas")
    ap.add_argument("--fault-profile", default="none",
                    choices=sorted(FAULT_PROFILES),
                    help="named client-fault profile (repro.sim.faults); "
                         "--fault-* flags override individual rates")
    ap.add_argument("--fault-drop", type=float, default=-1.0,
                    help="P(uplink report lost); <0 uses the profile")
    ap.add_argument("--fault-crash", type=float, default=-1.0,
                    help="P(client dies mid-round); <0 uses the profile")
    ap.add_argument("--fault-delay", type=float, default=-1.0,
                    help="P(report arrives rounds late); <0 uses the "
                         "profile")
    ap.add_argument("--fault-max-delay", type=int, default=-1,
                    help="late reports land 1..N rounds late; <0 uses the "
                         "profile")
    ap.add_argument("--fault-garble", type=float, default=-1.0,
                    help="P(payload corrupted) — buffered_async only; <0 "
                         "uses the profile")
    ap.add_argument("--fault-garble-scale", type=float, default=-1.0,
                    help="corrupted payloads scale by U(-s, s); <0 uses "
                         "the profile")
    ap.add_argument("--sanitize", action="store_true",
                    help="debug mode: jax_debug_nans + a checkify-wrapped "
                         "round with NaN/Inf/OOB checks on the flat "
                         "aggregate buffers (repro.core.sanitize)")
    ap.add_argument("--round-deadline", type=float, default=0.0,
                    help="sync barrier timeout in simulated round-units "
                         "(0: wait forever)")
    ap.add_argument("--retry-backoff", type=int, default=0,
                    help=">0: re-enqueue failed clients after "
                         "backoff * 2^attempt rounds")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    state, history = run_training(
        args.arch, rounds=args.rounds, cohort=args.cohort,
        client_batch=args.client_batch, seq=args.seq,
        algorithm=args.algorithm, meta=args.meta, share=args.share,
        local_steps=args.local_steps, local_epochs=args.local_epochs,
        client_lr=args.client_lr, server_lr=args.server_lr,
        meta_lr=args.meta_lr, server_opt=args.server_opt,
        meta_mode=args.meta_mode, ctrl_lr=args.ctrl_lr,
        participation=args.participation, codec=args.codec,
        error_feedback=args.error_feedback, topk_ratio=args.topk_ratio,
        strategy=args.strategy, cohort_chunk=args.cohort_chunk,
        executor=args.executor, mesh_model=args.mesh_model,
        num_clients=args.num_clients,
        log_every=args.log_every,
        examples=args.examples, iid=args.iid, seed=args.seed,
        ckpt_path=args.ckpt, resume=args.resume, fused=args.fused,
        rounds_per_call=args.rounds_per_call, engine=args.engine,
        async_buffer=args.async_buffer, async_capacity=args.async_capacity,
        async_max_staleness=args.async_max_staleness,
        staleness_mode=args.staleness_mode,
        fault_profile=args.fault_profile, fault_drop=args.fault_drop,
        fault_crash=args.fault_crash, fault_delay=args.fault_delay,
        fault_max_delay=args.fault_max_delay,
        fault_garble=args.fault_garble,
        fault_garble_scale=args.fault_garble_scale,
        round_deadline=args.round_deadline,
        retry_backoff=args.retry_backoff, sanitize=args.sanitize,
        tracker=args.tracker, run_dir=args.run_dir, profile=args.profile,
        profile_start=args.profile_start,
        trace_summary=args.trace_summary, roofline=args.roofline,
        ckpt_every=args.ckpt_every,
        keep_last=args.keep_last, keep_every=args.keep_every)
    if args.history_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.history_out)),
                    exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    main()
