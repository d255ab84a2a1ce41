"""One federated round as a single jit-able SPMD program (Fig. 1), built by
COMPOSING three plugin registries instead of a hand-wired branch tree:

    distribute -> local updating   (ClientAlgorithm registry,
                                    repro.core.algorithms: uga / fedavg /
                                    fedprox / fednova / yours)
               -> uplink codec     (GradientCodec registry, repro.comm:
                                    none / int8 / sign1bit / topk, with
                                    optional per-client error feedback in
                                    state["comm"] — the lossy-transport
                                    simulation, post-meta-mode only)
               -> unbiased aggregation (CohortExecutor registry,
                                    repro.core.executors: chunked / vmap /
                                    scan / sharded, all over the one
                                    streaming cohort core -> a uniform
                                    aggregate handle)
               -> server update    (ServerEngine registry,
                                    repro.core.engines: legacy_tree /
                                    fused_flat, with declared
                                    meta_capabilities)
               -> FedMeta step     (core/meta.py: "post" Eq. 20, or
                                    "through_aggregation" hypergradients if
                                    the engine declares the capability).

``make_federated_round(model, fed)`` returns ``round_fn(state, cohort_batch,
meta_batch, client_weights, rng) -> (state, metrics)`` suitable for
``jax.jit`` with in/out shardings from ``repro.sharding``.  The executor
and engine are resolved from ``fed`` (``cohort_chunk``,
``cohort_strategy``, ``fused_update``, ``grad_shardings``) or overridden by
registry name via the ``algorithm`` / ``executor`` / ``engine`` keywords.
Every synchronous executor streams the cohort through the same core
(``repro.core.aggregate.chunked_cohort_gradient_flat``), so the chunk size
never changes a round's bits; the tests compare each combination with a
plain tree-map reference round.

Partial participation / straggler dropout: ``fed.participation < 1`` draws
a per-round Bernoulli mask over the cohort and zeroes dropped clients'
aggregation weights — inside the streaming accumulate math, so every
executor and engine supports it unchanged (a w=0 client
contributes nothing to Eq. 14 and the surviving weights renormalize).

``rounds_per_call=K`` wraps the round body in ``lax.scan`` so drivers
compile K rounds into ONE donated program and sync metrics to host once per
K rounds; the returned function then takes K-stacked inputs
``(cohort_batches (K, cohort, ...), meta_batches (K, ...),
client_weights (K, cohort), rngs (K, ...))`` and returns K-stacked metrics.
``rounds_per_call=1`` keeps the exact legacy signature.  Drivers should not
call this module directly any more — :class:`repro.core.trainer.
FederatedTrainer` owns the jit cache, chunked sampling, checkpoint/resume
and history assembly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import FedConfig
from repro.core.algorithms import get_algorithm
from repro.core.engines import resolve_engine, tree_global_norm
from repro.core.executors import resolve_executor
from repro.core.flat import flatten_tree, make_flat_spec
from repro.core.meta import meta_update, meta_update_through_cohort
from repro.core.rngtags import PARTICIPATION_FOLD
from repro.core.sanitize import (check_flat_groups, checkify_round,
                                 throw_if_error)
from repro.models.model import Model
from repro.sim.faults import client_failed_mask, fault_streams, resolve_faults

PyTree = Any


def resolve_server_lr(fed: FedConfig) -> float:
    """Effective eta_g.  Algorithms registered with
    ``pseudo_gradient=True`` (fedavg/fedprox) produce parameter deltas that
    are exact parameter averages only under *plain-SGD* with a unit step,
    so lr is forced to 1.0 exactly there; every other combination — a true-
    gradient algorithm (UGA's eta_g, FedNova's normalized direction) or a
    FedOpt server optimizer (FedAdam/FedYogi/FedAvgM on pseudo-gradients) —
    honors ``fed.server_lr``."""
    if not get_algorithm(fed.algorithm).pseudo_gradient \
            or fed.server_opt != "sgd":
        return fed.server_lr
    return 1.0


def init_server_state(model: Model, fed: FedConfig, key, *,
                      engine: Optional[str] = None) -> PyTree:
    params = model.init(key)
    eng = resolve_engine(fed, engine=engine)
    state = {
        "params": params,
        "opt": eng.init_state(params),
        "round": jnp.zeros((), jnp.int32),
    }
    if fed.meta and fed.meta_mode == "through_aggregation":
        # Controllable aggregation: per-client log weight multipliers and a
        # log server step size, meta-learned through the engine's VJP.
        state["ctrl"] = {
            "w_logits": jnp.zeros((fed.cohort,), jnp.float32),
            "log_lr": jnp.log(jnp.float32(resolve_server_lr(fed))),
        }
    # lazy: repro.comm imports repro.core.flat, which triggers this package
    from repro.comm import init_comm_state, resolve_codec
    if fed.error_feedback and resolve_codec(fed).lossy:
        # Per-client compression residuals (repro.comm): zero EF memory per
        # cohort slot, threaded through checkpoints exactly like ctrl.
        state["comm"] = init_comm_state(fed, make_flat_spec(params))
    if getattr(eng, "is_async", False):
        # Buffered-async delta pool + staleness counters: part of server
        # state, so checkpoints capture a mid-run pool bit-exactly.
        from repro.core.async_round import init_async_state
        state["async"] = init_async_state(fed, make_flat_spec(params))
    return state


# back-compat name (pre-registry callers import it from here)
grad_global_norm = tree_global_norm


def participation_mask(rng: jax.Array, cohort: int, rate: float) -> jax.Array:
    """Per-round straggler mask: keep each client with prob ``rate``.
    Derived from a fold of the round rng so enabling participation never
    perturbs the client/meta rng streams.  An all-zero draw (every client
    dropped) is legal: the round program guards the server step with
    ``stepped = sum(weights) > 0`` and leaves params/opt/ctrl bit-unchanged
    for that round — the old silent fall-back to full participation
    over-trained exactly when the fleet was at its flakiest."""
    keep = jax.random.bernoulli(jax.random.fold_in(rng, PARTICIPATION_FOLD),
                                p=rate, shape=(cohort,))
    return keep.astype(jnp.float32)


def build_client_update(model: Model, fed: FedConfig,
                        algorithm: Optional[str] = None):
    """The registered local update (``fed.algorithm``, or ``algorithm``)
    for ``model``, traced under the ``repro.client`` named scope: every
    executor runs it, so the ops of the clients' local steps, gradient
    evaluations and Hessian-vector products carry the scope in the
    compiled program and in a profiler trace."""
    alg = get_algorithm(algorithm if algorithm is not None
                        else fed.algorithm)
    local_update = alg.build(
        model.loss, local_steps=fed.local_steps,
        local_epochs=fed.local_epochs, prox_mu=fed.prox_mu,
        remat=fed.remat_local_steps)

    def client_update(*args):
        with jax.named_scope("repro.client"):
            return local_update(*args)

    return client_update


def make_federated_round(model: Model, fed: FedConfig, *,
                         spmd_axis_name=None, grad_shardings=None,
                         rounds_per_call: int = 1,
                         algorithm: Optional[str] = None,
                         executor: Optional[str] = None,
                         engine: Optional[str] = None,
                         sanitize: bool = False):
    """Compose (algorithm, executor, engine) into one round program.

    ``spmd_axis_name``: mesh axes the cohort dimension is sharded over
    (client-parallel strategy) — forwarded to ``jax.vmap`` so per-client
    intermediates shard instead of replicate.  ``grad_shardings``: explicit
    NamedShardings for the stacked per-client gradients (cohort, *param) —
    selects the sharded executor, which keeps the per-leaf weighted mean so
    GSPMD never all-gathers the stack.  ``rounds_per_call``: scan K rounds
    into one program.  ``algorithm`` / ``executor`` / ``engine``: registry
    names overriding the ``fed``-derived defaults (``fed.algorithm``,
    ``fed.cohort_strategy`` + shardings, ``fed.fused_update``).
    ``sanitize``: plant :func:`repro.core.sanitize.check_flat_groups`
    probes on the post-round flat parameter buffers (and, async, on the
    decoded per-client deltas); inert unless the round program is
    transformed by :func:`repro.core.sanitize.checkify_round` — which
    :class:`RoundFnCache` does when built with ``sanitize=True``."""
    eng_probe = resolve_engine(fed, engine=engine)
    if getattr(eng_probe, "is_async", False):
        # Asynchronous engines replace the whole round SHAPE, not just the
        # server apply: route to the buffered-async tick program, which
        # shares one_round's signature so chunking below reuses unchanged.
        if grad_shardings is not None:
            raise ValueError(
                "engine='buffered_async' keeps a replicated delta pool "
                "(per-client staleness slots), so per-leaf grad_shardings "
                "cannot apply; drop grad_shardings or use a synchronous "
                "engine")
        from repro.core.async_round import make_async_tick
        return _chunk_rounds(
            make_async_tick(model, fed, algorithm=algorithm,
                            executor=executor, engine=engine,
                            spmd_axis_name=spmd_axis_name,
                            sanitize=sanitize),
            rounds_per_call)

    faults = resolve_faults(fed)
    if faults.garble > 0:
        if getattr(fed, "fault_garble", -1.0) >= 0:
            raise ValueError(
                f"fault_garble={fed.fault_garble} needs "
                "engine='buffered_async': payload corruption acts on the "
                "pooled per-client deltas, which only the async runtime "
                "models — synchronous engines see faults at the "
                "aggregation-weight level (drop/crash/timeout). Use the "
                "buffered_async engine or drop fault_garble.")
        # profile-carried garble (e.g. fault_profile='flaky') downgrades
        # silently on sync engines: the profile describes the fleet, and
        # the sync barrier simply cannot observe payload corruption
        faults = dataclasses.replace(faults, garble=0.0)

    client_update = build_client_update(model, fed, algorithm)
    exe = resolve_executor(fed, spmd_axis_name=spmd_axis_name,
                           grad_shardings=grad_shardings, executor=executor)
    eng = eng_probe

    kinds = exe.produces & eng.accepts
    if not kinds:
        raise ValueError(
            f"cohort executor {exe.name!r} produces {sorted(exe.produces)} "
            f"but server engine {eng.name!r} accepts {sorted(eng.accepts)}: "
            "no common aggregate-handle kind")
    kind = eng.preferred if eng.preferred in kinds else next(iter(kinds))

    server_lr = resolve_server_lr(fed)
    through_agg = fed.meta and fed.meta_mode == "through_aggregation"
    if through_agg and "through_aggregation" not in eng.meta_capabilities:
        # FedConfig validates this too, but re-check against the resolved
        # engine for configs built around __post_init__ (python -O,
        # object.__setattr__) and for registry-selected engines: without
        # the capability there is no ctrl hypergradient path.
        raise ValueError(
            f"meta_mode='through_aggregation' needs a server engine "
            f"declaring the 'through_aggregation' capability, but "
            f"{eng.name!r} declares {sorted(eng.meta_capabilities)}: the "
            "hypergradients flow through the fused engine's custom VJP. "
            "Set FedConfig(fused_update=True) (the fused_flat engine) or "
            "use meta_mode='post'.")
    if through_agg and not exe.supports_reweight:
        raise ValueError(
            f"meta_mode='through_aggregation' needs a cohort executor that "
            f"supports reweightable aggregation, but {exe.name!r} does "
            "not. Every built-in synchronous executor (vmap/scan/chunked "
            "and the two-tier sharded topology) supports it; only "
            "custom executors without a reweightable form and the async "
            "delta pool lack the per-client weight hypergradients. Use "
            "one of those executors or meta_mode='post'.")

    # lazy: repro.comm imports repro.core.flat, which triggers this package
    from repro.comm import comm_bytes_per_client, resolve_codec
    codec = resolve_codec(fed)
    lossy_codec = codec.lossy
    if lossy_codec:
        # FedConfig validates the built-in combinations too, but re-check
        # against the RESOLVED plugins (registry-name overrides, custom
        # executors/engines) so a lossy codec never silently runs a path
        # that drops the compression or differentiates through it.
        if through_agg:
            raise ValueError(
                f"codec={fed.codec!r} with "
                "meta_mode='through_aggregation' would differentiate "
                "through a non-differentiable quantizer (the hypergradient "
                "would silently treat the decoded gradients as exact). "
                "Lossy codecs are meta_mode='post' only for now — a "
                "straight-through codec VJP is a ROADMAP follow-up. Use "
                "meta_mode='post' or codec='none'.")
        if "lossy" not in exe.codec_capabilities:
            raise ValueError(
                f"codec={fed.codec!r} needs a cohort executor declaring "
                f"the 'lossy' codec capability, but {exe.name!r} declares "
                f"{sorted(exe.codec_capabilities)}. Every built-in "
                "executor (vmap/scan/chunked/sharded and the async delta "
                "pool) streams a per-client uplink and declares 'lossy'; "
                "a custom executor that pre-aggregates before the uplink "
                "cannot compress per client. Use one of the built-in "
                "executors or codec='none'.")
        if "lossy" not in eng.codec_capabilities:
            raise ValueError(
                f"codec={fed.codec!r} needs a server engine declaring the "
                f"'lossy' codec capability, but {eng.name!r} declares "
                f"{sorted(eng.codec_capabilities)}: lossy codecs decode "
                "into the flat dtype-group buffers the fused engine "
                "consumes. Set FedConfig(fused_update=True) (the "
                "fused_flat engine) or use codec='none'.")
    use_ef = lossy_codec and fed.error_feedback

    def one_round(state: PyTree, cohort_batch: PyTree, meta_batch: PyTree,
                  client_weights: jax.Array, rng: jax.Array
                  ) -> Tuple[PyTree, Dict[str, jax.Array]]:
        params = state["params"]
        r = state["round"].astype(jnp.float32)
        lr_c = fed.client_lr * (fed.lr_decay ** r)

        # NOTE: the 2-way split below is load-bearing for reproducibility —
        # the participation mask folds out of ``rng`` separately so that
        # participation=1 configs keep the exact historical rng streams.
        rng_c, rng_m = jax.random.split(rng)
        part_metrics = {}
        if fed.participation < 1.0:
            mask = participation_mask(rng, client_weights.shape[0],
                                      fed.participation)
            client_weights = client_weights * mask
            part_metrics = {"participants": jnp.sum(mask)}

        if faults.active:
            # crash/drop (and, past the round deadline, straggling) zero a
            # client's aggregation weight — inside the existing weighted
            # mean, so every executor/engine handles faults unchanged, and
            # (with EF codecs) a failed client's residual slot freezes
            fs = fault_streams(rng, client_weights.shape[0], faults)
            failed = client_failed_mask(fs, faults)
            client_weights = client_weights * (~failed).astype(jnp.float32)
            part_metrics = {
                **part_metrics,
                "arrivals": jnp.sum((client_weights > 0).astype(
                    jnp.float32)),
                "fault_crashed": jnp.sum(fs.crashed.astype(jnp.float32)),
                "fault_dropped": jnp.sum(fs.dropped.astype(jnp.float32)),
            }
            if faults.deadline > 0:
                late = ((fs.latency + fs.delay.astype(jnp.float32))
                        > faults.deadline)
                part_metrics["fault_timeout"] = jnp.sum(
                    late.astype(jnp.float32))

        meta_metrics = {}
        comm_metrics = {}
        new_comm = None
        if through_agg:
            rw = exe.reweightable(client_update, params, cohort_batch,
                                  client_weights, lr_c, rng_c)
            (new_params, opt_state, gn_post, client_loss, new_ctrl,
             meta_metrics) = meta_update_through_cohort(
                model.loss, rw, client_weights, params, state["opt"],
                meta_batch, state["ctrl"], engine=eng,
                ctrl_lr=fed.ctrl_lr, rng=rng_m)
        elif lossy_codec:
            handle, client_loss, new_comm = exe.run_coded(
                client_update, params, cohort_batch, client_weights, lr_c,
                rng_c, codec=codec, comm=state.get("comm"))
            # measured uplink bytes: per-client payload size (static — the
            # codec's transport shapes) times the clients that reported
            bytes_pc = comm_bytes_per_client(codec, make_flat_spec(params))
            n_up = part_metrics.get(
                "participants", jnp.float32(client_weights.shape[0]))
            comm_metrics = {"comm_bytes": jnp.float32(bytes_pc) * n_up}
        else:
            handle, client_loss = exe.run(
                client_update, params, cohort_batch, client_weights, lr_c,
                rng_c, kind=kind)
        if not through_agg:
            with jax.named_scope("repro.server"):
                new_params, opt_state, gn_post = eng.apply(
                    params, handle, state["opt"], lr=server_lr)

        # one metrics assembly for every arm: rounds_per_call chunking
        # (lax.scan) needs identical keys per config, so the executor/
        # engine/mode combinations must not each grow their own dict
        metrics = {"client_loss": client_loss, "grad_norm": gn_post,
                   **part_metrics, **meta_metrics, **comm_metrics}

        if fed.meta and not through_agg:
            lr_m = fed.meta_lr * (fed.lr_decay ** r)
            new_params, meta_loss = meta_update(
                model.loss, new_params, meta_batch, lr_m, rng_m)
            metrics["meta_loss"] = meta_loss

        new_state = {"params": new_params, "opt": opt_state,
                     "round": state["round"] + 1}
        if through_agg:
            new_state["ctrl"] = new_ctrl
        if use_ef:
            new_state["comm"] = new_comm

        if fed.participation < 1.0 or faults.active:
            # Degradation policy: a round whose entire cohort failed (mask
            # or faults) must be a no-op server step — params/opt/ctrl/comm
            # stay bit-identical (where(True, x, _) is a bitwise identity,
            # so surviving rounds are untouched).  Only the round counter
            # advances.  Metric keys stay fixed for lax.scan chunking; the
            # degenerate round's loss/norm values are gated to 0.
            stepped = jnp.sum(client_weights) > 0.0
            new_state = {
                k: (v if k == "round"
                    else jax.tree.map(
                        lambda a, b: jnp.where(stepped, a, b), v, state[k]))
                for k, v in new_state.items()}
            for mk in ("client_loss", "grad_norm", "meta_loss"):
                if mk in metrics:
                    metrics[mk] = jnp.where(stepped, metrics[mk], 0.0)
        if sanitize:
            spec = make_flat_spec(params)
            check_flat_groups(
                spec, flatten_tree(spec, new_state["params"]),
                "post-round server params (sync round)")
        return new_state, metrics

    return _chunk_rounds(one_round, rounds_per_call)


def _chunk_rounds(one_round, rounds_per_call: int):
    """Shared ``rounds_per_call`` wrapper (sync rounds AND async ticks):
    scan K rounds into one donated program over K-stacked inputs."""
    if rounds_per_call == 1:
        return one_round

    assert rounds_per_call > 1, rounds_per_call

    def round_fn(state: PyTree, cohort_batches: PyTree, meta_batches: PyTree,
                 client_weights: jax.Array, rngs: jax.Array
                 ) -> Tuple[PyTree, Dict[str, jax.Array]]:
        def body(st, xs):
            cb, mb, w, r = xs
            return one_round(st, cb, mb, w, r)

        return lax.scan(body, state,
                        (cohort_batches, meta_batches, client_weights, rngs))

    return round_fn


class RoundFnCache:
    """Jitted round programs keyed by chunk size, for drivers that mix
    full ``rounds_per_call`` chunks with a tail remainder — every driver
    shares this cache instead of re-implementing the per-k jit dict.

    ``sanitize=True`` jits each program under
    :func:`repro.core.sanitize.checkify_round` and raises the checkified
    error host-side after every call, so a NaN
    fires the round it appears with the planted probes' message instead of
    poisoning later rounds silently."""

    def __init__(self, model: Model, fed: FedConfig, *, donate: bool = True,
                 sanitize: bool = False, **round_kwargs):
        self._make = lambda k: make_federated_round(
            model, fed, rounds_per_call=k, sanitize=sanitize,
            **round_kwargs)
        self._donate = donate
        self._sanitize = sanitize
        self._fns: Dict[int, Any] = {}

    def __call__(self, k: int):
        if k not in self._fns:
            donate = (0,) if self._donate else ()
            if self._sanitize:
                # checkify_round keeps the positional signature (the error
                # value is an extra OUTPUT), so state stays argnum 0
                jitted = jax.jit(checkify_round(self._make(k)),
                                 donate_argnums=donate)

                def checked(*args, _fn=jitted):
                    err, out = _fn(*args)
                    throw_if_error(err)
                    return out

                self._fns[k] = checked
            else:
                self._fns[k] = jax.jit(self._make(k), donate_argnums=donate)
        return self._fns[k]


def stack_round_inputs(cohort_batches, meta_batches, client_weights, rngs):
    """K per-round host samples -> the K-stacked device inputs of a
    ``rounds_per_call=K`` round_fn (leaves gain a leading K axis)."""
    stack = lambda *xs: jnp.stack([jnp.asarray(x) for x in xs])
    return (jax.tree.map(stack, *cohort_batches),
            jax.tree.map(stack, *meta_batches),
            stack(*client_weights),
            stack(*rngs))
