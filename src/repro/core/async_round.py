"""Buffered asynchronous federation runtime (FedBuff-style) as ONE jitted
tick program.

The synchronous round (``repro.core.round``) is a barrier: every cohort
delta must arrive before the server steps, so one straggler stalls the
whole round.  The ``buffered_async`` engine replaces the barrier with a
**bounded pool of coded client deltas** carrying per-delta staleness
counters, and the server steps every ``K = FedConfig.async_buffer``
arrivals with staleness-discounted weights — the buffered-async scheme of
Nguyen et al. (FedBuff, 2022) with the robust staleness weighting of
arXiv:2205.10864, layered over this repo's fused flat-buffer kernels.

One **tick** (what ``round_fn`` runs per ``state["round"]`` increment) is
the simulated dispatch period: the server hands the current parameters to
a fresh cohort, their deltas finish locally, and each delta enters the
pool stamped with the server version it was computed against plus a
delivery tick (``tick + delay`` under a delay fault).  Then the server
flushes every K **arrived** deltas (delivered, not yet consumed):

  * flush weights are ``n_k * discount(staleness)`` with ``staleness =
    server_version - delta_version`` and ``discount`` from
    ``FedConfig.staleness_mode`` (``invsqrt``: ``1/sqrt(1+s)``, the FedBuff
    default; ``inv``; ``none``);
  * the weighted mean streams through the SAME fused flat-buffer FMA
    (``kernels/fused_update::accumulate_pass``) and fused
    clip->optimizer->write pass as the synchronous scan strategy — a
    fault-free tick with ``K = async_capacity = cohort`` is **bit-identical**
    to the synchronous ``cohort_strategy="scan"`` fused round
    (regression-gated by ``benchmarks/async_throughput.py``);
  * the server version increments per flush, staling every delta still in
    the pool; ``async_max_staleness`` optionally evicts arrived deltas
    whose staleness exceeds the bound.

Faults (``repro.sim.faults``) act where a real system would see them:
crash/drop zero a delta's pool weight (it never arrives), delay pushes its
delivery tick, garble scales the decoded payload.  Lossy uplink codecs
(``repro.comm``) ride the same per-client slots: the pool stores DECODED
deltas and error-feedback residuals live in ``state["comm"]`` exactly as
in the sync rounds (a crashed/dropped client's residual stays
byte-identical — it never transmitted).

Pool state (``state["async"]``) checkpoints/restores like every other
server-state slot, so a mid-run save/resume is bit-identical, buffer and
staleness counters included.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.engines import resolve_engine
from repro.core.executors import FlatAggregate, get_executor
from repro.core.flat import LANES, FlatSpec, make_flat_spec, zeros_flat
from repro.core.meta import meta_update
from repro.core.round import (build_client_update, participation_mask,
                              resolve_server_lr)
from repro.core.sanitize import check_flat_groups
from repro.kernels.fused_update.ops import flat_accumulate
from repro.models.model import Model
from repro.sim.faults import fault_streams, resolve_faults

PyTree = Any

STALENESS_HIST_BINS = 8     # staleness histogram: counts of s in 0..6, 7+

_INT32_MAX = jnp.iinfo(jnp.int32).max


def resolve_async_shape(fed) -> Tuple[int, int]:
    """(K, capacity): server steps every K arrivals; the pool holds
    ``capacity`` delta slots.  Defaults: K = cohort (one step per fault-free
    tick), capacity = 2 * cohort (headroom for delayed arrivals).  K >
    capacity could never flush (the deadlock FedConfig rejects)."""
    k = int(getattr(fed, "async_buffer", 0)) or fed.cohort
    cap = int(getattr(fed, "async_capacity", 0)) or 2 * fed.cohort
    return k, cap


def staleness_discount(mode: str):
    """Staleness -> weight multiplier.  ``discount(0) == 1.0`` exactly in
    every mode, so a fresh delta's weight is bit-unchanged."""
    if mode == "none":
        return lambda s: jnp.ones_like(s)
    if mode == "inv":
        return lambda s: 1.0 / (1.0 + s)
    if mode == "invsqrt":
        return lambda s: 1.0 / jnp.sqrt(1.0 + s)
    raise ValueError(
        f"unknown staleness_mode {mode!r}; expected 'none', 'inv' or "
        "'invsqrt' (the FedBuff 1/sqrt(1+s) default)")


def init_async_state(fed, spec: FlatSpec) -> PyTree:
    """The delta pool: per-dtype-group ``(capacity, rows, LANES)`` fp32
    slots plus per-slot weight / version / delivery-tick vectors and the
    server version counter.  ``weight == 0`` marks a free slot."""
    _, cap = resolve_async_shape(fed)
    return {
        "pool": tuple(jnp.zeros((cap, g.rows, LANES), jnp.float32)
                      for g in spec.groups),
        "weight": jnp.zeros((cap,), jnp.float32),
        "version": jnp.zeros((cap,), jnp.int32),
        "deliver": jnp.zeros((cap,), jnp.int32),
        "server_version": jnp.zeros((), jnp.int32),
    }


def make_async_tick(model: Model, fed, *, algorithm: Optional[str] = None,
                    executor: Optional[str] = None,
                    engine: Optional[str] = None, spmd_axis_name=None,
                    sanitize: bool = False):
    """Build ``one_tick(state, cohort_batch, meta_batch, client_weights,
    rng) -> (state, metrics)`` — same signature as the synchronous
    ``one_round``, so ``rounds_per_call`` chunking, the trainer and the
    checkpoint layout all reuse unchanged."""
    client_update = build_client_update(model, fed, algorithm)
    if executor not in (None, "buffered_async"):
        raise ValueError(
            f"engine='buffered_async' runs its own delta-pooling executor; "
            f"executor={executor!r} cannot be composed with it. Drop the "
            "executor override (fed.cohort_strategy picks the vmap/scan "
            "base the deltas are computed with).")
    exe = get_executor("buffered_async")(fed, spmd_axis_name=spmd_axis_name,
                                         grad_shardings=None)
    eng = resolve_engine(fed, engine=engine)
    faults = resolve_faults(fed)
    # lazy: repro.comm imports repro.core.flat, which triggers this package
    from repro.comm import comm_bytes_per_client, resolve_codec
    codec = resolve_codec(fed)
    use_ef = codec.lossy and fed.error_feedback
    K, cap = resolve_async_shape(fed)
    if K > cap:
        raise ValueError(
            f"async_buffer={K} exceeds async_capacity={cap}: the pool can "
            "never hold K deltas, so the server would never step "
            "(deadlock). Raise async_capacity or lower async_buffer.")
    max_steps = max(cap // K, 1)
    server_lr = resolve_server_lr(fed)
    discount = staleness_discount(fed.staleness_mode)
    max_stale = int(getattr(fed, "async_max_staleness", 0))
    accum = flat_accumulate()

    def one_tick(state: PyTree, cohort_batch: PyTree, meta_batch: PyTree,
                 client_weights: jax.Array, rng: jax.Array
                 ) -> Tuple[PyTree, Dict[str, jax.Array]]:
        params = state["params"]
        a = state["async"]
        tick = state["round"]
        r = tick.astype(jnp.float32)
        lr_c = fed.client_lr * (fed.lr_decay ** r)
        cohort = client_weights.shape[0]
        spec = make_flat_spec(params)

        # same 2-way split + participation fold as the sync round, so a
        # fault-free K=cohort tick replays the sync rng streams exactly
        rng_c, rng_m = jax.random.split(rng)
        w_in = client_weights
        part_metrics = {}
        if fed.participation < 1.0:
            mask = participation_mask(rng, cohort, fed.participation)
            w_in = w_in * mask
            part_metrics = {"participants": jnp.sum(mask)}

        fault_metrics = {}
        if faults.active:
            fs = fault_streams(rng, cohort, faults)
            # crashed/dropped reports never reach the pool; their zero
            # weight also keeps them out of the loss metric and (with EF
            # codecs) freezes their residual slot — they never transmitted
            w_in = w_in * fs.alive
            delay = fs.delay
            fault_metrics = {
                "fault_crashed": jnp.sum(fs.crashed.astype(jnp.float32)),
                "fault_dropped": jnp.sum(fs.dropped.astype(jnp.float32)),
                "fault_delayed": jnp.sum(fs.delayed.astype(jnp.float32)),
            }
        else:
            fs = None
            delay = jnp.zeros((cohort,), jnp.int32)

        # ---- local updates -> per-client DECODED flat deltas ------------
        comm_metrics = {}
        new_comm = None
        if codec.lossy:
            g_groups, client_loss, new_res = exe.run_deltas_coded(
                client_update, params, cohort_batch, w_in, lr_c, rng_c,
                spec=spec, codec=codec, comm=state.get("comm"))
            if use_ef:
                new_comm = {"residual": new_res}
            bytes_pc = comm_bytes_per_client(codec, spec)
            n_up = jnp.sum((w_in > 0).astype(jnp.float32))
            comm_metrics = {"comm_bytes": jnp.float32(bytes_pc) * n_up}
        else:
            g_groups, client_loss = exe.run_deltas(
                client_update, params, cohort_batch, w_in, lr_c, rng_c,
                spec=spec)
        if faults.active and faults.garble > 0:
            # payload corruption happens on the wire: AFTER codec
            # decode, BEFORE pooling (ungarbled multipliers are exactly
            # 1.0, an IEEE no-op)
            g_groups = [g * fs.garble_mult[:, None, None] for g in g_groups]
        if sanitize:
            # probe the decoded (and possibly garbled) payloads BEFORE they
            # enter the pool: a NaN caught here names the uplink, not a
            # server step several flushes later
            check_flat_groups(spec, g_groups,
                              "decoded client deltas before pool insert "
                              "(async tick)")

        # ---- pool insert (evict-stalest on overflow) --------------------
        v_now = a["server_version"]
        cand_w = jnp.concatenate([a["weight"], w_in.astype(jnp.float32)])
        cand_v = jnp.concatenate(
            [a["version"], jnp.full((cohort,), v_now, jnp.int32)])
        cand_d = jnp.concatenate([a["deliver"], tick + delay])
        occupied = cand_w > 0.0
        # ascending sort key: newest version first, free slots last; the
        # stable sort keeps insertion order within a version, so a
        # fault-free tick lands the cohort in client order (bit-identity
        # with the sync scan accumulation depends on this)
        sort_key = jnp.where(occupied, -cand_v, _INT32_MAX)
        keep = jnp.argsort(sort_key, stable=True)[:cap]
        pool = tuple(jnp.concatenate([p, g], axis=0)[keep]
                     for p, g in zip(a["pool"], g_groups))
        pw = cand_w[keep]
        pv = cand_v[keep]
        pd = cand_d[keep]
        overflow = (jnp.sum(occupied.astype(jnp.float32))
                    - jnp.sum((pw > 0).astype(jnp.float32)))
        arrivals = jnp.sum(((pw > 0) & (pd == tick)).astype(jnp.float32))

        # ---- flush every K arrived deltas -------------------------------
        slot_idx = jnp.arange(cap, dtype=jnp.int32)

        def flush(args):
            params_f, opt_f, pw_f, ver_f, st = args
            eligible = (pw_f > 0.0) & (pd <= tick)
            if max_stale > 0:
                eligible = eligible & ((ver_f - pv) <= max_stale)
            # select the K earliest-delivered eligible deltas, slot index
            # breaking ties (deterministic FIFO)
            sel_key = jnp.where(eligible, pd * (cap + 1) + slot_idx,
                                _INT32_MAX)
            rank = jnp.argsort(jnp.argsort(sel_key))
            sel = (rank < K) & eligible
            s = (ver_f - pv).astype(jnp.float32)
            w_eff = pw_f * discount(s) * sel.astype(jnp.float32)
            wsum = jnp.maximum(jnp.sum(w_eff), 1e-30)
            wn = w_eff / wsum

            # streaming FMA over the pool slots — the same accumulate_pass
            # sequence as scan_cohort_gradient_flat, so a fault-free
            # K=cap=cohort flush reproduces the sync scan bits exactly
            def acc_body(accs, xs):
                gs, wi = xs
                return tuple(accum(acc, g, wi)
                             for acc, g in zip(accs, gs)), None

            accs, _ = lax.scan(acc_body, tuple(zeros_flat(spec)), (pool, wn))
            handle = FlatAggregate(list(accs), spec, sq_norm=None)
            with jax.named_scope("repro.server"):
                new_p, new_o, gn = eng.apply(params_f, handle, opt_f,
                                             lr=server_lr)

            s_sel = jnp.where(sel, s, 0.0)
            bins = jnp.clip(s.astype(jnp.int32), 0, STALENESS_HIST_BINS - 1)
            hist_add = jnp.sum(
                jax.nn.one_hot(bins, STALENESS_HIST_BINS, dtype=jnp.float32)
                * sel.astype(jnp.float32)[:, None], axis=0)
            st = {
                "steps": st["steps"] + 1,
                "grad_norm": gn,
                "staleness_sum": st["staleness_sum"] + jnp.sum(s_sel),
                "staleness_cnt": (st["staleness_cnt"]
                                  + jnp.sum(sel.astype(jnp.float32))),
                "staleness_max": jnp.maximum(st["staleness_max"],
                                             jnp.max(s_sel)),
                "staleness_hist": st["staleness_hist"] + hist_add,
            }
            return new_p, new_o, jnp.where(sel, 0.0, pw_f), ver_f + 1, st

        def attempt(_, carry):
            _, _, pw_c, ver_c, _ = carry
            eligible = (pw_c > 0.0) & (pd <= tick)
            if max_stale > 0:
                eligible = eligible & ((ver_c - pv) <= max_stale)
            cnt = jnp.sum(eligible.astype(jnp.int32))
            return lax.cond(cnt >= K, flush, lambda c: c, carry)

        st0 = {"steps": jnp.zeros((), jnp.int32),
               "grad_norm": jnp.zeros((), jnp.float32),
               "staleness_sum": jnp.zeros((), jnp.float32),
               "staleness_cnt": jnp.zeros((), jnp.float32),
               "staleness_max": jnp.zeros((), jnp.float32),
               "staleness_hist": jnp.zeros((STALENESS_HIST_BINS,),
                                           jnp.float32)}
        new_params, new_opt, pw_fin, v_fin, st = lax.fori_loop(
            0, max_steps, attempt, (params, state["opt"], pw, v_now, st0))

        if max_stale > 0:
            # arrived deltas the staleness bound evicted this tick: still
            # occupying weight but permanently ineligible — clear them so
            # the pool doesn't silt up, and count them
            stale_now = ((pw_fin > 0.0) & (pd <= tick)
                         & ((v_fin - pv) > max_stale))
            fault_metrics = {**fault_metrics,
                             "expired": jnp.sum(stale_now.astype(
                                 jnp.float32))}
            pw_fin = jnp.where(stale_now, 0.0, pw_fin)

        metrics = {
            "client_loss": client_loss,
            "grad_norm": st["grad_norm"],
            "arrivals": arrivals,
            "server_steps": st["steps"].astype(jnp.float32),
            "buffer_fill": jnp.sum((pw_fin > 0).astype(jnp.float32)),
            "overflow_dropped": overflow,
            "staleness_mean": (st["staleness_sum"]
                               / jnp.maximum(st["staleness_cnt"], 1.0)),
            "staleness_max": st["staleness_max"],
            "staleness_hist": st["staleness_hist"],
            **part_metrics, **fault_metrics, **comm_metrics,
        }

        if fed.meta:
            # post-aggregation FedMeta step, once per tick, gated on the
            # server having stepped at all (a no-flush tick must leave
            # params bit-unchanged); where(True, x, _) is a bitwise
            # identity, so fault-free ticks keep the sync meta bits
            lr_m = fed.meta_lr * (fed.lr_decay ** r)
            m_params, meta_loss = meta_update(
                model.loss, new_params, meta_batch, lr_m, rng_m)
            stepped = st["steps"] > 0
            new_params = jax.tree.map(
                lambda m, n: jnp.where(stepped, m, n), m_params, new_params)
            metrics["meta_loss"] = jnp.where(stepped, meta_loss, 0.0)

        new_state = {
            "params": new_params, "opt": new_opt, "round": tick + 1,
            "async": {"pool": pool, "weight": pw_fin, "version": pv,
                      "deliver": pd, "server_version": v_fin},
        }
        if use_ef:
            new_state["comm"] = new_comm
        return new_state, metrics

    return one_tick
