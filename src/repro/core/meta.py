"""FedMeta — controllable meta updating (§3.2, Algorithm 2).

Two meta modes:

  * ``meta_update`` (``meta_mode='post'``, the paper's Eq. 20): after
    aggregation the server takes one gradient step on the curated meta
    training set D_meta, giving every round the same, *controllable*
    optimization objective regardless of which clients were sampled.

  * ``meta_update_through_aggregation`` (``meta_mode='through_aggregation'``):
    instead of stepping the parameters directly, differentiate the D_meta
    loss *through* the Eq. (14) aggregation and the server optimizer — the
    fused engine's hand-written custom VJP (``kernels/fused_update``) makes
    this two extra flat HBM sweeps — producing hypergradients w.r.t. the
    per-client aggregation weight multipliers and the server step size.
    Those live in the server state's controllable slot
    ``ctrl = {"w_logits": (cohort,), "log_lr": ()}`` (log-space so
    effective weights/lr stay positive) and are updated by one SGD step
    with ``ctrl_lr`` per round — the meta-learned-aggregation scheme of
    FedAgg / MAML-style FL personalization grafted onto the paper's
    controllable meta update.  ``meta_update_through_aggregation_scan`` is
    the same scheme under client-sequential (scan) cohorts, where the
    streaming flat accumulation's custom VJP supplies the per-client
    weight cotangents without ever stacking the cohort gradients.

Since the plugin-API redesign the round builder uses the strategy-agnostic
``meta_update_through_cohort``: it differentiates through a
:class:`repro.core.executors.ReweightableCohort` (vmap reweights its
retained gradient stack; scan re-runs the streaming accumulation) and any
:class:`repro.core.engines.ServerEngine` declaring the
``through_aggregation`` capability.  The two strategy-specific functions
below are kept as the tested reference forms and for back-compat.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.flat import make_flat_spec
from repro.kernels.fused_update.ops import (fused_apply_flat,
                                            fused_server_update)

PyTree = Any


def meta_update(loss_fn: Callable, params: PyTree, meta_batch: PyTree,
                meta_lr, rng=None) -> Tuple[PyTree, jax.Array]:
    """w <- w - eta_meta * grad L(w; D_meta).  Returns (params, meta_loss).
    Traced under the ``repro.meta`` named scope."""

    def obj(w):
        l, _ = loss_fn(w, meta_batch, rng)
        return l

    with jax.named_scope("repro.meta"):
        meta_loss, g = jax.value_and_grad(obj)(params)
        new = jax.tree.map(
            lambda p, gi: (p.astype(jnp.float32)
                           - meta_lr * gi.astype(jnp.float32)
                           ).astype(p.dtype),
            params, g)
    return new, meta_loss


def meta_update_through_cohort(
        loss_fn: Callable, reweightable, client_weights: jax.Array,
        params: PyTree, opt_state: PyTree, meta_batch: PyTree,
        ctrl: Dict[str, jax.Array], *, engine, ctrl_lr, rng=None
        ) -> Tuple[PyTree, PyTree, jax.Array, jax.Array,
                   Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Executor/engine-agnostic controllable aggregation — the plugin-API
    form of the two strategy-specific functions below (which it supersedes;
    they are kept for back-compat).

    ``reweightable`` is a :class:`repro.core.executors.ReweightableCohort`
    whose ``aggregate(weights)`` re-runs Eq. (14) under new weights
    (differentiably); ``engine`` is a :class:`repro.core.engines.ServerEngine`
    declaring the ``through_aggregation`` capability.  The objective takes
    this round's server step under eff_w = n_k * exp(w_logits) and step
    size exp(log_lr), and one SGD step with ``ctrl_lr`` on the D_meta-loss
    hypergradients updates the controllable state.

    Returns (new_params, new_opt_state, grad_norm_after_clip, client_loss,
    new_ctrl, metrics).  Traced under the ``repro.meta`` named scope; the
    clients' updates it runs nest their ``repro.client`` scope inside, and
    the server step its ``repro.server`` scope."""

    def objective(w_logits, log_lr):
        eff_w = client_weights.astype(jnp.float32) * jnp.exp(w_logits)
        handle, client_loss = reweightable.aggregate(eff_w)
        with jax.named_scope("repro.server"):
            new_p, new_opt, gn = engine.apply(params, handle, opt_state,
                                              lr=jnp.exp(log_lr))
        l, _ = loss_fn(new_p, meta_batch, rng)
        return l, (new_p, new_opt, gn, client_loss)

    with jax.named_scope("repro.meta"):
        (meta_loss, (new_p, new_opt, gn, client_loss)), (d_wl, d_llr) = \
            jax.value_and_grad(objective, argnums=(0, 1), has_aux=True)(
                ctrl["w_logits"], ctrl["log_lr"])
        new_ctrl = {"w_logits": ctrl["w_logits"] - ctrl_lr * d_wl,
                    "log_lr": ctrl["log_lr"] - ctrl_lr * d_llr}
    metrics = {"meta_loss": meta_loss,
               "ctrl_w_gnorm": jnp.sqrt(jnp.sum(d_wl * d_wl)),
               "ctrl_lr_grad": d_llr,
               "server_lr_eff": jnp.exp(ctrl["log_lr"])}
    return new_p, new_opt, gn, client_loss, new_ctrl, metrics


def meta_update_through_aggregation(
        loss_fn: Callable, params: PyTree, grad_stack: PyTree,
        client_weights: jax.Array, opt_state: PyTree, meta_batch: PyTree,
        ctrl: Dict[str, jax.Array], *, opt: str, clip_norm: float,
        momentum: float, ctrl_lr, rng=None
        ) -> Tuple[PyTree, PyTree, jax.Array, Dict[str, jax.Array],
                   Dict[str, jax.Array]]:
    """Take this round's fused server step under the controllable weights
    eff_w = n_k * exp(w_logits) and step size exp(log_lr), and update the
    controllable state by the hypergradient of the D_meta loss through
    that step (the fused engine's custom VJP).

    grad_stack: stacked per-client gradients (cohort leading axis);
    client_weights: (cohort,) n_k; ctrl: {"w_logits": (cohort,),
    "log_lr": ()}.  Returns (new_params, new_opt_state,
    grad_norm_after_clip, new_ctrl, metrics) — metrics carry the meta loss
    plus the hypergradient norms so drivers can gate on finiteness."""

    def objective(w_logits, log_lr):
        eff_w = client_weights.astype(jnp.float32) * jnp.exp(w_logits)
        new_p, new_opt, gn = fused_server_update(
            params, grad_stack, eff_w, opt_state, opt=opt,
            lr=jnp.exp(log_lr), clip_norm=clip_norm, momentum=momentum)
        l, _ = loss_fn(new_p, meta_batch, rng)
        return l, (new_p, new_opt, gn)

    (meta_loss, (new_p, new_opt, gn)), (d_wl, d_llr) = jax.value_and_grad(
        objective, argnums=(0, 1), has_aux=True)(
            ctrl["w_logits"], ctrl["log_lr"])
    new_ctrl = {"w_logits": ctrl["w_logits"] - ctrl_lr * d_wl,
                "log_lr": ctrl["log_lr"] - ctrl_lr * d_llr}
    metrics = {"meta_loss": meta_loss,
               "ctrl_w_gnorm": jnp.sqrt(jnp.sum(d_wl * d_wl)),
               "ctrl_lr_grad": d_llr,
               "server_lr_eff": jnp.exp(ctrl["log_lr"])}
    return new_p, new_opt, gn, new_ctrl, metrics


def meta_update_through_aggregation_scan(
        loss_fn: Callable, client_update: Callable, params: PyTree,
        cohort_batch: PyTree, client_weights: jax.Array, client_lr, rng_c,
        opt_state: PyTree, meta_batch: PyTree, ctrl: Dict[str, jax.Array],
        *, opt: str, clip_norm: float, momentum: float, ctrl_lr, rng=None
        ) -> Tuple[PyTree, PyTree, jax.Array, jax.Array,
                   Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Controllable aggregation under the client-sequential (scan) cohort
    strategy.  Per-client gradients are never stacked: the objective runs
    the cohort scan with the streaming flat accumulation
    (:func:`repro.core.aggregate.scan_cohort_gradient_flat`), whose
    accumulate custom VJP emits the per-client weight hypergradients
    dw_k = <g_k, dG> with g_k recomputed under ``jax.checkpoint`` — so the
    backward holds one client trajectory's residuals at a time and the
    hypergradients match the vmap path's to fp32 reduction order.

    Note the cost asymmetry vs the vmap path: vmap stores the (cohort,
    *model) gradient stack and never reruns clients; scan stores nothing
    and reruns each client's local update once inside the backward sweep.

    Returns (new_params, new_opt_state, grad_norm_after_clip, client_loss,
    new_ctrl, metrics); ``client_loss`` is weighted by the raw n_k (the
    aggregation uses the controllable eff_w), so the metric matches what
    the vmap branch reports in every round."""
    from repro.core.aggregate import scan_cohort_gradient_flat
    spec = make_flat_spec(params)

    def objective(w_logits, log_lr):
        eff_w = client_weights.astype(jnp.float32) * jnp.exp(w_logits)
        G_groups, client_loss = scan_cohort_gradient_flat(
            client_update, params, cohort_batch, eff_w, client_lr, rng_c,
            spec=spec, loss_weights=client_weights)
        new_p, new_opt, gn = fused_apply_flat(
            params, G_groups, opt_state, opt=opt, lr=jnp.exp(log_lr),
            clip_norm=clip_norm, momentum=momentum, spec=spec)
        l, _ = loss_fn(new_p, meta_batch, rng)
        return l, (new_p, new_opt, gn, client_loss)

    (meta_loss, (new_p, new_opt, gn, client_loss)), (d_wl, d_llr) = \
        jax.value_and_grad(objective, argnums=(0, 1), has_aux=True)(
            ctrl["w_logits"], ctrl["log_lr"])
    new_ctrl = {"w_logits": ctrl["w_logits"] - ctrl_lr * d_wl,
                "log_lr": ctrl["log_lr"] - ctrl_lr * d_llr}
    metrics = {"meta_loss": meta_loss,
               "ctrl_w_gnorm": jnp.sqrt(jnp.sum(d_wl * d_wl)),
               "ctrl_lr_grad": d_llr,
               "server_lr_eff": jnp.exp(ctrl["log_lr"])}
    return new_p, new_opt, gn, client_loss, new_ctrl, metrics
