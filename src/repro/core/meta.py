"""FedMeta — controllable meta updating (§3.2, Algorithm 2).

Two meta modes:

  * :func:`meta_update` (``meta_mode='post'``, the paper's Eq. 20): after
    aggregation the server takes one gradient step on the curated meta
    training set D_meta, giving every round the same, *controllable*
    optimization objective regardless of which clients were sampled.

  * :func:`meta_update_through_cohort`
    (``meta_mode='through_aggregation'``): instead of stepping the
    parameters directly, differentiate the D_meta loss *through* the
    Eq. (14) aggregation and the server step, producing hypergradients
    w.r.t. the per-client aggregation weight multipliers and the server
    step size.  Those live in the server state's controllable slot
    ``ctrl = {"w_logits": (cohort,), "log_lr": ()}`` (log-space so
    effective weights/lr stay positive) and are updated by one SGD step
    with ``ctrl_lr`` per round — the meta-learned-aggregation scheme of
    FedAgg / MAML-style FL personalization grafted onto the paper's
    controllable meta update.  It differentiates through a
    :class:`repro.core.executors.ReweightableCohort` (the streaming
    cohort core re-run under new weights; the accumulate kernel's custom
    VJP gives the per-client weight cotangents) and any
    :class:`repro.core.engines.ServerEngine` declaring the
    ``through_aggregation`` capability (the fused engine's hand-written
    update VJP, ``kernels/fused_update``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

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
    """Controllable aggregation: the through-aggregation meta step.

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
