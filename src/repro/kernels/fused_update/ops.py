"""Public engine for the fused server-side update.

:func:`fused_server_update` replaces the legacy 5+ tree-traversal server
step (``weighted_mean`` -> clip-norm scale -> fp32 cast -> optimizer ``upd``
-> param write) with exactly two HBM sweeps over flat per-dtype-group fp32
buffers (layout: ``repro.core.flat``):

  pass 1  kernels.aggregate_pass   cohort-weighted mean + ||G||^2
  pass 2  kernels.update_pass      clip scale + sgd/sgdm/adam/yogi + write

The client-sequential (scan) strategy streams pass 1 instead: the cohort
scan carries the flat group buffers and FMAs each client's flattened
gradient into them with :func:`flat_accumulate`
(``kernels.accumulate_pass``), then :func:`fused_apply_flat` runs pass 2
on the result — same engine, no stacked (cohort, rows, LANES) tensor ever
materializes.

Numerics match ``repro.core.server_opt.apply`` on the clipped fp32 mean to
<= 1e-5 relative (tested against both the pure-jnp ``ref`` oracle and the
legacy tree-map path).  ``use_ref=True`` swaps the Pallas kernels for the
oracle; ``interpret`` defaults to True off-TPU so the same code path runs
in the CPU tier-1 suite.

The engine is **differentiable**: each kernel pair is wrapped in a
``jax.custom_vjp`` (:func:`_agg_vjp` / :func:`_upd_vjp`) whose backward is
the hand-written ``aggregate_pass_bwd`` / ``update_pass_bwd`` Pallas
kernel (or the matching ``ref`` oracle under ``use_ref=True``), so
``jax.grad`` through :func:`fused_server_update` — w.r.t. the stacked
per-client gradients, the client weights, the learning rate and the
parameters — costs two more flat HBM sweeps instead of XLA
re-differentiating the engine.  Only the tiny scalar glue (weight
normalization, ||G||, clip scale, bias corrections) is left to XLA.  This
is what powers ``meta_mode="through_aggregation"`` (``core/meta.py``):
hypergradients of the meta loss w.r.t. per-client aggregation weights and
the server step size.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import flat as flat_mod
from repro.core.flat import FlatSpec, make_flat_spec
from repro.kernels.fused_update import kernel as K
from repro.kernels.fused_update import ref as R

PyTree = Any

# tree traversals per server step, for the BENCH report (legacy counts one
# full-model jax.tree.map per stage: weighted_mean, clip scale, g32 cast,
# m, v, step, param write — opt-dependent; fused is always two HBM sweeps)
TRAVERSALS_LEGACY = {"sgd": 4, "sgdm": 5, "adam": 8, "yogi": 8}
TRAVERSALS_FUSED = 2


def init_flat_opt_state(opt: str, spec: FlatSpec) -> PyTree:
    """Optimizer state in the flat layout (one fp32 buffer per dtype group,
    mirroring ``server_opt.init_state``'s per-leaf zeros)."""
    zeros = lambda: tuple(flat_mod.zeros_flat(spec))
    if opt == "sgd":
        return {}
    if opt == "sgdm":
        return {"m": zeros()}
    if opt in ("adam", "yogi"):
        return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32)}
    raise ValueError(opt)


@functools.lru_cache(maxsize=None)
def _agg_vjp(use_ref: bool, interpret: bool):
    """custom_vjp over the aggregate pass: (g_stack, w_norm) -> (G, ssq)."""

    @jax.custom_vjp
    def agg(g_stack, w_norm):
        if use_ref:
            return R.aggregate_ref(g_stack, w_norm)
        return K.aggregate_pass(g_stack, w_norm, interpret=interpret)

    def fwd(g_stack, w_norm):
        G, ssq = agg(g_stack, w_norm)
        return (G, ssq), (g_stack, w_norm, G)

    def bwd(res, cts):
        g_stack, w_norm, G = res
        dG, dssq = cts
        if use_ref:
            return R.aggregate_bwd_ref(g_stack, w_norm, G, dG, dssq)
        return K.aggregate_pass_bwd(g_stack, w_norm, G, dG, dssq,
                                    interpret=interpret)

    agg.defvjp(fwd, bwd)
    return agg


@functools.lru_cache(maxsize=None)
def _acc_vjp(use_ref: bool, interpret: bool):
    """custom_vjp over the streaming accumulate: (acc, g, w) -> acc + w*g.

    The scan strategy carries the flat group buffers and calls this once
    per client per group.  The backward is (d_acc, w*d_out, <g, d_out>) —
    the accumulator cotangent is the identity, so the cotangent arriving at
    step k is the cotangent of the FINAL aggregate, making dw_k = <g_k, dG>
    the through-aggregation weight hypergradient."""

    @jax.custom_vjp
    def accum(acc, g, w):
        if use_ref:
            return R.accumulate_ref(acc, g, w)
        return K.accumulate_pass(acc, g, w, interpret=interpret)

    def fwd(acc, g, w):
        return accum(acc, g, w), (g, w)

    def bwd(res, d_out):
        g, w = res
        if use_ref:
            dg, dw = R.accumulate_bwd_ref(g, w, d_out)
        else:
            dg, dw = K.accumulate_pass_bwd(g, w, d_out, interpret=interpret)
        return d_out, dg, dw

    accum.defvjp(fwd, bwd)
    return accum


def flat_accumulate(use_ref: bool = False, interpret: Optional[bool] = None):
    """Public getter for the cached streaming-accumulate custom VJP
    (``(acc, g, w) -> acc + w*g`` over one (rows, LANES) fp32 group)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _acc_vjp(use_ref, interpret)


@functools.lru_cache(maxsize=None)
def _upd_vjp(opt: str, momentum: float, b1: float, b2: float, eps: float,
             use_ref: bool, interpret: bool):
    """custom_vjp over the update pass:
    (G, p, m, v, scalars) -> (new_p, new_m, new_v).

    m/v (and their outputs/cotangents) are None for optimizers without the
    slot — None is an empty pytree, so custom_vjp threads it through.  The
    scalar cotangent covers [scale, lr, bc1, bc2]; lr's flows to meta-
    learned server step sizes, bc1/bc2's die at the int step counter."""
    hp = dict(opt=opt, momentum=momentum, b1=b1, b2=b2, eps=eps)

    @jax.custom_vjp
    def upd(G, p, m, v, scalars):
        if use_ref:
            return R.update_ref(G, p, m, v, scalars, **hp)
        return K.update_pass(G, p, m, v, scalars, interpret=interpret, **hp)

    def fwd(G, p, m, v, scalars):
        out = upd(G, p, m, v, scalars)
        return out, (G, m, v, scalars)

    def bwd(res, cts):
        G, m, v, scalars = res
        d_new_p, d_new_m, d_new_v = cts
        if use_ref:
            dG, dm, dv, dscal = R.update_bwd_ref(
                G, m, v, scalars, d_new_p, d_new_m, d_new_v, **hp)
        else:
            dG, dm, dv, dscal = K.update_pass_bwd(
                G, m, v, scalars, d_new_p, d_new_m, d_new_v,
                interpret=interpret, **hp)
        return dG, d_new_p, dm, dv, dscal    # dp = d_new_p (p' = p - lr*d)

    upd.defvjp(fwd, bwd)
    return upd


def flat_weighted_aggregate(spec: FlatSpec, grad_stack: PyTree,
                            client_weights: jax.Array, *,
                            use_ref: bool = False,
                            interpret: Optional[bool] = None
                            ) -> Tuple[list, jax.Array]:
    """Pass 1 alone: normalize ``client_weights``, flatten the stacked
    per-client gradients and run the differentiable aggregate kernel per
    dtype group.  Returns (G_groups, ssq) where ``ssq = ||G||^2`` summed
    over groups — exactly the interior of :func:`fused_server_update`, so
    cohort executors can produce the Eq. (14) flat weighted mean as a
    uniform handle and leave pass 2 to the server engine."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    w = client_weights.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)
    g_groups = flat_mod.flatten_stacked(spec, grad_stack)
    agg = _agg_vjp(use_ref, interpret)
    Gs, ssq = [], jnp.float32(0.0)
    for g_stack in g_groups:
        G, s = agg(g_stack, w)
        Gs.append(G)
        ssq = ssq + s
    return Gs, ssq


def flat_apply_groups(spec: FlatSpec, G_groups, gn, params: PyTree,
                      opt_state: PyTree, *, opt: str, lr,
                      clip_norm: float = 0.0, momentum: float = 0.9,
                      b1: float = 0.9, b2: float = 0.99, eps: float = 1e-8,
                      use_ref: bool = False,
                      interpret: Optional[bool] = None
                      ) -> Tuple[PyTree, PyTree, jax.Array]:
    """Pass 2 alone (public form of the shared ``_apply_groups``): clip
    scale + optimizer + param write over aggregated flat buffers, with the
    pre-clip global norm ``gn`` supplied by the caller (the aggregate
    kernel's ssq, or :func:`repro.core.flat.flat_sq_norm` for streamed
    accumulations).  Returns (new_params, new_opt_state, gn_after_clip)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _apply_groups(spec, list(G_groups), gn, params, opt_state,
                         opt=opt, lr=lr, clip_norm=clip_norm,
                         momentum=momentum, b1=b1, b2=b2, eps=eps,
                         use_ref=use_ref, interpret=interpret)


def fused_server_update(params: PyTree, grad_stack: PyTree,
                        client_weights: jax.Array, opt_state: PyTree, *,
                        opt: str = "sgd", lr, clip_norm: float = 0.0,
                        momentum: float = 0.9, b1: float = 0.9,
                        b2: float = 0.99, eps: float = 1e-8,
                        spec: Optional[FlatSpec] = None,
                        use_ref: bool = False,
                        interpret: Optional[bool] = None
                        ) -> Tuple[PyTree, PyTree, jax.Array]:
    """One fused server step over stacked per-client gradients.

    grad_stack: pytree matching ``params`` with a leading cohort axis on
    every leaf; client_weights: (cohort,) n_k (un-normalized);
    opt_state: flat state from :func:`init_flat_opt_state`.
    Returns (new_params, new_opt_state, grad_norm_after_clip)."""
    if spec is None:
        spec = make_flat_spec(params)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    Gs, ssq = flat_weighted_aggregate(spec, grad_stack, client_weights,
                                      use_ref=use_ref, interpret=interpret)

    return _apply_groups(spec, Gs, jnp.sqrt(ssq), params, opt_state,
                         opt=opt, lr=lr, clip_norm=clip_norm,
                         momentum=momentum, b1=b1, b2=b2, eps=eps,
                         use_ref=use_ref, interpret=interpret)


def fused_apply_flat(params: PyTree, G_groups, opt_state: PyTree, *,
                     opt: str = "sgd", lr, clip_norm: float = 0.0,
                     momentum: float = 0.9, b1: float = 0.9,
                     b2: float = 0.99, eps: float = 1e-8,
                     spec: Optional[FlatSpec] = None,
                     use_ref: bool = False,
                     interpret: Optional[bool] = None
                     ) -> Tuple[PyTree, PyTree, jax.Array]:
    """The clip+optimizer+write half of the engine over ALREADY-aggregated
    flat buffers — the scan strategy's entry point, where pass 1 happened
    as K streaming :func:`flat_accumulate` FMAs inside the cohort scan.

    G_groups: one (rows, LANES) fp32 buffer per dtype group of ``spec``
    holding the Eq. (14) weighted mean.  ||G||^2 is reduced here with plain
    jnp (one extra flat read; its VJP is the trivial 2G so no kernel is
    warranted).  Returns (new_params, new_opt_state, grad_norm_after_clip)
    exactly like :func:`fused_server_update`."""
    if spec is None:
        spec = make_flat_spec(params)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    gn = jnp.sqrt(flat_mod.flat_sq_norm(G_groups))
    return _apply_groups(spec, list(G_groups), gn, params,
                         opt_state, opt=opt, lr=lr, clip_norm=clip_norm,
                         momentum=momentum, b1=b1, b2=b2, eps=eps,
                         use_ref=use_ref, interpret=interpret)


def _apply_groups(spec: FlatSpec, Gs, gn, params: PyTree, opt_state: PyTree,
                  *, opt: str, lr, clip_norm: float, momentum: float,
                  b1: float, b2: float, eps: float, use_ref: bool,
                  interpret: bool) -> Tuple[PyTree, PyTree, jax.Array]:
    """Shared pass 2: clip scale + optimizer + param write over the flat
    dtype groups.  ``gn`` is the pre-clip global gradient norm."""
    upd = _upd_vjp(opt, momentum, b1, b2, eps, use_ref, interpret)
    p_groups = flat_mod.flatten_tree(spec, params)

    if clip_norm > 0:
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gn, 1e-9))
    else:
        scale = jnp.float32(1.0)

    if opt in ("adam", "yogi"):
        t = opt_state["t"] + 1
        tf = t.astype(jnp.float32)
        bc1 = 1.0 / (1.0 - b1 ** tf)
        bc2 = 1.0 / (1.0 - b2 ** tf)
    else:
        t = None
        bc1 = bc2 = jnp.float32(1.0)
    scalars = jnp.stack([scale, jnp.float32(lr), bc1, bc2]).reshape(1, 4)

    ms = opt_state.get("m", (None,) * len(spec.groups))
    vs = opt_state.get("v", (None,) * len(spec.groups))
    new_p, new_m, new_v = [], [], []
    for g, G, p, m, v in zip(spec.groups, Gs, p_groups, ms, vs):
        fn = upd
        if spec.mesh is not None:
            # Mosaic kernels are not partitioned automatically: run the
            # update on each device's block of the group (it is
            # elementwise given the replicated scalars)
            b = g.pspec if g.pspec is not None else P()
            fn = jax.shard_map(upd, mesh=spec.mesh,
                               in_specs=(b, b, b, b, P()),
                               out_specs=(b, b, b), check_vma=False)
        np_, nm, nv = fn(G, p, m, v, scalars)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)

    new_params = flat_mod.unflatten_tree(spec, new_p)
    if opt == "sgd":
        new_state: PyTree = {}
    elif opt == "sgdm":
        new_state = {"m": tuple(new_m)}
    else:
        new_state = {"m": tuple(new_m), "v": tuple(new_v), "t": t}
    return new_params, new_state, gn * scale
