"""Pallas TPU kernels for the fused server update (aggregate -> clip ->
apply) over flat fp32 buffers (layout: ``repro.core.flat``).

Two forward kernels, at most two passes over HBM per round:

  * :func:`aggregate_pass` — grid walks row tiles of the stacked client
    gradients ``(cohort, rows, LANES)``; each step reduces the cohort axis
    with the normalized weights (Eq. 14) and accumulates the global
    sum-of-squares into a (1, 1) output revisited by every grid step (TPU
    grids are sequential, so the accumulation is well-defined — same idiom
    as the flash_attention kv axis).
  * :func:`update_pass` — grid walks row tiles of the aggregated gradient,
    applies the clip scale and the server optimizer (sgd/sgdm/adam/yogi)
    and writes the new parameters (+ m/v slots) in one sweep.  Traced
    scalars (clip scale, lr, bias corrections) ride in a (1, 4) SMEM
    operand; static hyper-parameters (momentum, b1, b2, eps) are baked in.

A third forward kernel serves the client-sequential (scan) cohort
strategy, where the per-client gradients are never stacked:

  * :func:`accumulate_pass` — fused-multiply-add of ONE client's flattened
    gradient into the group accumulator, ``acc + w_k * g_k``, in a single
    HBM sweep.  The scan carry is the flat buffer itself, so a scan round
    is K streaming accumulates plus the same :func:`update_pass` — no
    pytree-carry tree-maps, no flatten round-trip of the aggregate.

Backward kernels give each pair a hand-written VJP (wired up by the
``jax.custom_vjp`` ops in ``ops.py``) so meta-learning *through* the
aggregation never falls back to XLA re-differentiating the engine:

  * :func:`aggregate_pass_bwd` — scatters the total cotangent of the mean
    ``dG + 2*dssq*G`` back to the ``(cohort, rows, LANES)`` stack
    (``dg_k = w_k * dGt``) and accumulates the per-client weight cotangents
    ``dw_k = <g_k, dGt>`` into a (cohort, 1) output revisited by every grid
    step.
  * :func:`accumulate_pass_bwd` — for the streaming FMA: ``d_acc`` is the
    identity (handled by the caller), ``dg_k = w_k * d_out`` and
    ``dw_k = <g_k, d_out>`` accumulated into a (1, 1) output.  Because the
    accumulator cotangent passes through later scan steps unchanged,
    ``d_out`` at step k IS the cotangent of the final aggregate, so
    ``dw_k = <g_k, dG>`` — exactly the through-aggregation hypergradient
    (g_k is recomputed under ``jax.checkpoint`` by the surrounding scan,
    one client trajectory alive at a time).
  * :func:`update_pass_bwd` — replays the optimizer recurrence from the
    saved (G, m, v, scalars) residuals and pushes the output cotangents
    (d new_p, d new_m, d new_v) back into gradient / opt-state cotangents
    plus the (1, 4) scalar cotangents [dscale, dlr, dbc1, dbc2].  ``sign``
    in yogi is treated as locally constant (the same zero-derivative
    convention XLA autodiff uses for ``jnp.sign``), and the ``sqrt`` factor
    is zero-guarded so the zero-padded tail rows of the flat layout produce
    exact zeros instead of ``0 * inf`` NaNs.

All kernels run on CPU with ``interpret=True`` (how the tier-1 suite
validates them) and lower through Mosaic on TPU unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flat import LANES

# scalar operand layout for update_pass: [scale, lr, bc1, bc2]
N_SCALARS = 4


def _block_rows(rows: int, target: int = 256) -> int:
    """Largest power-of-two row tile <= target that divides ``rows``
    (FlatSpec rows are a multiple of ``flat.ROW_ALIGN``, so the default
    target always divides them)."""
    br = min(target, rows)
    while rows % br:
        br //= 2
    return max(br, 1)


def _scalar_spec(cols: int, interpret: bool):
    """(1, cols) scalar operand or accumulator placement: SMEM on real
    TPUs (Mosaic refuses scalar stores to VMEM), default memory in
    interpret mode."""
    if not interpret:
        return pl.BlockSpec((1, cols), lambda i: (0, 0),
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec((1, cols), lambda i: (0, 0))


# ---------------------------------------------------------------------------
# Pass 1: weighted cohort reduce + global sum-of-squares
# ---------------------------------------------------------------------------
def _aggregate_kernel(w_ref, g_ref, out_ref, ssq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ssq_ref[0, 0] = jnp.float32(0.0)

    g = g_ref[...]                                    # (cohort, br, LANES)
    w = w_ref[...]                                    # (cohort, 1)
    G = jnp.sum(g * w[:, :, None], axis=0)            # (br, LANES)
    out_ref[...] = G
    ssq_ref[0, 0] += jnp.sum(G * G)


def aggregate_pass(g_stack: jax.Array, w_norm: jax.Array, *,
                   block_rows: int = 256, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """g_stack: (cohort, rows, LANES) fp32; w_norm: (cohort,) normalized
    weights.  Returns (G (rows, LANES) fp32, ssq () fp32)."""
    cohort, rows, lanes = g_stack.shape
    assert lanes == LANES, g_stack.shape
    br = _block_rows(rows, block_rows)
    G, ssq = pl.pallas_call(
        _aggregate_kernel,
        name="aggregate_pass",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((cohort, 1), lambda i: (0, 0)),
            pl.BlockSpec((cohort, br, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            _scalar_spec(1, interpret),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w_norm.astype(jnp.float32).reshape(cohort, 1), g_stack)
    return G, ssq[0, 0]


# ---------------------------------------------------------------------------
# Streaming pass (scan strategy): acc <- acc + w_k * g_k in one HBM sweep
# ---------------------------------------------------------------------------
def _accumulate_kernel(w_ref, acc_ref, g_ref, out_ref):
    out_ref[...] = acc_ref[...] + w_ref[0, 0] * g_ref[...]


def accumulate_pass(acc: jax.Array, g: jax.Array, w, *,
                    block_rows: int = 256, interpret: bool = False
                    ) -> jax.Array:
    """acc/g: (rows, LANES) fp32; w: scalar normalized client weight.
    Returns ``acc + w * g`` — the per-client streaming Eq. (14) term the
    scan strategy carries instead of a pytree."""
    rows, lanes = acc.shape
    assert lanes == LANES, acc.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    w_spec = _scalar_spec(1, interpret)
    out = pl.pallas_call(
        _accumulate_kernel,
        name="accumulate_pass",
        grid=(rows // br,),
        in_specs=[w_spec, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(w, jnp.float32).reshape(1, 1), acc, g)
    return out


def _accumulate_bwd_kernel(w_ref, g_ref, dout_ref, dg_ref, dw_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[0, 0] = jnp.float32(0.0)

    dout = dout_ref[...]
    dg_ref[...] = w_ref[0, 0] * dout                  # dg_k = w_k d_out
    dw_ref[0, 0] += jnp.sum(g_ref[...] * dout)        # dw_k = <g_k, d_out>


def accumulate_pass_bwd(g: jax.Array, w, d_out: jax.Array, *,
                        block_rows: int = 256, interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """VJP of :func:`accumulate_pass` w.r.t. (g, w); the accumulator
    cotangent is the identity and handled by the caller.  Returns
    (dg (rows, LANES), dw ())."""
    rows, lanes = g.shape
    assert lanes == LANES, g.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    w_spec = _scalar_spec(1, interpret)
    dg, dw = pl.pallas_call(
        _accumulate_bwd_kernel,
        name="accumulate_pass_bwd",
        grid=(rows // br,),
        in_specs=[w_spec, tile, tile],
        out_specs=[tile, _scalar_spec(1, interpret)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(w, jnp.float32).reshape(1, 1), g, d_out)
    return dg, dw[0, 0]


# ---------------------------------------------------------------------------
# Pass 2: clip-scale + server optimizer + parameter write
# ---------------------------------------------------------------------------
def _update_kernel(scal_ref, *refs, opt: str, momentum: float, b1: float,
                   b2: float, eps: float):
    scale = scal_ref[0, 0]
    lr = scal_ref[0, 1]
    g = refs[0][...] * scale                          # clipped gradient tile
    p = refs[1][...]

    if opt == "sgd":
        new_p_ref = refs[2]
        new_p_ref[...] = p - lr * g
        return
    if opt == "sgdm":
        m_ref, new_p_ref, new_m_ref = refs[2], refs[3], refs[4]
        m = momentum * m_ref[...] + g
        new_m_ref[...] = m
        new_p_ref[...] = p - lr * m
        return
    # adam / yogi
    bc1 = scal_ref[0, 2]
    bc2 = scal_ref[0, 3]
    m_ref, v_ref = refs[2], refs[3]
    new_p_ref, new_m_ref, new_v_ref = refs[4], refs[5], refs[6]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    if opt == "adam":
        v = b2 * v_ref[...] + (1.0 - b2) * g * g
    else:  # yogi
        v0 = v_ref[...]
        v = v0 - (1.0 - b2) * jnp.sign(v0 - g * g) * g * g
    new_m_ref[...] = m
    new_v_ref[...] = v
    new_p_ref[...] = p - lr * (m * bc1) / (jnp.sqrt(v * bc2) + eps)


def update_pass(G: jax.Array, p: jax.Array, m: Optional[jax.Array],
                v: Optional[jax.Array], scalars: jax.Array, *, opt: str,
                momentum: float = 0.9, b1: float = 0.9, b2: float = 0.99,
                eps: float = 1e-8, block_rows: int = 256,
                interpret: bool = False):
    """One fused optimizer sweep over a flat buffer group.

    scalars: (1, N_SCALARS) fp32 = [scale, lr, bc1, bc2] (traced).
    Returns (new_p, new_m, new_v) with None slots per optimizer arity."""
    rows, lanes = G.shape
    assert lanes == LANES, G.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    scal_spec = _scalar_spec(N_SCALARS, interpret)
    buf = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)

    state_in = {"sgd": [], "sgdm": [m], "adam": [m, v], "yogi": [m, v]}[opt]
    n_out = 1 + len(state_in)
    kernel = functools.partial(_update_kernel, opt=opt, momentum=momentum,
                               b1=b1, b2=b2, eps=eps)
    outs = pl.pallas_call(
        kernel,
        name="update_pass",
        grid=(rows // br,),
        in_specs=[scal_spec] + [tile] * (2 + len(state_in)),
        out_specs=[tile] * n_out,
        out_shape=[buf] * n_out,
        interpret=interpret,
    )(scalars.astype(jnp.float32), G, p, *state_in)
    new_p = outs[0]
    new_m = outs[1] if len(outs) > 1 else None
    new_v = outs[2] if len(outs) > 2 else None
    return new_p, new_m, new_v


# ---------------------------------------------------------------------------
# Backward pass 1: cotangent-of-mean scatter + per-client weight cotangents
# ---------------------------------------------------------------------------
def _aggregate_bwd_kernel(w_ref, dssq_ref, g_ref, G_ref, dG_ref,
                          dg_ref, dw_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # total mean cotangent: forward was G = sum_k w_k g_k, ssq = <G, G>
    dGt = dG_ref[...] + 2.0 * dssq_ref[0, 0] * G_ref[...]     # (br, LANES)
    dg_ref[...] = w_ref[...][:, :, None] * dGt[None, :, :]    # dg_k = w_k dGt
    dw_ref[...] += jnp.sum(jnp.sum(g_ref[...] * dGt[None, :, :], axis=2),
                           axis=1, keepdims=True)             # dw_k = <g_k,dGt>


def aggregate_pass_bwd(g_stack: jax.Array, w_norm: jax.Array, G: jax.Array,
                       dG: jax.Array, dssq: jax.Array, *,
                       block_rows: int = 256, interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """VJP of :func:`aggregate_pass` w.r.t. (g_stack, w_norm).

    g_stack/(dG, dssq): primals/cotangents as produced by the forward; G is
    the saved forward output.  Returns (dg_stack (cohort, rows, LANES),
    dw (cohort,))."""
    cohort, rows, lanes = g_stack.shape
    assert lanes == LANES, g_stack.shape
    br = _block_rows(rows, block_rows)
    dg, dw = pl.pallas_call(
        _aggregate_bwd_kernel,
        name="aggregate_pass_bwd",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((cohort, 1), lambda i: (0, 0)),
            _scalar_spec(1, interpret),
            pl.BlockSpec((cohort, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((cohort, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((cohort, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cohort, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((cohort, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w_norm.astype(jnp.float32).reshape(cohort, 1),
      dssq.astype(jnp.float32).reshape(1, 1), g_stack, G, dG)
    return dg, dw[:, 0]


# ---------------------------------------------------------------------------
# Backward pass 2: cotangents through clip-scale + optimizer recurrence
# ---------------------------------------------------------------------------
def _update_bwd_kernel(scal_ref, *refs, opt: str, momentum: float, b1: float,
                       b2: float, eps: float):
    i = pl.program_id(0)
    s = scal_ref[0, 0]
    lr = scal_ref[0, 1]
    G = refs[0][...]
    g = G * s                                         # clipped gradient tile
    dbc1 = dbc2 = jnp.float32(0.0)

    if opt == "sgd":
        # p' = p - lr * g
        dpn_ref, dG_ref, dscal_ref = refs[1], refs[2], refs[3]
        dpn = dpn_ref[...]
        dg = -lr * dpn
        dlr = -jnp.sum(g * dpn)
    elif opt == "sgdm":
        # m' = mu m + g;  p' = p - lr m'
        m_ref, dpn_ref, dmn_ct_ref = refs[1], refs[2], refs[3]
        dG_ref, dm_ref, dscal_ref = refs[4], refs[5], refs[6]
        dpn = dpn_ref[...]
        m_new = momentum * m_ref[...] + g
        dmn = dmn_ct_ref[...] - lr * dpn
        dlr = -jnp.sum(m_new * dpn)
        dg = dmn
        dm_ref[...] = momentum * dmn
    else:  # adam / yogi: p' = p - lr * (m' bc1) / (sqrt(v' bc2) + eps)
        bc1 = scal_ref[0, 2]
        bc2 = scal_ref[0, 3]
        m_ref, v_ref = refs[1], refs[2]
        dpn_ref, dmn_ct_ref, dvn_ct_ref = refs[3], refs[4], refs[5]
        dG_ref, dm_ref, dv_ref, dscal_ref = refs[6], refs[7], refs[8], refs[9]
        dpn = dpn_ref[...]
        m_new = b1 * m_ref[...] + (1.0 - b1) * g
        if opt == "adam":
            v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
        else:  # yogi (sign treated locally constant, like XLA's jnp.sign)
            sgn = jnp.sign(v_ref[...] - g * g)
            v_new = v_ref[...] - (1.0 - b2) * sgn * g * g
        rs = jnp.sqrt(v_new * bc2)
        denom = rs + eps
        step = m_new * bc1 / denom
        dstep = -lr * dpn
        dlr = -jnp.sum(step * dpn)
        dmn = dmn_ct_ref[...] + dstep * (bc1 / denom)
        dbc1 = jnp.sum(dstep * m_new / denom)
        ddenom = -dstep * step / denom
        # d sqrt blows up at 0; the padded tail rows (g = m = v = 0) must
        # stay exact zeros, so zero-guard the 1/(2 sqrt) factor.
        inv2rs = jnp.where(rs > 0.0, 0.5 / jnp.maximum(rs, 1e-30), 0.0)
        dvn = dvn_ct_ref[...] + ddenom * bc2 * inv2rs
        dbc2 = jnp.sum(ddenom * v_new * inv2rs)
        dm_ref[...] = b1 * dmn
        if opt == "adam":
            dv_ref[...] = b2 * dvn
            dg = (1.0 - b1) * dmn + 2.0 * (1.0 - b2) * g * dvn
        else:
            dv_ref[...] = dvn
            dg = (1.0 - b1) * dmn - 2.0 * (1.0 - b2) * sgn * g * dvn

    dG_ref[...] = s * dg

    @pl.when(i == 0)
    def _init():
        dscal_ref[0, 0] = jnp.float32(0.0)
        dscal_ref[0, 1] = jnp.float32(0.0)
        dscal_ref[0, 2] = jnp.float32(0.0)
        dscal_ref[0, 3] = jnp.float32(0.0)

    dscal_ref[0, 0] += jnp.sum(G * dg)                # dscale
    dscal_ref[0, 1] += dlr
    dscal_ref[0, 2] += dbc1
    dscal_ref[0, 3] += dbc2


def update_pass_bwd(G: jax.Array, m: Optional[jax.Array],
                    v: Optional[jax.Array], scalars: jax.Array,
                    d_new_p: jax.Array, d_new_m: Optional[jax.Array],
                    d_new_v: Optional[jax.Array], *, opt: str,
                    momentum: float = 0.9, b1: float = 0.9, b2: float = 0.99,
                    eps: float = 1e-8, block_rows: int = 256,
                    interpret: bool = False):
    """VJP of :func:`update_pass` w.r.t. (G, m, v, scalars); the param
    cotangent is the identity (p' = p - lr * step) and handled by the
    caller.  (G, m, v, scalars) are the saved forward residuals — the
    optimizer recurrence is replayed in-kernel rather than saving m'/v'.

    Returns (dG, dm, dv, dscalars (1, N_SCALARS)) with None slots matching
    the optimizer's state arity."""
    rows, lanes = G.shape
    assert lanes == LANES, G.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    scal_spec = _scalar_spec(N_SCALARS, interpret)
    buf = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    scal_buf = jax.ShapeDtypeStruct((1, N_SCALARS), jnp.float32)

    state_in = {"sgd": [], "sgdm": [m], "adam": [m, v], "yogi": [m, v]}[opt]
    ct_in = {"sgd": [d_new_p], "sgdm": [d_new_p, d_new_m],
             "adam": [d_new_p, d_new_m, d_new_v],
             "yogi": [d_new_p, d_new_m, d_new_v]}[opt]
    n_state = len(state_in)
    kernel = functools.partial(_update_bwd_kernel, opt=opt, momentum=momentum,
                               b1=b1, b2=b2, eps=eps)
    outs = pl.pallas_call(
        kernel,
        name="update_pass_bwd",
        grid=(rows // br,),
        in_specs=[scal_spec] + [tile] * (1 + n_state + len(ct_in)),
        out_specs=[tile] * (1 + n_state) + [scal_spec],
        out_shape=[buf] * (1 + n_state) + [scal_buf],
        interpret=interpret,
    )(scalars.astype(jnp.float32), G, *state_in, *ct_in)
    dG = outs[0]
    dm = outs[1] if n_state >= 1 else None
    dv = outs[2] if n_state >= 2 else None
    return dG, dm, dv, outs[-1]
