"""Pure-jnp oracle for the fused server-update kernels.

Operates on the flat-buffer layout of ``repro.core.flat`` with exactly the
two-pass structure of the Pallas kernels:

  pass 1  (accumulate): acc + w_k g_k, one client at a time
  pass 2  (apply):      d = optimizer(G * scale);  p <- p - lr * d

The per-optimizer math mirrors ``repro.core.server_opt.apply`` line for
line (fp32 throughout); bias corrections for adam/yogi arrive as the
precomputed scalars bc1 = 1/(1-b1^t), bc2 = 1/(1-b2^t).

:func:`accumulate_bwd_ref` / :func:`update_bwd_ref` are the matching
hand-derived VJPs — the oracles for ``kernel.accumulate_pass_bwd`` /
``kernel.update_pass_bwd`` and the ``use_ref=True`` arm of the
``jax.custom_vjp`` ops in ``ops.py``.  Same conventions as the kernels:
yogi's ``sign`` is locally constant and the adam/yogi ``1/(2 sqrt)``
factor is zero-guarded so padded (all-zero) rows backprop exact zeros.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def accumulate_ref(acc: jax.Array, g: jax.Array, w) -> jax.Array:
    """Streaming Eq. (14) term: ``acc + w * g`` over one client's flat
    (rows, lanes) fp32 gradient buffer."""
    return acc + jnp.asarray(w, jnp.float32) * g


def accumulate_bwd_ref(g: jax.Array, w, d_out: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """VJP of :func:`accumulate_ref` w.r.t. (g, w); the accumulator
    cotangent is the identity and handled by the caller.
    dg = w d_out, dw = <g, d_out>."""
    return jnp.asarray(w, jnp.float32) * d_out, jnp.sum(g * d_out)


def update_ref(G: jax.Array, p: jax.Array, m: Optional[jax.Array],
               v: Optional[jax.Array], scalars: jax.Array, *, opt: str,
               momentum: float = 0.9, b1: float = 0.9, b2: float = 0.99,
               eps: float = 1e-8):
    """One flat-buffer optimizer step.  ``scalars`` is the same (1, 4)
    [scale, lr, bc1, bc2] operand ``kernel.update_pass`` takes (signature
    parity is the fedlint FL202 contract: the oracle must be callable
    exactly like the kernel).  Returns (new_p, new_m, new_v) with None
    slots matching the optimizer's state arity."""
    scale, lr, bc1, bc2 = (scalars[0, 0], scalars[0, 1], scalars[0, 2],
                           scalars[0, 3])
    g = G * scale
    if opt == "sgd":
        return p - lr * g, None, None
    if opt == "sgdm":
        m_new = momentum * m + g
        return p - lr * m_new, m_new, None
    if opt in ("adam", "yogi"):
        m_new = b1 * m + (1.0 - b1) * g
        if opt == "adam":
            v_new = b2 * v + (1.0 - b2) * g * g
        else:
            v_new = v - (1.0 - b2) * jnp.sign(v - g * g) * g * g
        step = (m_new * bc1) / (jnp.sqrt(v_new * bc2) + eps)
        return p - lr * step, m_new, v_new
    raise ValueError(opt)


def update_bwd_ref(G: jax.Array, m: Optional[jax.Array],
                   v: Optional[jax.Array], scalars: jax.Array,
                   d_new_p: jax.Array, d_new_m: Optional[jax.Array],
                   d_new_v: Optional[jax.Array], *, opt: str,
                   momentum: float = 0.9, b1: float = 0.9, b2: float = 0.99,
                   eps: float = 1e-8):
    """VJP of :func:`update_ref` w.r.t. (G, m, v, scalars); the param
    cotangent is the identity and handled by the caller.  scalars is the
    (1, 4) [scale, lr, bc1, bc2] operand of the forward; the recurrence is
    replayed from the (G, m, v) residuals.  Returns (dG, dm, dv,
    dscalars (1, 4)) with None slots matching the optimizer arity."""
    s = scalars[0, 0]
    lr = scalars[0, 1]
    g = G * s
    dbc1 = dbc2 = jnp.float32(0.0)

    if opt == "sgd":
        dg = -lr * d_new_p
        dlr = -jnp.sum(g * d_new_p)
        dm = dv = None
    elif opt == "sgdm":
        m_new = momentum * m + g
        dmn = d_new_m - lr * d_new_p
        dlr = -jnp.sum(m_new * d_new_p)
        dg = dmn
        dm, dv = momentum * dmn, None
    elif opt in ("adam", "yogi"):
        bc1 = scalars[0, 2]
        bc2 = scalars[0, 3]
        m_new = b1 * m + (1.0 - b1) * g
        if opt == "adam":
            v_new = b2 * v + (1.0 - b2) * g * g
        else:
            sgn = jnp.sign(v - g * g)
            v_new = v - (1.0 - b2) * sgn * g * g
        rs = jnp.sqrt(v_new * bc2)
        denom = rs + eps
        step = m_new * bc1 / denom
        dstep = -lr * d_new_p
        dlr = -jnp.sum(step * d_new_p)
        dmn = d_new_m + dstep * (bc1 / denom)
        dbc1 = jnp.sum(dstep * m_new / denom)
        ddenom = -dstep * step / denom
        inv2rs = jnp.where(rs > 0.0, 0.5 / jnp.maximum(rs, 1e-30), 0.0)
        dvn = d_new_v + ddenom * bc2 * inv2rs
        dbc2 = jnp.sum(ddenom * v_new * inv2rs)
        dm = b1 * dmn
        if opt == "adam":
            dv = b2 * dvn
            dg = (1.0 - b1) * dmn + 2.0 * (1.0 - b2) * g * dvn
        else:
            dv = dvn
            dg = (1.0 - b1) * dmn - 2.0 * (1.0 - b2) * sgn * g * dvn
    else:
        raise ValueError(opt)

    dscal = jnp.stack([jnp.sum(G * dg), dlr, dbc1, dbc2]).reshape(1, 4)
    return s * dg, dm, dv, dscal
