"""Pallas TPU flash-attention forward kernel.

Blocked online-softmax with explicit VMEM tiling:

  grid = (batch*q_heads, Sq/bq, Skv/bk)   (kv axis innermost => sequential
                                           on TPU, accumulators in VMEM)
  q tile   (bq, D)   VMEM
  k,v tile (bk, D)   VMEM  (kv head = q head // group, via the index map —
                            GQA without materializing repeated KV)
  scratch: m (bq,), l (bq,), acc (bq, D)  float32 VMEM

bq/bk default 512/512 and D is a multiple of the 128-lane MXU dimension for
every assigned arch (head_dim 64/96/128/192) — tiles are hardware-aligned.
Numerics follow the same scheme as the XLA fallback
(repro.models.attention): fp32 max/exp/sum, bf16 operands into the MXU.

Validated on CPU with interpret=True against ref.py (the pure-jnp oracle);
on TPU the same pallas_call lowers through Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bq: int, bk: int, nk: int, scale: float, causal: bool,
               window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # (bq, D)
    k = k_ref[0]                                     # (bk, D)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    rel = q_pos - k_pos
    if causal:
        s = jnp.where(rel >= 0, s, NEG_INF)
    if window > 0:
        s = jnp.where(rel < window, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        bq: int = 512, bk: int = 512,
                        interpret: bool = False):
    """q: (BH, Sq, D); k/v: (BHkv, Skv, D) with BH = B*H, BHkv = B*Hkv and
    the head axis ordered (b, h) so kv_head = h // group.
    Returns (BH, Sq, D)."""
    BH, Sq, D = q.shape
    BHkv, Skv, _ = k.shape
    assert BH % BHkv == 0
    group = BH // BHkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                               causal=causal, window=window)
    scratch = [pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, D), jnp.float32)]

    # NOTE on the head index maps: q/o tiles walk (bh, qi); k/v tiles share
    # one kv head across `group` q heads (bh // group) — GQA stays a pure
    # indexing fact, no repeated KV in HBM.
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki, group=group:
                         (bh // group, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki, group=group:
                         (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
