"""Attention variants (pure JAX / XLA path).

``chunked_attention`` is a blocked online-softmax ("flash"-style) attention
written with two nested ``lax.scan``s so that the (S x S) score matrix is
never materialized — this is the XLA fallback used by the multi-pod dry-run
(Pallas/Mosaic custom calls do not lower on the CPU backend) and the oracle
the Pallas kernel is validated against.

Supports: causal masking, sliding windows, GQA (q heads grouped over kv
heads), cross-attention (causal=False), and Dk != Dv (needed by MLA whose
keys carry a decoupled RoPE slice).

Decode paths:
  - ``decode_attention``       : one-token query against a (possibly ring-
                                 buffer windowed) KV cache;
  - ``flash_decode_partial`` / ``combine_partials``: sequence-sharded decode
    for the 500k cache — each shard produces (m, l, o) partials which are
    combined with pmax/psum inside ``shard_map`` (see sharding/longctx.py).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _pad_to(x, axis: int, mult: int):
    s = x.shape[axis]
    rem = (-s) % mult
    if rem == 0:
        return x, s
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), s


# ---------------------------------------------------------------------------
# Blocked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------
def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_offset=0, q_block: int = 512, kv_block: int = 1024,
                      kv_len: Optional[jax.Array] = None):
    """q: (B, Sq, H, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv).

    window > 0 restricts attention to the last `window` positions (causal
    only).  q_offset: absolute position of q[0] (int or scalar array) for
    continued decoding / paged prefill.  kv_len: (B,) valid kv lengths.
    Returns (B, Sq, H, Dv).
    """
    B, Sq, H, Dk = q.shape
    Skv, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dk)
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)

    qp, Sq0 = _pad_to(q, 1, q_block)
    kp, Skv0 = _pad_to(k, 1, kv_block)
    vp, _ = _pad_to(v, 1, kv_block)
    nq, nk = qp.shape[1] // q_block, kp.shape[1] // kv_block

    # (nq, B, qb, Hkv, G, Dk)
    qs = qp.reshape(B, nq, q_block, Hkv, G, Dk).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(B, nk, kv_block, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, kv_block, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    valid_len = kv_len if kv_len is not None else jnp.full((B,), Skv0, jnp.int32)

    def q_step(_, qi):
        qblk, qidx = qi                       # (B, qb, Hkv, G, Dk), scalar
        q_pos = q_offset + qidx * q_block + jnp.arange(q_block)       # (qb,)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * kv_block + jnp.arange(kv_block)            # (kb,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            # validity: (B, 1, 1, 1, kb) — padded / beyond-kv_len slots
            valid = (k_pos[None, :] < valid_len[:, None])[:, None, None, None, :]
            rel = (q_pos[:, None] - k_pos[None, :])[None, None, None]  # (1,1,1,qb,kb)
            mask = valid
            if causal:
                mask = mask & (rel >= 0)
            if window > 0:
                mask = mask & (rel < window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))               # (B,Hkv,G,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (ks, vs, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]                  # (B,Hkv,G,qb,Dv)
        return None, out.transpose(0, 3, 1, 2, 4)                     # (B,qb,Hkv,G,Dv)

    _, outs = lax.scan(q_step, None, (qs, jnp.arange(nq)))            # (nq,B,qb,...)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_block, H, Dv)
    return out[:, :Sq0].astype(v.dtype)


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (memory-optimal train path)
# ---------------------------------------------------------------------------
# Naive reverse-mode through the online-softmax scan saves the (m, l, acc)
# carry for every KV block — O(S^2/blk) residual memory, which blew the HBM
# budget in the first dry-run (EXPERIMENTS.md §Perf iteration 1).  The
# custom VJP stores only (q, k, v, out, lse) and recomputes the probability
# blocks in the backward pass — the standard flash-attention backward, and
# the exact scheme the Pallas kernel (repro.kernels.flash_attention) uses.

def _block_bias(qidx, kidx, q_block, kv_block, causal, window):
    """Rank-2 additive mask (qb, kb) in fp32 — rank-2 so that XLA's
    loop-invariant hoisting (which materializes a stacked buffer of every
    scan step's mask) costs O(nq*nk*qb*kb), not O(... * B * H) — the
    broadcast-pred blow-up of §Perf iteration 1."""
    q_pos = qidx * q_block + jnp.arange(q_block)
    k_pos = kidx * kv_block + jnp.arange(kv_block)
    rel = q_pos[:, None] - k_pos[None, :]
    bias = jnp.zeros((q_block, kv_block), jnp.float32)
    if causal:
        bias = jnp.where(rel >= 0, bias, NEG_INF)
    if window > 0:
        bias = jnp.where(rel < window, bias, NEG_INF)
    return bias


def _fa_fwd_inner(q, k, v, causal, window, q_block, kv_block):
    """Returns (out (B,Sq,H,Dv), lse (B,Hkv,G,Sq))."""
    B, Sq, H, Dk = q.shape
    Skv, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dk)
    nq, nk = Sq // q_block, Skv // kv_block
    qs = q.reshape(B, nq, q_block, Hkv, G, Dk).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kv_block, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_block, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi):
        qblk, qidx = qi

        # checkpoint: when the whole attention is differentiated a second
        # time (UGA's keep-trace trajectory), the backward of this scan must
        # recompute the block body instead of stacking per-(qb,kb) p-block
        # residuals across the period scan — the 1 TB/chip blow-up of §Perf
        # iteration 1.
        @partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _block_bias(qidx, kidx, q_block, kv_block, causal, window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (ks, vs, jnp.arange(nk)))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4)
        lse = m + jnp.log(l_safe)
        return None, (out, lse)

    _, (outs, lses) = lax.scan(q_step, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dv)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, Sq)
    return out.astype(v.dtype), lse


def _fa_bwd_inner(q, k, v, out, lse, do, causal, window, q_block, kv_block):
    B, Sq, H, Dk = q.shape
    Skv, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dk)
    nq, nk = Sq // q_block, Skv // kv_block
    qs = q.reshape(B, nq, q_block, Hkv, G, Dk).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kv_block, Hkv, Dk).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_block, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    dos = do.reshape(B, nq, q_block, Hkv, G, Dv).transpose(1, 0, 2, 3, 4, 5)
    lses = lse.reshape(B, Hkv, G, nq, q_block).transpose(3, 0, 1, 2, 4)
    # delta = rowsum(do * out): (B,Sq,H) -> block layout (nq,B,Hkv,G,qb)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    deltas = delta.reshape(B, nq, q_block, Hkv, G).transpose(1, 0, 3, 4, 2)

    def q_step(carry, qi):
        dk_acc, dv_acc = carry                     # (nk,B,kb,Hkv,Dk/Dv) f32
        qblk, doblk, lseblk, dblk, qidx = qi

        @partial(jax.checkpoint, prevent_cse=False)
        def kv_step(dq_acc, ki):
            kblk, vblk, kidx = ki
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _block_bias(qidx, kidx, q_block, kv_block, causal, window)
            p = jnp.exp(s - lseblk[..., None])     # (B,Hkv,G,qb,kb)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", doblk, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dblk[..., None]) * scale
            dq_c = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kblk,
                              preferred_element_type=jnp.float32)
            dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qblk,
                              preferred_element_type=jnp.float32)
            dv_c = jnp.einsum("bhgqk,bqhgd->bkhd",
                              p.astype(jnp.float32), doblk.astype(jnp.float32),
                              preferred_element_type=jnp.float32)
            return dq_acc + dq_c, (dk_c, dv_c)

        dq0 = jnp.zeros((B, q_block, Hkv, G, Dk), jnp.float32)
        dq, (dk_c, dv_c) = lax.scan(kv_step, dq0,
                                    (ks, vs, jnp.arange(nk)))
        return (dk_acc + dk_c, dv_acc + dv_c), dq

    dk0 = jnp.zeros((nk, B, kv_block, Hkv, Dk), jnp.float32)
    dv0 = jnp.zeros((nk, B, kv_block, Hkv, Dv), jnp.float32)
    (dk_blocks, dv_blocks), dqs = lax.scan(
        q_step, (dk0, dv0), (qs, dos, lses, deltas, jnp.arange(nq)))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dk)
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, Dk)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512):
    """Memory-optimal blocked attention for train/prefill.

    q: (B,Sq,H,Dk), k: (B,Skv,Hkv,Dk), v: (B,Skv,Hkv,Dv); Sq/Skv must be
    multiples of the block sizes (callers pad).  GQA via H = G*Hkv.
    """
    out, _ = _fa_fwd_inner(q, k, v, causal, window,
                           min(q_block, q.shape[1]),
                           min(kv_block, k.shape[1]))
    return out


def _fa_fwd(q, k, v, causal, window, q_block, kv_block):
    out, lse = _fa_fwd_inner(q, k, v, causal, window,
                             min(q_block, q.shape[1]),
                             min(kv_block, k.shape[1]))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, q_block, kv_block, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _fa_bwd_inner(q, k, v, out, lse, do, causal, window,
                               min(q_block, q.shape[1]),
                               min(kv_block, k.shape[1]))
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def attend(q, k, v, *, causal: bool = True, window: int = 0,
           q_block: int = 512, kv_block: int = 512):
    """Dispatch: flash (custom-vjp) path when shapes are block-divisible,
    else the plain chunked scan (small smoke shapes).  Traced under the
    ``repro.attention`` named scope, forward and backward."""
    qb = min(q_block, q.shape[1])
    kb = min(kv_block, k.shape[1])
    with jax.named_scope("repro.attention"):
        if q.shape[1] % qb == 0 and k.shape[1] % kb == 0:
            return flash_attention(q, k, v, causal, window, qb, kb)
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_block=qb, kv_block=kb)


def simple_attention(q, k, v, *, causal: bool = True, window: int = 0,
                     q_offset=0, kv_len: Optional[jax.Array] = None):
    """Direct softmax attention — oracle for tests; same semantics."""
    B, Sq, H, Dk = q.shape
    Skv, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dk)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(Dk)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    rel = q_pos[:, None] - k_pos[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask = mask & (rel >= 0)
    if window > 0:
        mask = mask & (rel < window)
    mask = jnp.broadcast_to(mask[None, None, None], s.shape)
    if kv_len is not None:
        mask = mask & (k_pos[None, None, None, None, :] <
                       kv_len[:, None, None, None, None].astype(k_pos.dtype))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, Dv)


# ---------------------------------------------------------------------------
# Decode (one new token against a cache)
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, index, *, window: int = 0):
    """q: (B, H, Dk); caches: (B, S, Hkv, Dk/Dv); index: scalar int32 —
    number of tokens already in the cache (the new token's position).

    With window > 0 the cache is a ring buffer of size S == window and every
    slot written so far is valid (slot_pos = index - distance handled by the
    caller's ring arithmetic; validity simply requires slot < min(index+1, S)
    after the caller wrote the current token at index % S).
    """
    B, S, Hkv, Dk = k_cache.shape
    Dv = v_cache.shape[-1]
    H = q.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Dk)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(Dk)
    k_pos = jnp.arange(S)
    if window > 0:
        valid = k_pos < jnp.minimum(index + 1, S)          # ring buffer
    else:
        valid = k_pos <= index
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, H, Dv)


def flash_decode_partial(q, k_shard, v_shard, index, shard_offset):
    """Per-shard online-softmax partials for sequence-sharded decode.

    q: (B, H, Dk); k/v_shard: (B, S_loc, Hkv, D*); shard_offset: scalar —
    absolute position of this shard's first cache slot.
    Returns (m, l, o): (B,H), (B,H), (B,H,Dv) — combine with
    ``combine_partials`` (psum/pmax over the sequence-sharding axis).
    """
    B, S_loc, Hkv, Dk = k_shard.shape
    H = q.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Dk)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_shard,
                   preferred_element_type=jnp.float32) / math.sqrt(Dk)
    pos = shard_offset + jnp.arange(S_loc)
    s = jnp.where((pos <= index)[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_shard.dtype), v_shard)
    Dv = v_shard.shape[-1]
    return (m.reshape(B, H), l.reshape(B, H),
            o.reshape(B, H, Dv).astype(jnp.float32))


def combine_partials(m, l, o, axis_name: str):
    """Combine flash-decode partials across `axis_name` (inside shard_map)."""
    m_g = lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = lax.psum(l * corr, axis_name)
    o_g = lax.psum(o * corr[..., None], axis_name)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# GQA projection block (q/k/v/o) shared by the transformer stack
# ---------------------------------------------------------------------------
def gqa_init(key, d_model: int, num_heads: int, num_kv_heads: int,
             head_dim: int, dtype=jnp.float32):
    from repro.models.layers import dense_init
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, num_heads * head_dim, dtype),
        "wk": dense_init(kk, d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(kv, d_model, num_kv_heads * head_dim, dtype),
        "wo": dense_init(ko, num_heads * head_dim, d_model, dtype,
                         scale=1.0 / math.sqrt(num_heads * head_dim)),
    }


def gqa_project_qkv(x, p, num_heads: int, num_kv_heads: int, head_dim: int):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, num_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, S, num_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(B, S, num_kv_heads, head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------
def mla_init(key, d_model: int, num_heads: int, head_dim: int,
             kv_lora_rank: int, rope_head_dim: int, dtype=jnp.float32):
    from repro.models.layers import dense_init
    keys = jax.random.split(key, 6)
    return {
        "w_dkv": dense_init(keys[0], d_model, kv_lora_rank, dtype),
        "w_kr": dense_init(keys[1], d_model, rope_head_dim, dtype),
        "w_uk": (jax.random.normal(keys[2], (kv_lora_rank, num_heads, head_dim))
                 / math.sqrt(kv_lora_rank)).astype(dtype),
        "w_uv": (jax.random.normal(keys[3], (kv_lora_rank, num_heads, head_dim))
                 / math.sqrt(kv_lora_rank)).astype(dtype),
        "wq": dense_init(keys[4], d_model,
                         num_heads * (head_dim + rope_head_dim), dtype),
        "wo": dense_init(keys[5], num_heads * head_dim, d_model, dtype,
                         scale=1.0 / math.sqrt(num_heads * head_dim)),
    }


def mla_attention(x, p, positions, *, num_heads: int, head_dim: int,
                  rope_head_dim: int, rope_theta: float, causal: bool = True):
    """Training/prefill MLA: expand the latent kv and run standard attention
    with Dk = head_dim + rope_head_dim, Dv = head_dim."""
    B, S, _ = x.shape
    ckv = x @ p["w_dkv"]                                       # (B,S,r)
    k_rope = apply_rope_1h(x @ p["w_kr"], positions, rope_theta)  # (B,S,rd)
    k_nope = jnp.einsum("bsr,rhd->bshd", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhd->bshd", ckv, p["w_uv"])
    q = (x @ p["wq"]).reshape(B, S, num_heads, head_dim + rope_head_dim)
    q_nope, q_rope = q[..., :head_dim], q[..., head_dim:]
    from repro.models.layers import apply_rope
    q_rope = apply_rope(q_rope, positions, rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, num_heads, rope_head_dim))], axis=-1)
    out = attend(q, k, v, causal=causal)
    return out.reshape(B, S, num_heads * head_dim) @ p["wo"]


def apply_rope_1h(x, positions, theta):
    """RoPE on a single shared head: x (B,S,D)."""
    from repro.models.layers import apply_rope
    return apply_rope(x, positions, theta)


def mla_decode_absorbed(x, p, ckv_cache, krope_cache, index, *,
                        num_heads: int, head_dim: int, rope_head_dim: int,
                        rope_theta: float):
    """Absorbed-matmul MLA decode: scores/values computed directly in the
    compressed latent space — the cache stores only (ckv, k_rope).

    x: (B, d_model) current-token activations; caches (B, S, r)/(B, S, rd);
    index: scalar position.  Returns (B, d_model), updated caches.
    """
    B = x.shape[0]
    pos = jnp.full((B, 1), index, jnp.int32)
    ckv_new = x @ p["w_dkv"]                                   # (B, r)
    krope_new = apply_rope_1h((x @ p["w_kr"])[:, None, :], pos,
                              rope_theta)[:, 0]                # (B, rd)
    ckv_cache = lax.dynamic_update_slice_in_dim(
        ckv_cache, ckv_new[:, None, :].astype(ckv_cache.dtype), index, axis=1)
    krope_cache = lax.dynamic_update_slice_in_dim(
        krope_cache, krope_new[:, None, :].astype(krope_cache.dtype), index, axis=1)

    q = (x @ p["wq"]).reshape(B, num_heads, head_dim + rope_head_dim)
    q_nope, q_rope = q[..., :head_dim], q[..., head_dim:]
    from repro.models.layers import apply_rope
    q_rope = apply_rope(q_rope[:, None], pos, rope_theta)[:, 0]
    # absorb W_uk into the query: q_lat (B, H, r)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, p["w_uk"])
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, krope_cache,
                      preferred_element_type=jnp.float32))
    s = s / math.sqrt(head_dim + rope_head_dim)
    valid = jnp.arange(ckv_cache.shape[1]) <= index
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w.astype(ckv_cache.dtype), ckv_cache)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, p["w_uv"])           # (B,H,hd)
    out = o.reshape(B, num_heads * head_dim) @ p["wo"]
    return out, ckv_cache, krope_cache
