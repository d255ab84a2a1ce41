"""Plain reference of SmolLM-360M's next-token loss, in ``jax.numpy``.

Llama block: RMSNorm, rotary grouped-query attention (15 query heads over 5
key/value heads), SiLU-gated MLP, tied input embedding and output head,
mean cross-entropy over every next-token position.  No kernels, no
chunking of the vocabulary, no cache; layers run under a ``lax.scan`` with
``jax.checkpoint`` so that second-order gradients at the timed sizes fit.

Parameters arrive in the trainer's layout: ``embed`` (V, d), ``final_norm``
(d,), and ``blocks[0]`` with every layer's leaves stacked on a leading axis.
Rotary pairs are interleaved (x[2i], x[2i+1]), which is the published
half-split layout under a fixed permutation of the q/k projection columns.
The arithmetic follows the dtype of the parameters it is given.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def loss(cfg: dict, params, batch):
    """Mean next-token cross-entropy of ``batch["tokens"]`` (B, S + 1)."""
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = d // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    tokens = batch["tokens"]
    inp, lab = tokens[:, :-1], tokens[:, 1:]
    B, S = inp.shape
    h = params["embed"][inp]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(h, lp):
        at, mlp = lp["attn"], lp["mlp"]
        x = _rms(h, lp["norm1"], eps)
        q = _rope((x @ at["wq"]).reshape(B, S, H, D), theta)
        k = _rope((x @ at["wk"]).reshape(B, S, KV, D), theta)
        v = (x @ at["wv"]).reshape(B, S, KV, D)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(D), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * D)
        h = h + a @ at["wo"]
        x2 = _rms(h, lp["norm2"], eps)
        h = h + (jax.nn.silu(x2 @ mlp["w_gate"]) * (x2 @ mlp["w_up"])) \
            @ mlp["w_down"]
        return h, None

    h, _ = lax.scan(jax.checkpoint(layer), h, params["blocks"][0])
    h = _rms(h, params["final_norm"], eps)
    logits = (h @ params["embed"].T).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, lab[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)
