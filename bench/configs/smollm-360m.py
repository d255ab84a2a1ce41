"""SmolLM-360M for the benchmark: the program's model built from
``smollm-360m.json``, weights and client token streams from the seed, and
the round's required FLOPs counted from shapes.

Required FLOPs of one round, with F(n, S) the forward FLOPs of n sequences
of S tokens (2 per weight of every matmul, tied head included, plus the
causal attention's QK^T and PV, 4 * H * D * S (S + 1) / 2 per layer and
sequence):

* per client, ``n_kt = local_steps * local_epochs - 1`` keep-trace steps on
  a microbatch of ``client_batch / local_steps`` sequences: each is a
  gradient (3 F) whose backward in the client's own gradient is a
  Hessian-vector product (6 F), so 9 F(microbatch) a step;
* per client, the gradient of the whole client batch at the end point:
  3 F(client_batch);
* once a round, the meta step's gradient: 3 F(meta_batch).

Nothing that rematerialisation recomputes is counted.
"""
from __future__ import annotations

import math


def arch(cfg: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["param_dtype"],
        source=cfg["source"])


def build_model(cfg: dict):
    import jax.numpy as jnp
    from repro.models.model import build_model as build
    return build(arch(cfg), dtype=jnp.dtype(cfg["param_dtype"]),
                 loss_chunk=256)


def init_params(cfg: dict, abstract, key):
    """Weights from the seed in the served dtype: norms 1, the embedding
    N(0, 0.02^2), every projection N(0, 1 / fan_in)."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, a), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            out.append(jnp.ones(a.shape, a.dtype))
        elif "embed" in name:
            out.append((0.02 * jax.random.normal(k, a.shape)).astype(a.dtype))
        else:
            out.append((jax.random.normal(k, a.shape)
                        / math.sqrt(a.shape[-2])).astype(a.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def make_data(cfg: dict, traffic: dict, seed: int):
    """Client token streams: ``examples_per_client`` sequences of
    ``seq + 1`` tokens for each of ``population`` clients, and a meta set
    drawn from all of them."""
    import numpy as np
    from lib.gen import synthetic_tokens
    rng = np.random.default_rng(seed)
    pop = int(traffic["population"])
    n = pop * int(traffic["examples_per_client"])
    toks, client = synthetic_tokens(rng, n=n, seq_len=traffic["seq"] + 1,
                                    vocab=cfg["vocab_size"], num_clients=pop)
    parts = [np.flatnonzero(client == c) for c in range(pop)]
    n_meta = max(4 * int(traffic["meta_batch"]), n // 100)
    meta = rng.choice(n, n_meta, replace=False)
    return {"tokens": toks}, parts, meta


def forward_flops(cfg: dict, n_seq: int, seq: int) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, L, V = d // H, cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = d * H * D + 2 * d * KV * D + H * D * d + 3 * d * f
    mm = 2.0 * (L * per_layer + d * V) * seq
    attn = L * 4.0 * H * D * seq * (seq + 1) / 2
    return n_seq * (mm + attn)


def round_flops(cfg: dict, traffic: dict) -> float:
    steps = int(traffic["local_steps"])
    n_kt = steps * int(traffic.get("local_epochs", 1)) - 1
    b, S = int(traffic["client_batch"]), int(traffic["seq"])
    client = (n_kt * 9 * forward_flops(cfg, b // steps, S)
              + 3 * forward_flops(cfg, b, S))
    meta = 3 * forward_flops(cfg, int(traffic["meta_batch"]), S) \
        if traffic.get("meta", True) else 0.0
    return int(traffic["cohort"]) * client + meta
