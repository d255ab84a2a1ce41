"""Plain reference of the FedAvg CNN's loss on FEMNIST, in ``jax.numpy``.

Two 5x5 convolutions (SAME padding, bias, ReLU, 2x2 max pooling of stride
2), a ReLU fully connected layer and the 62-way output layer; mean softmax
cross-entropy.  No dropout.  The arithmetic follows the dtype of the
parameters it is given.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def loss(cfg: dict, params, batch):
    x = batch["x"].astype(params["conv1_w"].dtype)
    for i in (1, 2):
        x = lax.conv_general_dilated(
            x, params[f"conv{i}_w"], window_strides=(1, 1),
            padding=cfg["conv_padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params[f"conv{i}_b"]
        x = jax.nn.relu(x)
        p, s = cfg["pool"], cfg["pool_stride"]
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, p, p, 1),
                              (1, s, s, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    n_fc = len(cfg["fc"]) + 1
    for i in range(n_fc):
        x = x @ params[f"fc{i}_w"] + params[f"fc{i}_b"]
        if i < n_fc - 1:
            x = jax.nn.relu(x)
    logits = x.astype(jnp.float32)
    y = batch["y"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)
