"""The paper's FEMNIST CNN for the benchmark: the program's model built from
``paper-femnist-cnn.json``, weights and writers' images from the seed, and
the round's required FLOPs counted from shapes.

Forward FLOPs of one image, F: each convolution 2 * k * k * C_in * C_out per
output pixel (SAME padding, so the input's size), each dense layer 2 per
weight.  A round needs, per client, ``n_kt = local_steps * local_epochs - 1``
keep-trace steps on a microbatch (9 F each: a gradient and the
Hessian-vector product of its backward) and one gradient of the whole
client batch (3 F), and once a round the meta step's gradient (3 F).
"""
from __future__ import annotations

import math


def build_model(cfg: dict):
    from repro.configs.paper_models import CNNConfig
    from repro.models.model import build_paper_cnn
    return build_paper_cnn(CNNConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        conv_channels=tuple(cfg["conv_channels"]),
        conv_kernel=cfg["conv_kernel"], pool=cfg["pool"],
        pool_stride=cfg["pool_stride"], fc=tuple(cfg["fc"]),
        dropout=cfg["dropout"]))


def init_params(cfg: dict, abstract, key):
    """He-normal convolutions and dense layers (the last one scaled by 0.1,
    so the first loss sits near ln 62), zero biases."""
    import jax
    import jax.numpy as jnp
    names = sorted(abstract)
    keys = dict(zip(names, jax.random.split(key, len(names))))
    last = f"fc{len(cfg['fc'])}_w"
    out = {}
    for n in names:
        a = abstract[n]
        if n.endswith("_b"):
            out[n] = jnp.zeros(a.shape, a.dtype)
            continue
        fan_in = math.prod(a.shape[:-1])
        s = math.sqrt(2.0 / fan_in) * (0.1 if n == last else 1.0)
        out[n] = (s * jax.random.normal(keys[n], a.shape)).astype(a.dtype)
    return out


def make_data(cfg: dict, traffic: dict, seed: int):
    """``population`` writers with LEAF FEMNIST's spread of sample counts,
    their images made on the device; the meta set is 1% of all samples."""
    import numpy as np
    from lib.gen import synthetic_writer_images, writer_sizes
    rng = np.random.default_rng(seed)
    sizes = writer_sizes(rng, int(traffic["population"]),
                         float(traffic["samples_mean"]),
                         float(traffic["samples_spread"]),
                         int(traffic["samples_min"]))
    x, y, w = synthetic_writer_images(
        seed, sizes, image_size=cfg["image_size"],
        channels=cfg["in_channels"], num_classes=cfg["num_classes"])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    parts = [np.arange(s, s + n) for s, n in zip(starts, sizes)]
    meta = rng.choice(x.shape[0], max(x.shape[0] // 100,
                                      4 * int(traffic["meta_batch"])),
                      replace=False)
    return {"x": x, "y": y}, parts, meta


def forward_flops(cfg: dict) -> float:
    s, k = cfg["image_size"], cfg["conv_kernel"]
    c_in, total = cfg["in_channels"], 0.0
    for c in cfg["conv_channels"]:
        total += 2.0 * s * s * k * k * c_in * c
        s = (s - cfg["pool"]) // cfg["pool_stride"] + 1
        c_in = c
    dims = [s * s * c_in] + list(cfg["fc"]) + [cfg["num_classes"]]
    total += sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return total


def round_flops(cfg: dict, traffic: dict) -> float:
    steps = int(traffic["local_steps"])
    n_kt = steps * int(traffic.get("local_epochs", 1)) - 1
    b = int(traffic["client_batch"])
    f = forward_flops(cfg)
    client = n_kt * 9 * f * (b // steps) + 3 * f * b
    meta = 3 * f * int(traffic["meta_batch"]) \
        if traffic.get("meta", True) else 0.0
    return int(traffic["cohort"]) * client + meta
