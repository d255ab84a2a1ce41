"""The benchmark's own traffic generators: client data made from the seed.

``synthetic_tokens`` is a copy of the program's token generator (per-client
zipfian unigram streams, each client's distribution rolled by its own
shift).  ``synthetic_writer_images`` follows the program's image generator
(class prototypes under a per-writer gain and bias field, plus noise), made
on the device in equal blocks of writers so that set-up stays short and the
device never holds more than one block.  Every seed gets the same sizes in
another order, so the work of a round does not depend on the seed.
"""
from __future__ import annotations

import numpy as np


def synthetic_tokens(rng: np.random.Generator, *, n: int, seq_len: int,
                     vocab: int, num_clients: int):
    """(tokens (n, seq_len) int32, client id (n,) int32).  Each client gets
    ``n // num_clients`` sequences (the rest go to the first clients)."""
    base = 1.0 / (1.0 + np.arange(vocab)) ** 1.1
    client = np.arange(n, dtype=np.int32) % num_clients
    rng.shuffle(client)
    shift = rng.integers(0, vocab, num_clients)
    toks = np.zeros((n, seq_len), np.int32)
    for c in range(num_clients):
        idx = np.flatnonzero(client == c)
        p = np.roll(base, shift[c])
        p = p / p.sum()
        toks[idx] = rng.choice(vocab, size=(idx.size, seq_len), p=p)
    return toks, client


def writer_sizes(rng: np.random.Generator, writers: int, mean: float,
                 spread: float, low: int) -> np.ndarray:
    """Per-writer sample counts: the same multiset for every seed (evenly
    spaced quantiles of a normal around ``mean``), in a seeded order."""
    from statistics import NormalDist
    q = (np.arange(writers) + 0.5) / writers
    nd = NormalDist(mean, spread)
    sizes = np.array([max(low, int(round(nd.inv_cdf(float(x))))) for x in q],
                     np.int64)
    return rng.permutation(sizes)


def synthetic_writer_images(seed: int, sizes: np.ndarray, *, image_size: int,
                            channels: int, num_classes: int,
                            noise: float = 0.35, style_strength: float = 0.5,
                            block_writers: int = 64):
    """Images (N, H, W, C) float32, labels (N,) int32 and writer ids (N,)
    int32 on the host, for writers with ``sizes[w]`` samples each.

    Made on the device: one jitted program per block of ``block_writers``
    writers (every block has the same shapes, so it compiles once)."""
    import jax
    import jax.numpy as jnp

    writers = int(sizes.size)
    per = int(sizes.max())
    n_blocks = -(-writers // block_writers)
    hw = (image_size, image_size, channels)

    @jax.jit
    def block(key, protos):
        kg, kb, ky, kn = jax.random.split(key, 4)
        gains = 1.0 + style_strength * jax.random.normal(
            kg, (block_writers, image_size, 1, channels))
        biases = style_strength * jax.random.normal(
            kb, (block_writers, 1, image_size, channels))
        y = jax.random.randint(ky, (block_writers, per), 0, num_classes)
        x = (protos[y] * gains[:, None] + biases[:, None]
             + noise * jax.random.normal(kn, (block_writers, per) + hw))
        return x.astype(jnp.float32), y.astype(jnp.int32)

    key = jax.random.key(np.uint32(seed % (1 << 32)))
    kp, key = jax.random.split(key)
    protos = jax.random.normal(kp, (num_classes,) + hw)
    total = int(sizes.sum())
    xs = np.empty((total,) + hw, np.float32)
    ys = np.empty((total,), np.int32)
    ws = np.repeat(np.arange(writers, dtype=np.int32), sizes)
    off = 0
    for b in range(n_blocks):
        xb, yb = jax.device_get(block(jax.random.fold_in(key, b), protos))
        for j in range(block_writers):
            w = b * block_writers + j
            if w >= writers:
                break
            n = int(sizes[w])
            xs[off:off + n] = xb[j, :n]
            ys[off:off + n] = yb[j, :n]
            off += n
    return xs, ys, ws
