"""Plain reference of a synchronous federated round: UGA clients, the
weighted (Eq. 14) aggregate, the server optimizer, the post-aggregation
meta step.  Imports nothing of the program under test.

Per round r, with client learning rate ``client_lr * lr_decay**r``:

* each client splits its batch into ``local_steps`` microbatches, takes
  ``local_steps * local_epochs - 1`` SGD steps cycling through them, and
  evaluates the loss of the whole batch at the end point; its gradient is
  that loss differentiated through the SGD steps with respect to the
  round's starting parameters (autodiff through the trajectory, the
  paper's Algorithm 1 line for line);
* with the int8 uplink each client's gradient is quantized over all of its
  elements with one scale ``max|g| / 127`` and rounded to nearest before
  the server adds it;
* G = sum_k (n_k / sum n) g_k, the client loss likewise;
* the server takes one SGD or Adam (b1 0.9, b2 0.99, eps 1e-8) step on G
  with ``server_lr``;
* the meta step takes one gradient step on the meta batch with
  ``meta_lr * lr_decay**r``.

Every matmul runs under ``jax.default_matmul_precision`` of the caller's
choice; the parameters' dtype sets the arithmetic.

Only what a client's step works on stays on the device: the parameters,
the aggregate and the client's gradient.  Adam's moments wait on the host
between server steps, and what is kept for the caller (the first
aggregate, the parameters after the first round) goes to the host as it is
made, so that the client's second-order gradient at full width has the
rest of the chip's memory.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

B1, B2, EPS = 0.9, 0.99, 1e-8


def _client(loss: Callable, steps: int, n_kt: int):
    def objective(w0, batch, lr):
        mbs = jax.tree.map(
            lambda x: x.reshape((steps, x.shape[0] // steps) + x.shape[1:]),
            batch)

        def step(w, i):
            mb = jax.tree.map(lambda x: x[i % steps], mbs)
            g = jax.grad(loss)(w, mb)
            return jax.tree.map(lambda p, gi: (p - lr * gi).astype(p.dtype),
                                w, g), None

        w = w0
        if n_kt:
            w, _ = lax.scan(jax.checkpoint(step), w0, jnp.arange(n_kt))
        return loss(w, batch)

    return jax.value_and_grad(objective)


def _quantize_i8(g):
    leaves = jax.tree.leaves(g)
    amax = jnp.max(jnp.stack([jnp.max(jnp.abs(x.astype(jnp.float32)))
                              for x in leaves]))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jax.tree.map(
        lambda x: jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                           -127.0, 127.0) * scale, g)


class Reference:
    """The reference's jitted pieces for one loss, traffic and matmul
    precision, built once and reused across seeds."""

    def __init__(self, loss: Callable, hp: Dict, precision: str):
        self.hp, self.precision = hp, precision
        steps = int(hp["local_steps"])
        n_kt = steps * int(hp.get("local_epochs", 1)) - 1
        self.client = jax.jit(_client(loss, steps, n_kt))
        self.meta_grad = jax.jit(jax.value_and_grad(loss))
        self.quant = jax.jit(_quantize_i8)
        self.fma = jax.jit(lambda acc, g, w: jax.tree.map(
            lambda a, x: a + w * x.astype(jnp.float32), acc, g))
        self.server = jax.jit(self._server)
        self.meta_step = jax.jit(lambda p, g, lr: jax.tree.map(
            lambda a, gi: (a.astype(jnp.float32)
                           - lr * gi.astype(jnp.float32)).astype(a.dtype),
            p, g))

    def _server(self, p, G, m, v, t):
        lr = self.hp["server_lr"]
        if self.hp["server_opt"] == "sgd":
            return jax.tree.map(lambda a, g: (a.astype(jnp.float32) - lr * g
                                              ).astype(a.dtype), p, G), m, v
        m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, G)
        v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, G)
        bc1 = 1.0 / (1.0 - B1 ** t)
        bc2 = 1.0 / (1.0 - B2 ** t)
        p = jax.tree.map(
            lambda a, mm, vv: (a.astype(jnp.float32) - lr * (mm * bc1)
                               / (jnp.sqrt(vv * bc2) + EPS)).astype(a.dtype),
            p, m, v)
        return p, m, v

    def run(self, params0, rounds: Sequence[Dict], *, dtype=jnp.float32,
            keep_half: bool = False) -> Dict:
        """Run ``len(rounds)`` rounds from ``params0`` (best on the host, so
        that the device holds only the copy the rounds update).  Each
        round's inputs are ``{"cohort_batch", "client_weights",
        "meta_batch"}`` as numpy arrays.

        Returns per-round client and meta losses, the first round's
        aggregate ``G0`` as the server optimizer receives it and the
        parameters after the first round (trees on the host), and the
        parameters after the last round (on the device).

        ``keep_half`` plants a fault for the benchmark's own checks: only
        the first half of each round's clients is run, and the mean is
        taken over them."""
        hp = self.hp
        with jax.default_matmul_precision(self.precision):
            p = jax.tree.map(lambda x: jnp.asarray(x, dtype), params0)
            zeros = lambda: jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p)
            host_zeros = lambda: jax.tree.map(
                lambda x: np.zeros(x.shape, np.float32), p)
            m, v = ((host_zeros(), host_zeros())
                    if hp["server_opt"] == "adam" else (None, None))
            out: Dict = {"client_loss": [], "meta_loss": []}
            for r, rin in enumerate(rounds):
                decay = float(hp.get("lr_decay", 1.0)) ** r
                lr_c = jnp.float32(hp["client_lr"] * decay)
                w = jnp.asarray(rin["client_weights"], jnp.float32)
                if keep_half:
                    w = w[:max(w.shape[0] // 2, 1)]
                wn = w / jnp.maximum(jnp.sum(w), 1e-30)
                G = zeros()
                closs = jnp.float32(0.0)
                cb = rin["cohort_batch"]
                for k in range(w.shape[0]):
                    batch = {n: jnp.asarray(a[k]) for n, a in cb.items()}
                    lk, gk = self.client(p, batch, lr_c)
                    if hp.get("codec", "none") == "int8":
                        gk = self.quant(gk)
                    G = self.fma(G, gk, wn[k])
                    closs = closs + wn[k] * lk.astype(jnp.float32)
                if r == 0:
                    out["G0"] = jax.device_get(G)
                if m is None:
                    p, _, _ = self.server(p, G, None, None, jnp.float32(r + 1))
                else:
                    p, m, v = self.server(p, G, jax.device_put(m),
                                          jax.device_put(v),
                                          jnp.float32(r + 1))
                    m, v = jax.device_get((m, v))
                del G
                if hp.get("meta", True):
                    mb = {n: jnp.asarray(a)
                          for n, a in rin["meta_batch"].items()}
                    ml, mg = self.meta_grad(p, mb)
                    p = self.meta_step(p, mg,
                                       jnp.float32(hp["meta_lr"] * decay))
                    out["meta_loss"].append(float(ml))
                out["client_loss"].append(float(closs))
                if r == 0:
                    out["params1"] = jax.device_get(p)
            out["params"] = p
            return out
