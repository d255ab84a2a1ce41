"""Property tests for the paper's core algorithms (hypothesis-driven).

System invariants:
  I1  UGA with E=1 equals the central gradient on pooled data (§2.1: the
      one-step case is exactly Eq. (7); unbiasedness base case).
  I2  The HVP-form UGA equals straight autodiff through the keep-trace
      trajectory (implementation equivalence — exact same math).
  I3  Client-parallel (chunk = cohort) and client-sequential (chunk = 1)
      cohorts produce the same aggregate.
  I4  FedProx with mu=0 is exactly FedAvg.
  I5  The cohort core's weighted aggregation (Eq. 14) is permutation-
      invariant and respects weights.
  I6  FedMeta's update moves params along -grad of the meta loss.
  I7  UGA == FedAvg pseudo-gradient direction at lr->0, E=2 (both reduce to
      the sum of microbatch gradients at w_t).
"""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; unit tests still run
    from _hypothesis_stub import given, settings, st

from repro.core.aggregate import chunked_cohort_gradient_flat
from repro.core.client import (fedavg_update, make_client_update, uga_update,
                               uga_update_autodiff)
from repro.core.flat import make_flat_spec, unflatten_tree
from repro.core.meta import meta_update

SETTINGS = dict(max_examples=12, deadline=None)


def cohort_gradient(client_update, w, batch, weights, lr, chunk=None):
    """The streaming cohort core's aggregate as a pytree (chunk defaults to
    the whole cohort).  Returns (G, weighted mean client loss)."""
    spec = make_flat_spec(w)
    chunk = weights.shape[0] if chunk is None else chunk
    G, loss = chunked_cohort_gradient_flat(client_update, w, batch, weights,
                                           lr, None, spec=spec, chunk=chunk)
    return unflatten_tree(spec, G), loss


def quad_loss(w, batch, rng=None):
    pred = jnp.tanh(batch["x"] @ w["w1"]) @ w["w2"] + w["b"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _problem(seed, cohort=3, b=8, d=5, h=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = {"w1": jax.random.normal(ks[0], (d, h)),
         "w2": jax.random.normal(ks[1], (h,)),
         "b": jnp.zeros(())}
    batch = {"x": jax.random.normal(ks[2], (cohort, b, d)),
             "y": jax.random.normal(ks[3], (cohort, b))}
    weights = jnp.asarray(np.random.default_rng(seed).integers(
        1, 20, cohort), jnp.float32)
    return w, batch, weights


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000))
def test_I1_uga_e1_unbiased(seed):
    w, batch, weights = _problem(seed)
    cu = make_client_update("uga", quad_loss, local_steps=1)
    G, _ = cohort_gradient(cu, w, batch, weights, 0.05)
    # central gradient on the weighted pooled distribution
    def pooled(w0):
        per = jax.vmap(lambda bx, by: quad_loss(w0, {"x": bx, "y": by})[0])(
            batch["x"], batch["y"])
        return jnp.sum(per * weights) / jnp.sum(weights)
    central = jax.grad(pooled)(w)
    for a, b in zip(jax.tree.leaves(G), jax.tree.leaves(central)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), steps=st.integers(2, 4),
       epochs=st.integers(1, 2))
def test_I2_hvp_equals_autodiff(seed, steps, epochs):
    w, batch, _ = _problem(seed, cohort=1, b=12)
    bt = jax.tree.map(lambda x: x[0], batch)
    g1, l1 = uga_update(quad_loss, w, bt, 0.1, None,
                        local_steps=steps, local_epochs=epochs)
    g2, l2 = uga_update_autodiff(quad_loss, w, bt, 0.1, None,
                                 local_steps=steps, local_epochs=epochs)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), algo=st.sampled_from(["uga", "fedavg"]))
def test_I3_vmap_equals_scan(seed, algo):
    w, batch, weights = _problem(seed, cohort=4)
    cu = make_client_update(algo, quad_loss, local_steps=2)
    Gv, lv = cohort_gradient(cu, w, batch, weights, 0.05, chunk=4)
    Gs, ls = cohort_gradient(cu, w, batch, weights, 0.05, chunk=1)
    np.testing.assert_allclose(lv, ls, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(Gv), jax.tree.leaves(Gs)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000))
def test_I4_fedprox_mu0_is_fedavg(seed):
    w, batch, _ = _problem(seed, cohort=1)
    bt = jax.tree.map(lambda x: x[0], batch)
    fa = make_client_update("fedavg", quad_loss, local_steps=2)
    fp = make_client_update("fedprox", quad_loss, local_steps=2, prox_mu=0.0)
    ga, _ = fa(w, bt, 0.1, None)
    gp, _ = fp(w, bt, 0.1, None)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gp)):
        np.testing.assert_allclose(a, b, atol=0, rtol=0)


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000))
def test_I5_weighted_mean_properties(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)
    wgt = jnp.asarray(rng.integers(1, 9, 5), jnp.float32)
    w0 = {"x": jnp.zeros((4, 3), jnp.float32)}

    def returns_batch(w, batch, lr, r):          # client k's "gradient"
        return batch, jnp.float32(0.0)           # is its batch x[k]

    def weighted_mean(xs, weights):
        return cohort_gradient(returns_batch, w0, {"x": xs}, weights,
                               0.0)[0]["x"]

    m = weighted_mean(x, wgt)
    # permutation invariance
    perm = rng.permutation(5)
    m2 = weighted_mean(x[perm], wgt[perm])
    np.testing.assert_allclose(m, m2, atol=1e-6)
    # scale invariance of weights
    m3 = weighted_mean(x, wgt * 7.0)
    np.testing.assert_allclose(m, m3, atol=1e-6)
    # equal weights == plain mean
    m4 = weighted_mean(x, jnp.ones(5))
    np.testing.assert_allclose(m4, jnp.mean(x, 0), atol=1e-6)


def test_I6_meta_update_descends():
    w, batch, _ = _problem(0, cohort=1)
    bt = jax.tree.map(lambda x: x[0], batch)
    l0 = quad_loss(w, bt)[0]
    w2, meta_l = meta_update(quad_loss, w, bt, 0.05)
    l1 = quad_loss(w2, bt)[0]
    assert float(l1) < float(l0)
    np.testing.assert_allclose(meta_l, l0, rtol=1e-6)


def test_I7_uga_fedavg_agree_at_small_lr():
    w, batch, _ = _problem(3, cohort=1, b=8)
    bt = jax.tree.map(lambda x: x[0], batch)
    # lr small enough for the first-order limit, large enough that the
    # fedavg pseudo-gradient (a parameter DIFFERENCE) isn't fp32-cancelled
    lr = 1e-3
    g_uga, _ = uga_update(quad_loss, w, bt, lr, None, local_steps=2)
    g_fa, _ = fedavg_update(quad_loss, w, bt, lr, None, local_steps=2)
    # fedavg pseudo-grad ~ lr * (g_mb1 + g_mb2) at lr->0; UGA's gradient
    # evaluation over the full batch ~ (g_mb1 + g_mb2)/2 — so
    # g_uga == g_fa / (2*lr) in the limit.
    for a, b in zip(jax.tree.leaves(g_uga), jax.tree.leaves(g_fa)):
        np.testing.assert_allclose(a, b / (2 * lr), rtol=6e-2, atol=6e-3)


def test_gradient_bias_is_real_and_uga_removes_it():
    """§2.1 demonstrated: with heterogeneous clients and E>1, the FedAvg
    pseudo-gradient direction diverges from the true gradient direction;
    UGA's aggregate IS the true gradient of the composed objective."""
    w, batch, weights = _problem(7, cohort=4, b=8)
    lr = 0.2  # large local lr => visible bias

    def cos(a, b):
        fa = jnp.concatenate([x.ravel() for x in jax.tree.leaves(a)])
        fb = jnp.concatenate([x.ravel() for x in jax.tree.leaves(b)])
        return float(fa @ fb / (jnp.linalg.norm(fa) * jnp.linalg.norm(fb)))

    # the UGA objective: mean_k L(h_k(w); D_k) — its true gradient
    def uga_objective(w0):
        def per_client(bx, by):
            bt = {"x": bx, "y": by}
            mb = jax.tree.map(lambda x: x[:4], bt)
            g = jax.grad(lambda ww: quad_loss(ww, mb)[0])(w0)
            w1 = jax.tree.map(lambda p, gi: p - lr * gi, w0, g)
            return quad_loss(w1, bt)[0]
        per = jax.vmap(per_client)(batch["x"], batch["y"])
        return jnp.sum(per * weights) / jnp.sum(weights)

    true_g = jax.grad(uga_objective)(w)
    cu = make_client_update("uga", quad_loss, local_steps=2)
    G_uga, _ = cohort_gradient(cu, w, batch, weights, lr)
    fa = make_client_update("fedavg", quad_loss, local_steps=2)
    G_fa, _ = cohort_gradient(fa, w, batch, weights, lr)

    assert cos(G_uga, true_g) > 0.9999           # unbiased
    assert cos(G_fa, true_g) < cos(G_uga, true_g)  # fedavg is biased
