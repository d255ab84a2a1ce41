"""Plain tree-map references for the cohort stage, independent of the
streaming cohort core the system runs (``repro.core.aggregate``): every
client's local update vmapped in pytree form, then the Eq. (14) weighted
mean as one reduction over the cohort axis.  Tests compare the system's
rounds and aggregates against these to fp32 reassociation.
"""
import jax
import jax.numpy as jnp


def weighted_mean(trees, weights, dtype=jnp.float32):
    """trees: pytree with leading cohort axis; weights: (cohort,) n_k."""
    w = weights.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)

    def agg(x):
        wx = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.float32)
        return jnp.sum(x.astype(jnp.float32) * wx, axis=0).astype(dtype)

    return jax.tree.map(agg, trees)


def cohort_gradient(client_update, w_t, cohort_batch, client_weights, lr,
                    rng, *, agg_dtype=jnp.float32, aggregate=True):
    """Run ``client_update`` for every client (vmapped, with the per-client
    rng streams the round splits: ``jax.random.split(rng, cohort)``) and
    aggregate Eq. (14).  Returns (G, client loss weighted by
    ``client_weights``); ``aggregate=False`` returns the stacked per-client
    gradients in place of G."""
    cohort = client_weights.shape[0]
    rngs = (jax.random.split(rng, cohort) if rng is not None
            else jnp.zeros((cohort, 2), jnp.uint32))

    def one(batch, r):
        return client_update(w_t, batch, lr, r if rng is not None else None)

    g_all, losses = jax.vmap(one)(cohort_batch, rngs)
    w = client_weights.astype(jnp.float32)
    mean_loss = jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1e-30)
    if not aggregate:
        return g_all, mean_loss
    return weighted_mean(g_all, client_weights, agg_dtype), mean_loss
