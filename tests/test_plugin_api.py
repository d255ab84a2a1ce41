"""Plugin-API redesign (algorithm/executor/engine registries + facade):

  * equivalence MATRIX: the registry-built round matches a plain tree-map
    reference round (``make_reference_round`` below, over the reference
    cohort stage in ``_reference.py``: vmapped tree clients, the Eq. (14)
    weighted mean, ``server_opt``, and the meta step — post, or
    through_aggregation by XLA autodiff) to fp32 reassociation, across
    {legacy, fused} x {vmap, scan} x {post, through_aggregation} x
    {sgd, adam}, including rounds_per_call > 1;
  * a toy ClientAlgorithm and a toy ServerEngine land purely through
    ``register_algorithm`` / ``register_engine`` (no core/round.py edits)
    and run a round end to end;
  * fednova (the shipped registry-only algorithm): tau-normalized deltas,
    == fedavg exactly when the server step size equals tau;
  * partial participation: ``fed.participation < 1`` == manually zeroing
    the same clients' weights (the mask folds out of the round rng, so
    participation=1 keeps historical rng streams bit-exactly);
  * FederatedTrainer: the deduplicated driver reproduces the legacy
    ``k==1`` loop's history bit-exactly, and save/restore mid-run
    continues identically to never stopping;
  * back-compat import surface + actionable ``sample_round`` cohort error.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import (FederatedTrainer, RoundFnCache, available_algorithms,
                        init_server_state, make_client_update,
                        make_federated_round, meta_update,
                        participation_mask, register_algorithm,
                        register_engine, server_opt, stack_round_inputs)
from repro.core import flat as F
from repro.core.client import fedavg_update
from repro.core.engines import ServerEngine, tree_global_norm
from repro.data.pipeline import FederatedData
from repro.models.model import Model

from _reference import cohort_gradient, weighted_mean


def make_mlp_model(d=10, h=16, classes=4):
    def init(k):
        k1, k2 = jax.random.split(k)
        return {"w1": jax.random.normal(k1, (d, h)) * 0.3,
                "w2": jax.random.normal(k2, (h, classes)) * 0.3}

    def loss(w, batch, rng=None):
        logits = jnp.tanh(batch["x"] @ w["w1"]) @ w["w2"]
        l = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), batch["y"][:, None], 1))
        return l, {}

    return Model(name="mlp", init=init, loss=loss)


def sample_batch(rng, cohort, b, d=10, classes=4):
    return {"x": jnp.asarray(rng.normal(0, 1, (cohort, b, d)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, classes, (cohort, b)),
                             jnp.int32)}


def _round_inputs(seed=0, cohort=4, b=16):
    rng = np.random.default_rng(seed)
    batch = sample_batch(rng, cohort, b)
    meta = {"x": jnp.asarray(rng.normal(0, 1, (8, 10)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 4, 8), jnp.int32)}
    wts = jnp.asarray(rng.uniform(1.0, 5.0, cohort), jnp.float32)
    return batch, meta, wts


def tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# the plain tree-map reference round
# ---------------------------------------------------------------------------
def _ref_resolve_server_lr(fed):
    if fed.algorithm == "uga" or fed.server_opt != "sgd":
        return fed.server_lr
    return 1.0


def assert_close(got, want, tol=1e-5):
    """Per-leaf max error <= tol * the leaf's scale: the system and the
    reference add the cohort in different orders, so they agree to fp32
    reassociation, not to the bit."""
    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert a.shape == b.shape
        scale = max(float(np.max(np.abs(b))), 1e-8)
        err = float(np.max(np.abs(a - b))) / scale
        assert err <= tol, (a.shape, err)


def make_reference_round(model, fed):
    """One round in plain tree form, written apart from the registries:
    vmapped tree clients (``_reference.cohort_gradient``), the Eq. (14)
    weighted mean, clip, ``server_opt.apply``, then the meta step — Eq. (20)
    for ``post``, XLA autodiff of the D_meta loss through that server step
    for ``through_aggregation``.  A fused config's flat opt state is
    unflattened on entry and flattened on exit, so the state trees line up
    with the registry round's."""
    client_update = make_client_update(
        fed.algorithm, model.loss, local_steps=fed.local_steps,
        local_epochs=fed.local_epochs, prox_mu=fed.prox_mu,
        remat=fed.remat_local_steps)
    agg_dtype = jnp.dtype(fed.grad_agg_dtype)
    server_lr = _ref_resolve_server_lr(fed)
    through_agg = fed.meta and fed.meta_mode == "through_aggregation"
    slots = ("m", "v")

    def server_step(params, G, opt_state, lr):
        if fed.clip_norm > 0:
            gn = tree_global_norm(G)
            scale = jnp.minimum(1.0, fed.clip_norm / jnp.maximum(gn, 1e-9))
            G = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                        ).astype(g.dtype), G)
        new_params, opt_state = server_opt.apply(
            fed.server_opt, opt_state, params, G, lr,
            momentum=fed.server_momentum)
        return new_params, opt_state, tree_global_norm(G)

    def one_round(state, cohort_batch, meta_batch, client_weights, rng):
        params = state["params"]
        r = state["round"].astype(jnp.float32)
        lr_c = fed.client_lr * (fed.lr_decay ** r)
        rng_c, rng_m = jax.random.split(rng)
        opt_state = state["opt"]
        if fed.fused_update:
            spec = F.make_flat_spec(params)
            opt_state = {k: (F.unflatten_tree(spec, v, dtype=jnp.float32)
                             if k in slots else v)
                         for k, v in opt_state.items()}

        if through_agg:
            g_all, client_loss = cohort_gradient(
                client_update, params, cohort_batch, client_weights, lr_c,
                rng_c, aggregate=False)
            ctrl = state["ctrl"]

            def objective(w_logits, log_lr):
                G = weighted_mean(g_all, client_weights * jnp.exp(w_logits),
                                  agg_dtype)
                new_p, new_opt, gn = server_step(params, G, opt_state,
                                                 jnp.exp(log_lr))
                l, _ = model.loss(new_p, meta_batch, rng_m)
                return l, (new_p, new_opt, gn)

            (meta_loss, (new_params, opt_state, gn)), (d_wl, d_llr) = \
                jax.value_and_grad(objective, argnums=(0, 1), has_aux=True)(
                    ctrl["w_logits"], ctrl["log_lr"])
            new_ctrl = {"w_logits": ctrl["w_logits"] - fed.ctrl_lr * d_wl,
                        "log_lr": ctrl["log_lr"] - fed.ctrl_lr * d_llr}
            metrics = {"client_loss": client_loss, "grad_norm": gn,
                       "meta_loss": meta_loss,
                       "ctrl_w_gnorm": jnp.sqrt(jnp.sum(d_wl * d_wl)),
                       "ctrl_lr_grad": d_llr,
                       "server_lr_eff": jnp.exp(ctrl["log_lr"])}
        else:
            G, client_loss = cohort_gradient(
                client_update, params, cohort_batch, client_weights, lr_c,
                rng_c, agg_dtype=agg_dtype)
            new_params, opt_state, gn = server_step(params, G, opt_state,
                                                    server_lr)
            metrics = {"client_loss": client_loss, "grad_norm": gn}
            if fed.meta:
                lr_m = fed.meta_lr * (fed.lr_decay ** r)
                new_params, metrics["meta_loss"] = meta_update(
                    model.loss, new_params, meta_batch, lr_m, rng_m)

        if fed.fused_update:
            opt_state = {k: (tuple(F.flatten_tree(spec, v))
                             if k in slots else v)
                         for k, v in opt_state.items()}
        new_state = {"params": new_params, "opt": opt_state,
                     "round": state["round"] + 1}
        if through_agg:
            new_state["ctrl"] = new_ctrl
        return new_state, metrics

    return one_round


MATRIX = [(fused, strat, mode, opt)
          for fused in (False, True)
          for strat in ("vmap", "scan")
          for mode in ("post", "through_aggregation")
          for opt in ("sgd", "adam")
          if not (mode == "through_aggregation" and not fused)]


@pytest.mark.parametrize("fused,strat,mode,opt", MATRIX)
def test_equivalence_matrix_bit_identical(key, fused, strat, mode, opt):
    """Registry-built round == the plain tree-map reference round to fp32
    reassociation: params, opt state, ctrl and every metric, over two
    chained rounds (so round-1 outputs feed round-2 inputs on both
    sides)."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt=opt, clip_norm=1.0, lr_decay=0.9,
                    cohort_strategy=strat, fused_update=fused,
                    meta_mode=mode)
    batch, meta, wts = _round_inputs()
    new_rf = jax.jit(make_federated_round(model, fed))
    ref_rf = jax.jit(make_reference_round(model, fed))
    st_new = init_server_state(model, fed, key)
    st_ref = jax.tree.map(jnp.copy, st_new)
    for r in range(2):
        st_new, m_new = new_rf(st_new, batch, meta, wts,
                               jax.random.fold_in(key, r))
        st_ref, m_ref = ref_rf(st_ref, batch, meta, wts,
                               jax.random.fold_in(key, r))
    assert jax.tree.structure(st_new) == jax.tree.structure(st_ref)
    assert_close(st_new, st_ref)
    assert sorted(m_new) == sorted(m_ref)
    for name in m_new:
        assert_close(m_new[name], m_ref[name])


@pytest.mark.parametrize("fused,strat,mode,opt",
                         [(True, "vmap", "through_aggregation", "adam"),
                          (True, "scan", "post", "sgd"),
                          (False, "vmap", "post", "adam")])
def test_equivalence_matrix_rounds_per_call(key, fused, strat, mode, opt):
    """Same gate under the K-chunked driver: new rounds_per_call=2 round ==
    the reference body wrapped in the same lax.scan."""
    from jax import lax
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt=opt, clip_norm=1.0, lr_decay=0.9,
                    cohort_strategy=strat, fused_update=fused,
                    meta_mode=mode)
    Kr = 2
    batch, meta, wts = _round_inputs()
    stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * Kr), t)
    rngs = jnp.stack([jax.random.fold_in(key, r) for r in range(Kr)])

    new_rf = jax.jit(make_federated_round(model, fed, rounds_per_call=Kr))
    ref_body = make_reference_round(model, fed)

    def ref_rf(state, cbs, mbs, ws, rs):
        return lax.scan(lambda st, xs: ref_body(st, *xs), state,
                        (cbs, mbs, ws, rs))

    st_new, m_new = new_rf(init_server_state(model, fed, key), stack(batch),
                           stack(meta), jnp.stack([wts] * Kr), rngs)
    st_ref, m_ref = jax.jit(ref_rf)(init_server_state(model, fed, key),
                                    stack(batch), stack(meta),
                                    jnp.stack([wts] * Kr), rngs)
    assert jax.tree.structure(st_new) == jax.tree.structure(st_ref)
    assert_close(st_new, st_ref)
    assert sorted(m_new) == sorted(m_ref)
    for name in m_new:
        assert_close(m_new[name], m_ref[name])


# ---------------------------------------------------------------------------
# registry-only extensions: toy algorithm, toy engine, fednova
# ---------------------------------------------------------------------------
@register_algorithm("_test_halfavg", pseudo_gradient=True,
                    description="fedavg deltas scaled by 1/2 (test only)")
def _build_halfavg(loss_fn, *, local_steps, local_epochs, prox_mu, remat):
    del prox_mu

    def update(w_t, batch, lr, rng):
        pseudo, l = fedavg_update(loss_fn, w_t, batch, lr, rng,
                                  local_steps=local_steps,
                                  local_epochs=local_epochs, remat=remat)
        return jax.tree.map(lambda g: 0.5 * g, pseudo), l
    return update


@register_engine("_test_sign_sgd")
class _SignSgdEngine(ServerEngine):
    """Tree-consuming sign-SGD engine (test only): w <- w - lr * sign(G)."""
    name = "_test_sign_sgd"
    accepts = frozenset({"tree"})
    preferred = "tree"
    meta_capabilities = frozenset({"post"})

    def __init__(self, fed):
        del fed

    def init_state(self, params):
        return {}

    def apply(self, params, handle, opt_state, *, lr):
        G = handle.tree
        new_p = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * jnp.sign(g.astype(jnp.float32))
                          ).astype(p.dtype), params, G)
        return new_p, opt_state, tree_global_norm(G)


def test_registered_toy_algorithm_runs_end_to_end(key):
    """A client algorithm lands via register_algorithm ONLY (no core/round
    edits): halved fedavg deltas => exactly half the parameter step under
    the plain-SGD unit-lr server."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    p0 = model.init(key)
    deltas = {}
    for algo in ("fedavg", "_test_halfavg"):
        fed = FedConfig(algorithm=algo, meta=False, cohort=4, local_steps=2,
                        client_lr=0.05)
        st = init_server_state(model, fed, key)
        st, m = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        assert np.isfinite(float(m["client_loss"]))
        deltas[algo] = jax.tree.map(
            lambda new, old: np.asarray(new, np.float32)
            - np.asarray(old, np.float32), st["params"], p0)
    # atol ~ eps32 * |param|: the delta is recovered as new - old, so each
    # entry carries one ulp of the PARAMETER scale from the p - G/2 round
    for k_ in deltas["fedavg"]:
        np.testing.assert_allclose(deltas["_test_halfavg"][k_],
                                   0.5 * deltas["fedavg"][k_],
                                   rtol=1e-5, atol=2e-7)


@pytest.mark.parametrize("strat", ["vmap", "scan"])
def test_registered_toy_engine_runs_end_to_end(key, strat):
    """A server engine lands via register_engine ONLY and composes with
    both built-in cohort executors through the tree handle."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    fed = FedConfig(algorithm="uga", meta=False, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.01, cohort_strategy=strat)
    st = init_server_state(model, fed, key, engine="_test_sign_sgd")
    rf = jax.jit(make_federated_round(model, fed, engine="_test_sign_sgd"))
    st1, m = rf(st, batch, meta, wts, key)
    # sign-SGD: every parameter moved by exactly +-lr (fp32 grid)
    p0 = model.init(key)
    for a, b in zip(jax.tree.leaves(st1["params"]), jax.tree.leaves(p0)):
        step = np.abs(np.asarray(a) - np.asarray(b))
        np.testing.assert_allclose(step, 0.01, rtol=1e-5)
    assert np.isfinite(float(m["client_loss"]))


def test_fednova_matches_fedavg_at_tau_server_lr(key):
    """fednova normalizes deltas by tau = local_steps * local_epochs; with
    server_opt=sgd and server_lr=tau the round recovers fedavg exactly up
    to XLA fusion (tau=2 keeps the normalize+rescale mathematically exact,
    but the two programs contract the server FMA differently — ~1 ulp)."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    states = {}
    for algo, slr in (("fedavg", 0.123), ("fednova", 2.0)):
        fed = FedConfig(algorithm=algo, meta=False, cohort=4, local_steps=2,
                        local_epochs=1, client_lr=0.05, server_lr=slr)
        st = init_server_state(model, fed, key)
        p0 = st["params"]
        states[algo], _ = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
    for p, a, b in zip(jax.tree.leaves(p0),
                       jax.tree.leaves(states["fedavg"]["params"]),
                       jax.tree.leaves(states["fednova"]["params"])):
        p, a, b = np.asarray(p), np.asarray(a), np.asarray(b)
        # the ulp is that of the server step's operands (p0 and the step),
        # not of the result, which cancels to near zero where they meet
        bound = 1e-6 * (np.abs(p) + np.abs(p - b)) + 1e-7
        assert np.all(np.abs(a - b) <= bound), np.max(np.abs(a - b) - bound)


def test_fednova_registered_and_validates():
    assert "fednova" in available_algorithms()
    FedConfig(algorithm="fednova")                       # validates
    with pytest.raises(ValueError, match="register_algorithm"):
        FedConfig(algorithm="not-a-thing")


def test_sharded_is_not_a_base_cohort_strategy():
    """'sharded' wraps cohort_strategy as its base (selected by
    grad_shardings), so using it AS the base must fail actionably at
    config time, not as a bare ValueError deep in the cohort dispatch."""
    with pytest.raises(ValueError, match="grad_shardings"):
        FedConfig(cohort_strategy="sharded")


def test_config_engine_field_drives_capability_and_round(key):
    """FedConfig.engine names a registry engine directly: a capability-
    declaring engine makes through_aggregation valid WITHOUT
    fused_update=True (the capability check runs against the resolved
    engine, not the fused_update flag), and the round runs end to end."""
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, server_opt="sgd",
                    fused_update=False, engine="fused_flat",
                    meta_mode="through_aggregation", ctrl_lr=0.5)
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    st = init_server_state(model, fed, key)
    st, m = jax.jit(make_federated_round(model, fed))(
        st, batch, meta, wts, key)
    assert np.isfinite(float(m["meta_loss"]))
    assert not np.allclose(np.asarray(st["ctrl"]["w_logits"]), 0.0)
    # an engine without the capability still fails loudly at config time
    with pytest.raises(ValueError, match="capability"):
        FedConfig(meta=True, meta_mode="through_aggregation",
                  fused_update=True, engine="_test_sign_sgd")


# ---------------------------------------------------------------------------
# partial participation / straggler dropout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused,strat", [(False, "vmap"), (True, "vmap"),
                                         (True, "scan")])
def test_participation_equals_manual_weight_masking(key, fused, strat):
    """participation<1 == zeroing the same clients' weights by hand: the
    mask folds out of the round rng (never perturbing the client/meta
    streams), so a participation=1 round fed pre-masked weights is bit-
    identical on params and shared metrics."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    rate = 0.5
    kw = dict(algorithm="uga", meta=True, cohort=4, local_steps=2,
              client_lr=0.05, server_lr=0.1, meta_lr=0.05, clip_norm=1.0,
              cohort_strategy=strat, fused_update=fused)
    fed_p = FedConfig(participation=rate, **kw)
    fed_1 = FedConfig(**kw)
    mask = participation_mask(key, 4, rate)
    assert 0 < float(mask.sum()) < 4, "seed gives a non-trivial mask"

    st_p = init_server_state(model, fed_p, key)
    st_p, m_p = jax.jit(make_federated_round(model, fed_p))(
        st_p, batch, meta, wts, key)
    st_1 = init_server_state(model, fed_1, key)
    st_1, m_1 = jax.jit(make_federated_round(model, fed_1))(
        st_1, batch, meta, wts * mask, key)

    assert tree_equal(st_p["params"], st_1["params"])
    assert float(m_p["participants"]) == float(mask.sum())
    for name in m_1:
        np.testing.assert_array_equal(np.asarray(m_p[name]),
                                      np.asarray(m_1[name]), err_msg=name)


def test_participation_one_is_bit_identical_to_default(key):
    """participation=1.0 must not change ANYTHING (same rng splits, same
    metric keys) — the historical-stream guard."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    outs = {}
    for p in (None, 1.0):
        fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                        client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                        **({} if p is None else {"participation": p}))
        st = init_server_state(model, fed, key)
        outs[p] = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
    assert tree_equal(outs[None][0], outs[1.0][0])
    assert sorted(outs[None][1]) == sorted(outs[1.0][1])
    assert "participants" not in outs[1.0][1]


def test_participation_with_through_aggregation(key):
    """Dropped clients get zero effective weight AND zero w_logits
    hypergradient (d eff_w / d logit = n_k * mask * exp = 0)."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, server_opt="sgd",
                    fused_update=True, meta_mode="through_aggregation",
                    ctrl_lr=1.0, participation=0.5)
    # a round rng whose mask drops someone and keeps at least two: a sole
    # survivor's normalized weight is 1 whatever its logit, so its
    # hypergradient is exactly zero and the assertion below needs two
    rng = next(k for k in map(jax.random.PRNGKey, range(64))
               if 2 <= np.sum(participation_mask(k, 4, 0.5)) < 4)
    mask = np.asarray(participation_mask(rng, 4, 0.5))
    assert 2 <= mask.sum() < 4, mask
    st = init_server_state(model, fed, key)
    st, m = jax.jit(make_federated_round(model, fed))(
        st, batch, meta, wts, rng)
    wl = np.asarray(st["ctrl"]["w_logits"])
    assert np.all(wl[mask == 0.0] == 0.0)
    assert np.any(wl[mask == 1.0] != 0.0)
    assert np.isfinite(float(m["meta_loss"]))


def test_participation_validation():
    with pytest.raises(ValueError, match="participation"):
        FedConfig(participation=0.0)
    with pytest.raises(ValueError, match="participation"):
        FedConfig(participation=1.5)


# ---------------------------------------------------------------------------
# FederatedTrainer: driver dedup, resume, records
# ---------------------------------------------------------------------------
def _toy_fed_data(seed=0, n=256, d=10, classes=4, clients=8):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    y = rng.integers(0, classes, n).astype(np.int32)
    parts = np.array_split(rng.permutation(n), clients)
    meta = rng.choice(n, 16, replace=False)
    return FederatedData(arrays={"x": x, "y": y}, client_indices=parts,
                         meta_indices=meta, seed=seed)


def test_trainer_k1_history_matches_legacy_driver_loop(key):
    """The deduplicated rounds_per_call=1 path must reproduce the old
    driver branch (direct unstacked call + scalar float()) bit-exactly —
    the regression gate for routing k==1 through the shared assembly."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05)
    data = _toy_fed_data()
    rounds, batch, meta_bs = 4, 16, 8

    # --- the pre-facade k==1 loop, verbatim ---
    legacy_key = jax.random.PRNGKey(0)
    get_rf = RoundFnCache(model, fed)
    state = init_server_state(model, fed, legacy_key)
    legacy_hist = []
    for r in range(rounds):
        s = data.sample_round(r, cohort=4, batch=batch, share=False)
        mb = data.sample_meta(r, meta_bs)
        state, m = get_rf(1)(
            state, jax.tree.map(jnp.asarray, s["cohort_batch"]),
            jax.tree.map(jnp.asarray, mb),
            jnp.asarray(s["client_weights"]),
            jax.random.fold_in(legacy_key, r))
        rec = {name: float(v) for name, v in m.items()}
        rec["round"] = r
        legacy_hist.append(rec)

    trainer = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    hist = trainer.run(data, rounds=rounds, cohort=4, batch=batch,
                       meta_batch=meta_bs)
    assert hist == legacy_hist
    assert tree_equal(trainer.state["params"], state["params"])


def test_trainer_chunked_records_and_tail(key):
    """rounds_per_call=4 over 6 rounds: one full chunk + a 2-round tail,
    one record per round, on_records sees every chunk."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="fedavg", meta=False, cohort=4, local_steps=2,
                    client_lr=0.05, fused_update=True)
    data = _toy_fed_data()
    seen = []
    trainer = FederatedTrainer(model, fed, rounds_per_call=4, seed=0)
    hist = trainer.run(data, rounds=6, cohort=4, batch=16,
                       on_records=lambda recs, tr: seen.append(len(recs)))
    assert [h["round"] for h in hist] == list(range(6))
    assert seen == [4, 2]
    assert trainer.round == 6
    assert all(np.isfinite(h["client_loss"]) for h in hist)


@pytest.mark.parametrize("fused", [False, True])
def test_trainer_resume_continues_bit_identically(key, tmp_path, fused):
    """save at round 2 of 6 (mid-chunk schedule), restore into a FRESH
    trainer, finish: params and history tail == the uninterrupted run."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt="adam", fused_update=fused)
    data = _toy_fed_data()
    kw = dict(cohort=4, batch=16, meta_batch=8)

    straight = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    full_hist = straight.run(data, rounds=6, **kw)

    part = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    part.run(data, rounds=2, **kw)
    path = os.path.join(tmp_path, "state.msgpack")
    part.save(path, extra={"arch": "mlp"})

    resumed = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    extra = resumed.restore(path)
    assert extra["arch"] == "mlp"
    assert resumed.round == 2
    tail = resumed.run(data, rounds=6, **kw)
    assert tree_equal(resumed.state, straight.state)
    assert tail == full_hist[2:]


# ---------------------------------------------------------------------------
# back-compat import surface + data-pipeline error
# ---------------------------------------------------------------------------
def test_backcompat_import_surface():
    """Every pre-registry entry point stays importable from repro.core AND
    its original module, with working call signatures."""
    from repro.core import (init_server_state, make_federated_round,  # noqa
                            resolve_server_lr, RoundFnCache,
                            stack_round_inputs, grad_global_norm)
    from repro.core.round import (init_server_state as r_init,  # noqa
                                  make_federated_round as r_make,
                                  RoundFnCache as r_cache,
                                  stack_round_inputs as r_stack,
                                  grad_global_norm as r_norm,
                                  resolve_server_lr as r_lr)
    from repro.core.client import make_client_update
    model = make_mlp_model()
    # make_client_update resolves EVERY registered algorithm (incl. the
    # registry-only fednova) and still raises for unknown names
    for algo in available_algorithms():
        assert callable(make_client_update(algo, model.loss, local_steps=2))
    with pytest.raises(ValueError):
        make_client_update("nope", model.loss, local_steps=2)
    # grad_global_norm keeps its semantics
    g = {"a": jnp.asarray([3.0, 4.0])}
    np.testing.assert_allclose(float(grad_global_norm(g)), 5.0, rtol=1e-6)
    # RoundFnCache / stack_round_inputs keep their pre-facade signatures
    fed = FedConfig(algorithm="uga", meta=False, cohort=2, local_steps=2)
    assert callable(RoundFnCache(model, fed)(1))
    cb, mb, w, r = stack_round_inputs(
        [{"x": np.ones((2, 4))}] * 2, [None, None],
        [np.ones(2)] * 2, [jax.random.PRNGKey(0)] * 2)
    assert cb["x"].shape == (2, 2, 4) and mb is None and w.shape == (2, 2)


def test_explicit_executor_override_with_grad_shardings_raises():
    """An explicit executor name + grad_shardings would silently drop the
    sharding constraints (flat/scan paths never attach them) — it must be
    rejected with the sharded executor named."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=False, cohort=2, local_steps=2,
                    fused_update=True)
    with pytest.raises(ValueError, match="sharded"):
        make_federated_round(model, fed, grad_shardings={"w1": None},
                             executor="vmap")


def test_train_cli_plugin_flag_registers_algorithm(tmp_path):
    """The documented one-file CLI plugin workflow: --plugin imports the
    module before --algorithm's choices freeze, so a register_algorithm
    name is selectable in the same invocation."""
    import subprocess
    import sys
    import textwrap
    (tmp_path / "cli_demo_plugin.py").write_text(textwrap.dedent("""
        from functools import partial
        from repro.core.algorithms import register_algorithm
        from repro.core.client import fedavg_update

        @register_algorithm("cli_demo", pseudo_gradient=True,
                            description="CLI plugin smoke algorithm")
        def build(loss_fn, *, local_steps, local_epochs, prox_mu, remat):
            del prox_mu
            return partial(fedavg_update, loss_fn, local_steps=local_steps,
                           local_epochs=local_epochs, remat=remat)
    """))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), str(tmp_path)] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--plugin", "cli_demo_plugin", "--algorithm", "cli_demo",
         "--arch", "smollm-360m-smoke", "--rounds", "2", "--cohort", "2",
         "--client-batch", "4", "--seq", "16", "--no-meta",
         "--num-clients", "4", "--examples", "32", "--log-every", "1"],
        capture_output=True, text=True, cwd=root, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "round    1" in out.stdout


def test_run_training_layers_cuts_depth_only():
    """``layers`` keeps the first N blocks of the arch and every width."""
    import dataclasses
    from repro.configs import get_arch
    from repro.launch.train import run_training
    from repro.models.model import build_model
    state, hist = run_training(
        "smollm-360m-smoke", rounds=1, cohort=2, client_batch=2, seq=16,
        layers=1, meta=False, num_clients=4, examples=32, log_every=0)
    assert len(hist) == 1
    cfg = get_arch("smollm-360m-smoke")
    assert cfg.num_layers > 1
    want = jax.eval_shape(
        build_model(dataclasses.replace(cfg, num_layers=1),
                    dtype=jnp.float32).init,
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), state["params"])
    assert got == jax.tree.map(lambda x: (x.shape, x.dtype), want)


def test_enable_compile_cache_env_or_fixed_path(monkeypatch, tmp_path):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, untouched, and
    otherwise to a fixed directory at the checkout root."""
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == str(cc.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.CACHE_DIR)
        assert cc.CACHE_DIR.name == ".jax_cache"
        assert (cc.CACHE_DIR.parent / "pytest.ini").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_sample_round_cohort_exceeds_clients_actionable_error():
    """cohort > num_clients used to surface numpy's opaque 'Cannot take a
    larger sample than population' — it must name both numbers now."""
    data = _toy_fed_data(clients=4)
    with pytest.raises(ValueError, match=r"cohort=9.*num_clients=4"):
        data.sample_round(0, cohort=9, batch=8)
    # boundary: cohort == num_clients still samples
    s = data.sample_round(0, cohort=4, batch=8)
    assert len(s["clients"]) == 4
