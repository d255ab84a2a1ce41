"""Controllable meta-through-aggregation, FedOpt server-lr, local-epochs
threading, and full-server-state checkpoint/resume:

  * ``meta_mode="through_aggregation"`` hypergradients (w.r.t. per-client
    weight logits and log server lr) from ``meta_update_through_cohort``
    (the streaming cohort core's accumulate VJP + the fused update VJP)
    match XLA autodiff through the legacy tree-map server step;
  * the same round under ``cohort_strategy="scan"`` (the core at chunk 1,
    g_k recomputed under ``jax.checkpoint``) equals the vmap round (chunk
    = cohort) bit for bit, and the combination runs under
    rounds_per_call>1;
  * the mode-combination guards fail loudly (ValueError with the fix named)
    instead of a bare NameError / silently-broadcast ctrl update;
  * one controllable round updates the ctrl state with finite metrics and
    leaves ``meta_mode="post"`` (the default) bit-identical to before;
  * ``server_lr`` regression: forced to 1.0 ONLY for fedavg/fedprox under
    plain-SGD (exact parameter averaging); honored for UGA and for every
    FedOpt server optimizer (FedAdam/FedYogi on pseudo-gradients);
  * ``FedConfig.local_epochs`` threads through ``make_federated_round`` →
    ``make_client_update`` (E>1 == the example-tiled E=1 round) and the
    batch-divisibility contract asserts at trace time;
  * checkpoint save/restore round-trips the FULL server state (params +
    legacy and fused tuple-structured opt state + ctrl + round counter),
    and a mid-run save/restore continues bit-identically.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, save
from repro.configs.base import FedConfig
from repro.core import (init_server_state, make_federated_round,
                        meta_update_through_cohort, resolve_server_lr,
                        server_opt)
from repro.core.engines import get_engine
from repro.core.executors import get_executor
from repro.models.model import Model

from _reference import weighted_mean


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
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# hypergradients through the fused aggregation == legacy autodiff
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt", ["sgd", "adam"])
@pytest.mark.parametrize("clip", [0.0, 1.0])
def test_hypergrad_matches_legacy_autodiff(key, opt, clip):
    """meta_update_through_cohort over the streaming core (each client
    returns a fixed gradient: its batch) == XLA autodiff of the same meta
    loss through the legacy tree-map server step."""
    model = make_mlp_model()
    params = model.init(key)
    batch, meta, wts = _round_inputs()
    cohort = wts.shape[0]
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, p.size),
                                    (cohort,) + p.shape), params)
    from repro.core import flat as F
    from repro.kernels.fused_update import ops as O
    spec = F.make_flat_spec(params)
    fed = FedConfig(cohort=cohort, fused_update=True, server_opt=opt,
                    clip_norm=clip)
    # adam at t=1 from zeros is a sign-step — scale-invariant in G, so the
    # weight hypergradient is ~0 and both engines return fp32 noise; a warm
    # state makes the step genuinely weight-sensitive.
    m_tree = jax.tree.map(
        lambda p: 0.3 * jax.random.normal(jax.random.fold_in(key, p.size + 3),
                                          p.shape), params)
    v_tree = jax.tree.map(
        lambda p: 0.1 + jnp.abs(jax.random.normal(
            jax.random.fold_in(key, p.size + 4), p.shape)), params)

    def _warm(st, flat):
        if "m" in st:
            st["m"] = tuple(F.flatten_tree(spec, m_tree)) if flat else m_tree
        if "v" in st:
            st["v"] = tuple(F.flatten_tree(spec, v_tree)) if flat else v_tree
            st["t"] = jnp.asarray(5, jnp.int32)
        return st

    def fused_hypergrads(w_logits, log_lr):
        # the ctrl logits start at 0 with w_logits folded into the client
        # weights: d/d(logit) at 0 of the loss under n_k * exp(w_logits) *
        # exp(logit) is the gradient at w_logits, and with ctrl_lr = 1 the
        # updated logits are exactly -d_wl
        eff_w = wts * jnp.exp(w_logits)
        rw = get_executor("chunked")(fed).reweightable(
            lambda w, g, lr, rng: (g, jnp.float32(0.0)), params, grads,
            eff_w, 0.0, None)
        st = _warm(O.init_flat_opt_state(opt, spec), flat=True)
        ctrl = {"w_logits": jnp.zeros_like(w_logits), "log_lr": log_lr}
        _, _, _, _, new_ctrl, metrics = meta_update_through_cohort(
            model.loss, rw, eff_w, params, st, meta, ctrl,
            engine=get_engine("fused_flat")(fed), ctrl_lr=1.0)
        return -new_ctrl["w_logits"], metrics["ctrl_lr_grad"]

    def legacy_meta_loss(w_logits, log_lr):
        eff_w = wts * jnp.exp(w_logits)
        G = weighted_mean(grads, eff_w)
        if clip > 0:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(G)))
            s = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
            G = jax.tree.map(lambda x: x * s, G)
        st = _warm(server_opt.init_state(opt, params), flat=False)
        new_p, _ = server_opt.apply(opt, st, params, G, jnp.exp(log_lr))
        return model.loss(new_p, meta)[0]

    wl = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (cohort,))
    llr = jnp.log(jnp.float32(0.2))
    f_wl, f_lr = jax.jit(fused_hypergrads)(wl, llr)
    l_wl, l_lr = jax.grad(legacy_meta_loss, argnums=(0, 1))(wl, llr)
    scale = max(float(jnp.max(jnp.abs(l_wl))), 1e-8)
    assert float(jnp.max(jnp.abs(f_wl - l_wl))) <= 1e-5 * scale
    np.testing.assert_allclose(float(f_lr), float(l_lr),
                               rtol=1e-4, atol=1e-7)
    assert np.isfinite(np.asarray(f_wl)).all() and np.isfinite(float(f_lr))


def test_through_aggregation_round_updates_ctrl_state(key):
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, server_opt="adam",
                    clip_norm=1.0, fused_update=True,
                    meta_mode="through_aggregation", ctrl_lr=0.05)
    rf = jax.jit(make_federated_round(model, fed))
    batch, meta, wts = _round_inputs()
    state = init_server_state(model, fed, key)
    assert state["ctrl"]["w_logits"].shape == (4,)
    np.testing.assert_allclose(float(jnp.exp(state["ctrl"]["log_lr"])), 0.1,
                               rtol=1e-6)
    for r in range(2):
        state, m = rf(state, batch, meta, wts, jax.random.fold_in(key, r))
    for name in ("client_loss", "grad_norm", "meta_loss", "ctrl_w_gnorm",
                 "ctrl_lr_grad", "server_lr_eff"):
        assert np.isfinite(float(m[name])), name
    # the hypergradient step moved the controllable state
    assert float(m["ctrl_w_gnorm"]) > 0
    assert not np.allclose(np.asarray(state["ctrl"]["w_logits"]), 0.0)
    assert int(state["round"]) == 2


def test_meta_mode_post_default_unchanged(key):
    """meta_mode='post' must stay bit-identical to a config that never
    heard of meta modes (regression guard on the default path)."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    states = {}
    for mode in ("post", "through_aggregation"):
        fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                        client_lr=0.05, server_lr=0.1, server_opt="sgd",
                        fused_update=True, meta_mode=mode)
        st = init_server_state(model, fed, key)
        states[mode], _ = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
    # both modes step the params, but differently (post adds the Eq. 20
    # parameter step; through_aggregation reinvests the signal in ctrl)
    assert "ctrl" not in states["post"]
    assert "ctrl" in states["through_aggregation"]
    assert not tree_equal(states["post"]["params"],
                          states["through_aggregation"]["params"])


def test_through_aggregation_config_validation():
    # ValueError (not a bare assert): must stay loud under python -O, and
    # the message should name the fix
    with pytest.raises(ValueError, match="fused_update=True"):
        FedConfig(meta=True, meta_mode="through_aggregation",
                  fused_update=False)
    with pytest.raises(ValueError, match="server_lr"):
        FedConfig(meta=True, meta_mode="through_aggregation",
                  fused_update=True, server_lr=0.0)
    # scan cohorts are now a SUPPORTED combination (streaming flat
    # accumulation feeds the per-client weight hypergradients)
    FedConfig(meta=True, meta_mode="through_aggregation",
              fused_update=True, cohort_strategy="scan")
    with pytest.raises(ValueError, match="meta_mode"):
        FedConfig(meta_mode="sideways")


def test_through_aggregation_round_guards():
    """make_federated_round re-validates at trace-build time: a config that
    dodged __post_init__ (python -O, object.__setattr__) must not reach the
    legacy branch and die on an undefined new_ctrl.  grad_shardings used to
    be rejected here (the old sharded executor pre-aggregated per leaf);
    the two-tier sharded executor recomputes per-client hypergradients per
    chunk, so the same config now BUILDS — pinned positively."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    fused_update=True, meta_mode="through_aggregation")
    object.__setattr__(fed, "fused_update", False)     # simulate -O bypass
    with pytest.raises(ValueError, match="fused_update=True"):
        make_federated_round(model, fed)

    fed2 = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                     fused_update=True, meta_mode="through_aggregation")
    round_fn = make_federated_round(model, fed2,
                                    grad_shardings={"w1": None})
    assert callable(round_fn)


# ---------------------------------------------------------------------------
# through_aggregation under scan cohorts == the vmap path, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt,clip", [("sgd", 0.0), ("sgd", 1.0),
                                      ("sgdm", 1.0)])
def test_scan_hypergrads_match_vmap(key, opt, clip):
    """Regression for the old silently-wrong combination: scan used to feed
    a pre-aggregated (1, ...) stack + w_fused=ones(1) into the ctrl update,
    broadcasting against (cohort,) w_logits.  Now the streaming accumulate
    VJP supplies per-client cotangents, and scan (chunk 1) and vmap (chunk
    = cohort) are one core in global client order: two rounds' ctrl,
    params, opt state and metrics are identical to the bit."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    ctrls = {}
    for strat in ("vmap", "scan"):
        fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                        client_lr=0.05, server_lr=0.1, server_opt=opt,
                        clip_norm=clip, fused_update=True,
                        cohort_strategy=strat,
                        meta_mode="through_aggregation", ctrl_lr=1.0)
        st = init_server_state(model, fed, key)
        rf = jax.jit(make_federated_round(model, fed))
        # two rounds: round 2 runs with w_logits != 0, so the client_loss
        # metric parity below also covers the eff_w-vs-n_k weighting
        for r in range(2):
            st, m = rf(st, batch, meta, wts, jax.random.fold_in(key, r))
        ctrls[strat] = (st, m)
    (st_v, m_v), (st_s, m_s) = ctrls["vmap"], ctrls["scan"]
    assert not np.allclose(np.asarray(st_v["ctrl"]["w_logits"]), 0.0)
    assert jax.tree.structure(st_s) == jax.tree.structure(st_v)
    assert tree_equal(st_s, st_v)
    assert sorted(m_s) == sorted(m_v)
    for name in m_v:
        np.testing.assert_array_equal(np.asarray(m_s[name]),
                                      np.asarray(m_v[name]), err_msg=name)


def test_scan_hypergrads_match_vmap_adam_warm(key):
    """adam arm of the scan==vmap hypergradient gate, warm (t=5) state: at
    t=1 from zeros the sign-step's weight hypergradient is ~0 (fp32
    cancellation noise), so the warm state keeps the gate meaningful; the
    two are one core, so the round is identical to the bit."""
    model = make_mlp_model()
    params0 = model.init(key)
    from repro.core import flat as F
    spec = F.make_flat_spec(params0)
    batch, meta, wts = _round_inputs()
    m_tree = jax.tree.map(
        lambda p: 0.3 * jax.random.normal(jax.random.fold_in(key, p.size + 3),
                                          p.shape), params0)
    v_tree = jax.tree.map(
        lambda p: 0.1 + jnp.abs(jax.random.normal(
            jax.random.fold_in(key, p.size + 4), p.shape)), params0)
    ctrls = {}
    for strat in ("vmap", "scan"):
        fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                        client_lr=0.05, server_lr=0.1, server_opt="adam",
                        clip_norm=1.0, fused_update=True,
                        cohort_strategy=strat,
                        meta_mode="through_aggregation", ctrl_lr=1.0)
        st = init_server_state(model, fed, key)
        st["opt"] = {"m": tuple(F.flatten_tree(spec, m_tree)),
                     "v": tuple(F.flatten_tree(spec, v_tree)),
                     "t": jnp.asarray(5, jnp.int32)}
        st, _ = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        ctrls[strat] = st
    assert not np.allclose(np.asarray(ctrls["vmap"]["ctrl"]["w_logits"]),
                           0.0)
    assert (jax.tree.structure(ctrls["scan"])
            == jax.tree.structure(ctrls["vmap"]))
    assert tree_equal(ctrls["scan"], ctrls["vmap"])


def test_scan_through_aggregation_rounds_per_call(key):
    """scan + through_aggregation + rounds_per_call>1 (the 90B/398B driver
    shape): nested scans trace, ctrl state moves, metrics stay finite."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, server_opt="adam",
                    clip_norm=1.0, fused_update=True, cohort_strategy="scan",
                    meta_mode="through_aggregation", ctrl_lr=0.05)
    Kr = 2
    batch, meta, wts = _round_inputs()
    rf = jax.jit(make_federated_round(model, fed, rounds_per_call=Kr))
    st = init_server_state(model, fed, key)
    st, m = rf(st,
               jax.tree.map(lambda x: jnp.stack([x] * Kr), batch),
               jax.tree.map(lambda x: jnp.stack([x] * Kr), meta),
               jnp.stack([wts] * Kr),
               jnp.stack([jax.random.fold_in(key, r) for r in range(Kr)]))
    assert int(st["round"]) == Kr
    for name in ("client_loss", "grad_norm", "meta_loss", "ctrl_w_gnorm",
                 "ctrl_lr_grad", "server_lr_eff"):
        assert m[name].shape == (Kr,)
        assert np.isfinite(np.asarray(m[name])).all(), name
    assert not np.allclose(np.asarray(st["ctrl"]["w_logits"]), 0.0)


# ---------------------------------------------------------------------------
# FedOpt server-lr regression (was silently forced to 1.0 for fedavg)
# ---------------------------------------------------------------------------
def test_resolve_server_lr_paths():
    mk = lambda algo, opt: FedConfig(algorithm=algo, server_opt=opt,
                                     server_lr=0.37)
    assert resolve_server_lr(mk("uga", "sgd")) == 0.37
    assert resolve_server_lr(mk("uga", "adam")) == 0.37
    assert resolve_server_lr(mk("fedavg", "sgd")) == 1.0      # exact FedAvg
    assert resolve_server_lr(mk("fedprox", "sgd")) == 1.0
    assert resolve_server_lr(mk("fedavg", "adam")) == 0.37    # FedAdam
    assert resolve_server_lr(mk("fedprox", "yogi")) == 0.37   # FedYogi
    assert resolve_server_lr(mk("fedavg", "sgdm")) == 0.37    # FedAvgM


@pytest.mark.parametrize("fused", [False, True])
def test_fedavg_fedopt_server_lr_applied(key, fused):
    """Under plain SGD fedavg must ignore server_lr (exact averaging);
    under a FedOpt server optimizer two different server_lr values MUST
    produce different parameters (the old code forced both to 1.0)."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()

    def run(opt, server_lr):
        fed = FedConfig(algorithm="fedavg", meta=False, cohort=4,
                        local_steps=2, client_lr=0.05, server_lr=server_lr,
                        server_opt=opt, fused_update=fused)
        st = init_server_state(model, fed, key)
        st, _ = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        return st["params"]

    # plain SGD: server_lr has no effect (lr forced to 1.0 on both)
    assert tree_equal(run("sgd", 0.5), run("sgd", 0.01))
    # FedAdam: server_lr is live again
    p_big, p_small = run("adam", 0.5), run("adam", 0.01)
    assert not tree_equal(p_big, p_small)
    # and scales the step: adam's step saturates to ~lr*sign, so the
    # parameter delta ratio tracks the lr ratio
    p0 = model.init(key)
    d_big = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(p_big), jax.tree.leaves(p0)))
    d_small = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in
                  zip(jax.tree.leaves(p_small), jax.tree.leaves(p0)))
    assert d_big > 10 * d_small


# ---------------------------------------------------------------------------
# local_epochs threading through FedConfig
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algo", ["uga", "fedavg"])
def test_local_epochs_threads_through_round(key, algo):
    """E>1 through FedConfig == the E=1 round over example-tiled client
    batches with local_steps*E steps (the schedule-equality contract the
    client-level tests prove, now through make_federated_round)."""
    model = make_mlp_model()
    steps, epochs = 2, 3
    batch, meta, wts = _round_inputs(b=12)
    tiled = {k: jnp.tile(v, (1, epochs) + (1,) * (v.ndim - 2))
             for k, v in batch.items()}
    kw = dict(algorithm=algo, meta=True, cohort=4, client_lr=0.05,
              server_lr=0.1, meta_lr=0.05)
    fed_e = FedConfig(local_steps=steps, local_epochs=epochs, **kw)
    fed_1 = FedConfig(local_steps=steps * epochs, local_epochs=1, **kw)
    st_e = init_server_state(model, fed_e, key)
    st_1 = init_server_state(model, fed_1, key)
    st_e, m_e = jax.jit(make_federated_round(model, fed_e))(
        st_e, batch, meta, wts, key)
    st_1, m_1 = jax.jit(make_federated_round(model, fed_1))(
        st_1, tiled, meta, wts, key)
    for a, b in zip(jax.tree.leaves(st_e["params"]),
                    jax.tree.leaves(st_1["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m_e["client_loss"]),
                               float(m_1["client_loss"]),
                               rtol=1e-5, atol=1e-7)


def test_local_steps_batch_divisibility_asserts(key):
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=False, cohort=2, local_steps=5,
                    local_epochs=2, client_lr=0.05)
    rf = make_federated_round(model, fed)
    batch, meta, wts = _round_inputs(cohort=2, b=12)   # 12 % 5 != 0
    st = init_server_state(model, fed, key)
    with pytest.raises(AssertionError, match="not divisible"):
        jax.jit(rf)(st, batch, meta, wts, key)


# ---------------------------------------------------------------------------
# full-server-state checkpointing + resume
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused,opt,mode", [
    (False, "adam", "post"),            # legacy per-leaf m/v/t
    (True, "adam", "post"),             # fused tuple-structured flat state
    (True, "sgdm", "post"),
    (True, "yogi", "through_aggregation"),   # + controllable ctrl slot
])
def test_server_state_checkpoint_roundtrip(key, tmp_path, fused, opt, mode):
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, server_opt=opt,
                    fused_update=fused, meta_mode=mode)
    batch, meta, wts = _round_inputs()
    rf = jax.jit(make_federated_round(model, fed))
    state = init_server_state(model, fed, key)
    state, _ = rf(state, batch, meta, wts, key)        # non-trivial opt state
    path = os.path.join(tmp_path, "state.msgpack")
    save(path, state, extra={"algorithm": "uga"})
    restored, extra = restore(path, init_server_state(model, fed, key))
    assert extra["algorithm"] == "uga"
    assert jax.tree_util.tree_structure(restored) == \
        jax.tree_util.tree_structure(state)
    assert tree_equal(state, restored)
    assert int(restored["round"]) == 1

    # resuming must continue bit-identically to never having stopped
    state2, _ = rf(state, batch, meta, wts, jax.random.fold_in(key, 1))
    resumed2, _ = rf(restored, batch, meta, wts, jax.random.fold_in(key, 1))
    assert tree_equal(state2, resumed2)


def test_restore_params_only_checkpoint_into_state_errors(key, tmp_path):
    """Old drivers saved bare params; resuming those into a full server
    state must fail loudly, not KeyError deep in the blob."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", server_opt="adam")
    path = os.path.join(tmp_path, "params.msgpack")
    save(path, model.init(key))
    with pytest.raises(KeyError, match="different structure"):
        restore(path, init_server_state(model, fed, key))
