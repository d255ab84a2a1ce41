"""Fused server-update engine (kernels/fused_update + core/flat):

  * flat-buffer round-trip preserves structure/shapes/dtypes;
  * fused Pallas kernels == pure-jnp ref oracle == legacy tree-map path
    for all four server optimizers, with and without clipping (the fused
    step as the cohort core feeds it: one streaming ``flat_accumulate`` per
    client, then ``flat_apply_groups``);
  * the custom-VJP backward: ``jax.grad`` through that fused step (w.r.t.
    per-client gradient stack, client weights, server lr) == autodiff
    through the legacy tree-map path, for both the Pallas bwd kernels and
    the ref oracle bwd;
  * rounds_per_call>1 (lax.scan driver) == K sequential single-round calls;
  * the modulo-indexed epoch schedule == the old jnp.tile expansion;
  * the streaming cohort core: its flat accumulation (``accumulate_pass``
    + custom VJP) produces BIT-identical aggregates to a plain pytree scan
    carry, and the fused scan round matches the legacy scan round end to
    end (warm adam/yogi state per the sign-step conditioning caveat the
    vmap tests document).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import flat as F
from repro.core import init_server_state, make_federated_round, server_opt
from repro.core.aggregate import chunked_cohort_gradient_flat
from repro.core.client import (fedavg_update, make_client_update, uga_update)
from repro.kernels.fused_update import kernel as K
from repro.kernels.fused_update import ops as O
from repro.kernels.fused_update import ref as R
from repro.models.model import Model

from _reference import weighted_mean


def mixed_tree(key):
    ks = jax.random.split(key, 4)
    return {
        "dense": {"w": jax.random.normal(ks[0], (10, 16)),
                  "b": jnp.zeros((16,))},
        "half": jax.random.normal(ks[1], (7, 9)).astype(jnp.bfloat16),
        "scalarish": jax.random.normal(ks[2], (3,)),
        "head": jax.random.normal(ks[3], (16, 4)).astype(jnp.bfloat16),
    }


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
    return {"x": jnp.asarray(rng.normal(0, 1, (cohort, b, d)),
                             jnp.float32),
            "y": jnp.asarray(rng.integers(0, classes, (cohort, b)),
                             jnp.int32)}


# ---------------------------------------------------------------------------
# flat buffers
# ---------------------------------------------------------------------------
def test_flat_roundtrip_structure_and_dtypes(key):
    tree = mixed_tree(key)
    spec = F.make_flat_spec(tree)
    assert len(spec.groups) == 2                     # float32 + bfloat16
    for g in spec.groups:
        assert g.rows % F.ROW_ALIGN == 0 and g.rows * F.LANES >= g.size
    rt = F.unflatten_tree(spec, F.flatten_tree(spec, tree))
    assert jax.tree_util.tree_structure(rt) == \
        jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_flat_stacked_matches_per_client_flatten(key):
    tree = mixed_tree(key)
    spec = F.make_flat_spec(tree)
    cohort = 3
    stacked = jax.tree.map(
        lambda x: jnp.stack([x.astype(jnp.float32) * (i + 1)
                             for i in range(cohort)]).astype(x.dtype), tree)
    bufs = F.flatten_stacked(spec, stacked)
    for i in range(cohort):
        one = jax.tree.map(lambda x, i=i: x[i], stacked)
        for got, want in zip(bufs, F.flatten_tree(spec, one)):
            np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want))


def test_unflatten_stacked_inverts_flatten_stacked(key):
    tree = mixed_tree(key)
    spec = F.make_flat_spec(tree)
    cohort = 3
    stacked = jax.tree.map(
        lambda x: jnp.stack([x.astype(jnp.float32) * (i + 1)
                             for i in range(cohort)]).astype(x.dtype), tree)
    rt = F.unflatten_stacked(spec, F.flatten_stacked(spec, stacked))
    assert jax.tree_util.tree_structure(rt) == \
        jax.tree_util.tree_structure(stacked)
    for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(stacked)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# fused engine vs ref oracle vs legacy tree-map path
# ---------------------------------------------------------------------------
def fused_step(params, grad_stack, wts, opt_state, *, opt, lr, clip_norm,
               momentum=0.9, use_ref=False):
    """The fused server step over stacked per-client gradients as the
    cohort core feeds it: one streaming ``flat_accumulate`` per client into
    the flat groups, in client order, then pass 2 (``flat_apply_groups``).
    Returns (new_params, new_opt_state, grad_norm_after_clip)."""
    spec = F.make_flat_spec(params)
    accum = O.flat_accumulate(use_ref)
    w = wts.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)
    stacks = F.flatten_stacked(spec, grad_stack)
    Gs = F.zeros_flat(spec)
    for k in range(w.shape[0]):
        Gs = [accum(G, g[k], w[k]) for G, g in zip(Gs, stacks)]
    return O.flat_apply_groups(
        spec, Gs, jnp.sqrt(F.flat_sq_norm(Gs)), params, opt_state, opt=opt,
        lr=lr, clip_norm=clip_norm, momentum=momentum, use_ref=use_ref)


@pytest.mark.parametrize("opt", ["sgd", "sgdm", "adam", "yogi"])
@pytest.mark.parametrize("clip", [0.0, 0.5])
def test_fused_matches_ref_and_legacy(key, opt, clip):
    params = mixed_tree(key)
    spec = F.make_flat_spec(params)
    cohort = 5
    gkey = jax.random.fold_in(key, 9)
    grads = jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.fold_in(gkey, p.size), (cohort,) + p.shape,
            jnp.float32), params)
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    lr = 0.07

    out = {}
    for use_ref in (False, True):
        st = O.init_flat_opt_state(opt, spec)
        newp, newst, gn = jax.jit(lambda p, g, w, s: fused_step(
            p, g, w, s, opt=opt, lr=lr, clip_norm=clip,
            use_ref=use_ref))(params, grads, wts, st)
        out[use_ref] = (newp, gn)
    # Pallas kernels == oracle (same flat math, bit-level expectations loose)
    for a, b in zip(jax.tree.leaves(out[False][0]),
                    jax.tree.leaves(out[True][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7)

    # legacy tree-map pipeline on the same inputs
    G = weighted_mean(grads, wts)
    if clip > 0:
        gn_l = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree.leaves(G)))
        s = jnp.minimum(1.0, clip / jnp.maximum(gn_l, 1e-9))
        G = jax.tree.map(lambda g: (g.astype(jnp.float32) * s
                                    ).astype(g.dtype), G)
    lp, _ = server_opt.apply(opt, server_opt.init_state(opt, params),
                             params, G, lr, momentum=0.9)
    for a, b in zip(jax.tree.leaves(out[False][0]), jax.tree.leaves(lp)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        rel = np.max(np.abs(a - b) / (np.abs(b) + 1e-6))
        assert rel <= 1e-5, (opt, clip, rel)


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_fused_round_matches_legacy_round(key, opt):
    model = make_mlp_model()
    rng = np.random.default_rng(0)
    batch = sample_batch(rng, cohort=4, b=16)
    meta = {"x": batch["x"][0], "y": batch["y"][0]}
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    kw = dict(algorithm="uga", meta=True, cohort=4, local_steps=2,
              client_lr=0.05, server_lr=0.1, meta_lr=0.05, server_opt=opt,
              clip_norm=1.0)
    states, metrics = {}, {}
    for fused in (False, True):
        fed = FedConfig(fused_update=fused, **kw)
        rf = jax.jit(make_federated_round(model, fed))
        st = init_server_state(model, fed, key)
        p0 = st["params"]
        states[fused], metrics[fused] = rf(st, batch, meta, wts, key)
    for k in states[False]["params"]:
        a = np.asarray(states[True]["params"][k])
        b = np.asarray(states[False]["params"][k])
        p = np.asarray(p0[k])
        # the new parameter is p0 - step, and adam's first step is about
        # lr * sign(g) for every element, so an element whose p0 is close
        # to its step cancels to near zero; the engines' rounding is
        # relative to the operands of that subtraction, not its result
        rel = np.max(np.abs(a - b) / (np.abs(p) + np.abs(p - b) + 1e-6))
        assert rel <= 1e-5, (opt, k, rel)
    for name in ("client_loss", "grad_norm", "meta_loss"):
        np.testing.assert_allclose(float(metrics[True][name]),
                                   float(metrics[False][name]),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# scanned multi-round driver
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", [False, True])
def test_rounds_per_call_matches_sequential(key, fused):
    model = make_mlp_model()
    K = 3
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt="adam", clip_norm=1.0, lr_decay=0.9,
                    fused_update=fused)
    rng = np.random.default_rng(1)
    batches = [sample_batch(rng, cohort=4, b=16) for _ in range(K)]
    metas = [{"x": b["x"][0], "y": b["y"][0]} for b in batches]
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    rngs = jnp.stack([jax.random.fold_in(key, r) for r in range(K)])

    rf1 = jax.jit(make_federated_round(model, fed))
    st = init_server_state(model, fed, key)
    per_round = []
    for r in range(K):
        st, m = rf1(st, batches[r], metas[r], wts, rngs[r])
        per_round.append(m)

    rfK = jax.jit(make_federated_round(model, fed, rounds_per_call=K))
    stK = init_server_state(model, fed, key)
    stK, mK = rfK(stK,
                  jax.tree.map(lambda *xs: jnp.stack(xs), *batches),
                  jax.tree.map(lambda *xs: jnp.stack(xs), *metas),
                  jnp.stack([wts] * K), rngs)

    assert int(stK["round"]) == int(st["round"]) == K
    for a, b in zip(jax.tree.leaves(stK["params"]),
                    jax.tree.leaves(st["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for name in mK:
        assert mK[name].shape == (K,)
        for r in range(K):
            np.testing.assert_allclose(float(mK[name][r]),
                                       float(per_round[r][name]),
                                       rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# epoch schedule: modulo indexing == the old jnp.tile expansion
# ---------------------------------------------------------------------------
def _tile_batch(batch, epochs):
    return jax.tree.map(
        lambda x: jnp.tile(x, (epochs,) + (1,) * (x.ndim - 1)), batch)


# ---------------------------------------------------------------------------
# custom-VJP backward: jax.grad through the fused engine == legacy autodiff
# ---------------------------------------------------------------------------
def f32_tree(key):
    """All-f32 mixed-shape params (grad comparisons at 1e-5 need both paths
    to share the leaf dtype; bf16 leaves round each path differently)."""
    ks = jax.random.split(key, 3)
    return {"w1": jax.random.normal(ks[0], (10, 16)) * 0.3,
            "w2": jax.random.normal(ks[1], (16, 4)) * 0.3,
            "b": jax.random.normal(ks[2], (5,))}


def _coeff_like(key, tree, salt):
    return jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.fold_in(key, p.size + salt), p.shape), tree)


def _tree_dot(a, b):
    return sum(jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def assert_grads_close(got, want, tol=1e-5):
    """Per-leaf max error <= tol * the leaf's gradient scale (fp32
    reduction order differs between the engines, so elementwise relative
    error on entries ~1000x below the leaf scale is pure ulp noise)."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(float(np.max(np.abs(b))), 1e-8)
        err = float(np.max(np.abs(a - b))) / scale
        assert err <= tol, (a.shape, err)


@pytest.mark.parametrize("opt", ["sgd", "sgdm", "adam", "yogi"])
@pytest.mark.parametrize("clip", [0.0, 0.5])
@pytest.mark.parametrize("use_ref", [False, True])
def test_grad_through_fused_matches_legacy_autodiff(key, opt, clip, use_ref):
    """d(objective)/d(grad_stack, client_weights, lr) through the fused
    step's custom VJPs (the streaming accumulate and the update pass) ==
    autodiff through the legacy tree-map path, where the
    objective touches new params, the clipped grad norm AND the new
    optimizer state (so every backward-kernel output cotangent is live).

    adam/yogi use a warm (t=5, random m, v>0) state: at t=1 from zeros the
    update saturates to lr*sign(g) whose g-derivative is a catastrophic
    fp32 cancellation in ANY implementation — the same conditioning caveat
    the forward bench documents for its numerics gate."""
    params = f32_tree(key)
    spec = F.make_flat_spec(params)
    cohort = 5
    gkey = jax.random.fold_in(key, 9)
    grads = jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.fold_in(gkey, p.size), (cohort,) + p.shape,
            jnp.float32), params)
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    lr = 0.07
    c_p = _coeff_like(key, params, 7)
    c_m = _coeff_like(key, params, 8)
    c_v = _coeff_like(key, params, 9)
    m_tree = jax.tree.map(lambda p: 0.3 * p, _coeff_like(key, params, 11))
    v_tree = jax.tree.map(lambda p: 0.1 + jnp.abs(p),
                          _coeff_like(key, params, 12))
    t0 = 5

    def _flat_dot(bufs, coeff_tree):
        return sum(jnp.sum(a * c) for a, c in
                   zip(bufs, F.flatten_tree(spec, coeff_tree)))

    def fused_obj(g, w, lr_):
        st = O.init_flat_opt_state(opt, spec)
        if "m" in st:
            st["m"] = tuple(F.flatten_tree(spec, m_tree))
        if "v" in st:
            st["v"] = tuple(F.flatten_tree(spec, v_tree))
            st["t"] = jnp.asarray(t0, jnp.int32)
        newp, newst, gn = fused_step(params, g, w, st, opt=opt, lr=lr_,
                                     clip_norm=clip, use_ref=use_ref)
        obj = _tree_dot(newp, c_p) + 0.3 * gn
        if "m" in newst:
            obj = obj + _flat_dot(newst["m"], c_m)
        if "v" in newst:
            obj = obj + _flat_dot(newst["v"], c_v)
        return obj

    def legacy_obj(g, w, lr_):
        G = weighted_mean(g, w)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(G)))
        if clip > 0:
            s = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
            G = jax.tree.map(lambda x: x * s, G)
            gn = gn * s
        st = server_opt.init_state(opt, params)
        if "m" in st:
            st["m"] = m_tree
        if "v" in st:
            st["v"] = v_tree
            st["t"] = jnp.asarray(t0, jnp.int32)
        newp, newst = server_opt.apply(opt, st, params, G, lr_, momentum=0.9)
        obj = _tree_dot(newp, c_p) + 0.3 * gn
        if "m" in newst:
            obj = obj + _tree_dot(newst["m"], c_m)
        if "v" in newst:
            obj = obj + _tree_dot(newst["v"], c_v)
        return obj

    fg = jax.jit(jax.grad(fused_obj, argnums=(0, 1, 2)))(grads, wts, lr)
    lg = jax.grad(legacy_obj, argnums=(0, 1, 2))(grads, wts, lr)
    assert_grads_close(fg, lg)


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_grad_wrt_params_through_fused_matches_legacy(key, opt):
    """Cotangents also flow into the *parameters* (dp = d new_p through
    p' = p - lr*step is the identity in the custom bwd)."""
    params = f32_tree(key)
    spec = F.make_flat_spec(params)
    grads = jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.fold_in(key, p.size + 1), (3,) + p.shape), params)
    wts = jnp.asarray([1.0, 2.0, 3.0])
    c_p = _coeff_like(key, params, 7)

    def fused_obj(p):
        st = O.init_flat_opt_state(opt, spec)
        newp, _, _ = fused_step(p, grads, wts, st, opt=opt, lr=0.07,
                                clip_norm=0.5)
        return _tree_dot(newp, c_p)

    def legacy_obj(p):
        G = weighted_mean(grads, wts)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(G)))
        s = jnp.minimum(1.0, 0.5 / jnp.maximum(gn, 1e-9))
        G = jax.tree.map(lambda x: x * s, G)
        newp, _ = server_opt.apply(opt, server_opt.init_state(opt, p), p,
                                   G, 0.07)
        return _tree_dot(newp, c_p)

    assert_grads_close(jax.jit(jax.grad(fused_obj))(params),
                       jax.grad(legacy_obj)(params))


# ---------------------------------------------------------------------------
# the streaming cohort core: flat accumulation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_ref", [False, True])
def test_accumulate_pass_matches_formula_and_vjp(key, use_ref):
    """acc + w*g forward (Pallas == ref == jnp) and the custom VJP
    (d_acc identity, dg = w d_out, dw = <g, d_out>) == XLA autodiff."""
    rng = np.random.default_rng(3)
    acc = jnp.asarray(rng.normal(0, 1, (16, F.LANES)), jnp.float32)
    g = jnp.asarray(rng.normal(0, 1, (16, F.LANES)), jnp.float32)
    w = jnp.float32(0.37)
    got = (R.accumulate_ref(acc, g, w) if use_ref
           else K.accumulate_pass(acc, g, w, interpret=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc + w * g),
                               rtol=1e-6, atol=1e-6)

    accum = O.flat_accumulate(use_ref=use_ref, interpret=True)
    obj = lambda a, gg, ww: jnp.sum(jnp.sin(accum(a, gg, ww)))
    ref = lambda a, gg, ww: jnp.sum(jnp.sin(a + ww * gg))
    got_g = jax.grad(obj, argnums=(0, 1, 2))(acc, g, w)
    want_g = jax.grad(ref, argnums=(0, 1, 2))(acc, g, w)
    assert_grads_close(got_g, want_g)


# the accumulate formula as XLA computes it, with no kernel and no alias
fma = jax.jit(lambda a, g, w: a + w * g)


@pytest.mark.parametrize("jit", [False, True])
def test_accumulate_pass_keeps_a_live_accumulator(jit):
    """The kernel writes into ``acc``'s buffer; a caller that keeps ``acc``
    alive still reads it unchanged (XLA copies it first), and both calls on
    the one ``acc`` give exactly ``acc + w * g``."""
    rng = np.random.default_rng(5)
    acc, g1, g2 = (jnp.asarray(rng.normal(0, 1, (16, F.LANES)), jnp.float32)
                   for _ in range(3))
    acc_before = np.asarray(acc).copy()
    w1, w2 = jnp.float32(0.37), jnp.float32(-1.25)

    def two(a, x, y):
        return (K.accumulate_pass(a, x, w1, interpret=True),
                K.accumulate_pass(a, y, w2, interpret=True), a)

    out1, out2, acc_in = (jax.jit(two) if jit else two)(acc, g1, g2)
    np.testing.assert_array_equal(np.asarray(acc_in), acc_before)
    np.testing.assert_array_equal(np.asarray(acc), acc_before)
    np.testing.assert_array_equal(np.asarray(out1),
                                  np.asarray(fma(acc, g1, w1)))
    np.testing.assert_array_equal(np.asarray(out2),
                                  np.asarray(fma(acc, g2, w2)))


@pytest.mark.parametrize("chunk", [1, 3])
def test_streamed_aggregate_bitmatches_folded_formula(key, chunk):
    """The streaming core's aggregate (accumulator updated in place) ==
    ``acc + w_k * g_k`` folded over the per-client flat gradients in client
    order, bit for bit; chunk 3 of a cohort of 5 is the ragged case."""
    from repro.core.aggregate import (chunked_cohort_gradient_flat,
                                      scan_cohort_deltas_flat)
    model = make_mlp_model()
    params = model.init(key)
    spec = F.make_flat_spec(params)
    rng = np.random.default_rng(6)
    batch = sample_batch(rng, cohort=5, b=16)
    wts = jnp.asarray(rng.uniform(1.0, 5.0, 5), jnp.float32)
    cu = make_client_update("uga", model.loss, local_steps=2)

    G, _ = jax.jit(lambda p: chunked_cohort_gradient_flat(
        cu, p, batch, wts, 0.05, key, spec=spec, chunk=chunk))(params)
    deltas, _ = jax.jit(lambda p: scan_cohort_deltas_flat(
        cu, p, batch, wts, 0.05, key, spec=spec))(params)
    wn = wts / jnp.maximum(jnp.sum(wts), 1e-30)
    want = F.zeros_flat(spec)
    for k in range(5):
        want = [fma(a, d[k], wn[k]) for a, d in zip(want, deltas)]
    assert len(G) == len(want)
    for a, b in zip(G, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def tree_scan_cohort_gradient(client_update, w_t, cohort_batch,
                              client_weights, lr, rng):
    """Plain pytree-carry cohort scan: one client at a time, ``acc + w_k *
    g_k`` per leaf in fp32 with the weights normalized before the scan, the
    weighted loss summed in client order."""
    cohort = client_weights.shape[0]
    rngs = jax.random.split(rng, cohort)
    w32 = client_weights.astype(jnp.float32)
    wn = w32 / jnp.maximum(jnp.sum(w32), 1e-30)

    def body(carry, inp):
        G_acc, l_acc = carry
        batch, wk, r = inp
        g_k, l_k = client_update(w_t, batch, lr, r)
        G_acc = jax.tree.map(
            lambda a, g: a + wk * g.astype(jnp.float32), G_acc, g_k)
        return (G_acc, l_acc + wk * l_k), None

    G0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), w_t)
    (G, mean_loss), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (G0, jnp.zeros((), jnp.float32)), (cohort_batch, wn, rngs))
    return G, mean_loss


@pytest.mark.parametrize("use_ref", [False, True])
@pytest.mark.parametrize("algo", ["uga", "fedavg"])
def test_scan_flat_cohort_bitmatches_legacy_carry(key, use_ref, algo):
    """The streaming core at chunk 1 is the SAME fp32 math in the same
    client order as a plain pytree carry — the aggregate and the weighted
    client loss must match bit for bit."""
    model = make_mlp_model()
    params = model.init(key)
    spec = F.make_flat_spec(params)
    rng = np.random.default_rng(4)
    batch = sample_batch(rng, cohort=4, b=16)
    wts = jnp.asarray(rng.uniform(1.0, 5.0, 4), jnp.float32)
    cu = make_client_update(algo, model.loss, local_steps=2)

    G_legacy, l_legacy = jax.jit(lambda p: tree_scan_cohort_gradient(
        cu, p, batch, wts, 0.05, key))(params)
    G_flat, l_flat = jax.jit(lambda p: chunked_cohort_gradient_flat(
        cu, p, batch, wts, 0.05, key, spec=spec, chunk=1,
        use_ref=use_ref))(params)
    G_flat_tree = F.unflatten_tree(spec, G_flat)
    np.testing.assert_array_equal(np.asarray(l_flat), np.asarray(l_legacy))
    for a, b in zip(jax.tree.leaves(G_flat_tree), jax.tree.leaves(G_legacy)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("opt", ["sgd", "sgdm"])
@pytest.mark.parametrize("clip", [0.0, 1.0])
def test_scan_fused_round_matches_legacy_scan_round(key, opt, clip):
    """Full round, cohort_strategy='scan': fused flat streaming == legacy
    pytree carry to <= 1e-5 relative on params and round metrics (smooth
    optimizers; adam/yogi are gated warm-state below, same as the vmap
    engine's sign-step caveat)."""
    model = make_mlp_model()
    rng = np.random.default_rng(0)
    batch = sample_batch(rng, cohort=4, b=16)
    meta = {"x": batch["x"][0], "y": batch["y"][0]}
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    kw = dict(algorithm="uga", meta=True, cohort=4, local_steps=2,
              client_lr=0.05, server_lr=0.1, meta_lr=0.05, server_opt=opt,
              clip_norm=clip, cohort_strategy="scan")
    states, metrics = {}, {}
    for fused in (False, True):
        fed = FedConfig(fused_update=fused, **kw)
        rf = jax.jit(make_federated_round(model, fed))
        st = init_server_state(model, fed, key)
        states[fused], metrics[fused] = rf(st, batch, meta, wts, key)
    for k in states[False]["params"]:
        a = np.asarray(states[True]["params"][k])
        b = np.asarray(states[False]["params"][k])
        rel = np.max(np.abs(a - b) / (np.abs(b) + 1e-6))
        assert rel <= 1e-5, (opt, clip, k, rel)
    for name in ("client_loss", "grad_norm", "meta_loss"):
        np.testing.assert_allclose(float(metrics[True][name]),
                                   float(metrics[False][name]),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("opt", ["adam", "yogi"])
@pytest.mark.parametrize("clip", [0.0, 1.0])
def test_scan_fused_round_matches_legacy_warm_adam_yogi(key, opt, clip):
    """adam/yogi arm of the scan bit-compat gate, warm (t=5) opt state: at
    t=1 from zeros the step saturates to lr*sign(g) whose params are ulp-
    unstable in ANY engine (the documented vmap caveat); warm state makes
    the comparison well-conditioned and both paths must agree <= 1e-5."""
    model = make_mlp_model()
    params0 = model.init(key)
    spec = F.make_flat_spec(params0)
    rng = np.random.default_rng(1)
    batch = sample_batch(rng, cohort=4, b=16)
    meta = {"x": batch["x"][0], "y": batch["y"][0]}
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    m_tree = jax.tree.map(
        lambda p: 0.3 * jax.random.normal(jax.random.fold_in(key, p.size + 3),
                                          p.shape), params0)
    v_tree = jax.tree.map(
        lambda p: 0.1 + jnp.abs(jax.random.normal(
            jax.random.fold_in(key, p.size + 4), p.shape)), params0)
    kw = dict(algorithm="uga", meta=True, cohort=4, local_steps=2,
              client_lr=0.05, server_lr=0.1, meta_lr=0.05, server_opt=opt,
              clip_norm=clip, cohort_strategy="scan")
    states = {}
    for fused in (False, True):
        fed = FedConfig(fused_update=fused, **kw)
        st = init_server_state(model, fed, key)
        if fused:
            st["opt"] = {"m": tuple(F.flatten_tree(spec, m_tree)),
                         "v": tuple(F.flatten_tree(spec, v_tree)),
                         "t": jnp.asarray(5, jnp.int32)}
        else:
            st["opt"] = {"m": m_tree, "v": v_tree,
                         "t": jnp.asarray(5, jnp.int32)}
        rf = jax.jit(make_federated_round(model, fed))
        states[fused], _ = rf(st, batch, meta, wts, key)
    for k in states[False]["params"]:
        a = np.asarray(states[True]["params"][k])
        b = np.asarray(states[False]["params"][k])
        rel = np.max(np.abs(a - b) / (np.abs(b) + 1e-6))
        assert rel <= 1e-5, (opt, clip, k, rel)


@pytest.mark.parametrize("fused", [False, True])
def test_scan_rounds_per_call_matches_sequential(key, fused):
    """The scanned multi-round driver composes with the scan cohort
    strategy (nested lax.scan: rounds over clients)."""
    model = make_mlp_model()
    Kr = 3
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt="sgdm", clip_norm=1.0, lr_decay=0.9,
                    cohort_strategy="scan", fused_update=fused)
    rng = np.random.default_rng(1)
    batches = [sample_batch(rng, cohort=4, b=16) for _ in range(Kr)]
    metas = [{"x": b["x"][0], "y": b["y"][0]} for b in batches]
    wts = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    rngs = jnp.stack([jax.random.fold_in(key, r) for r in range(Kr)])

    rf1 = jax.jit(make_federated_round(model, fed))
    st = init_server_state(model, fed, key)
    for r in range(Kr):
        st, _ = rf1(st, batches[r], metas[r], wts, rngs[r])

    rfK = jax.jit(make_federated_round(model, fed, rounds_per_call=Kr))
    stK = init_server_state(model, fed, key)
    stK, mK = rfK(stK,
                  jax.tree.map(lambda *xs: jnp.stack(xs), *batches),
                  jax.tree.map(lambda *xs: jnp.stack(xs), *metas),
                  jnp.stack([wts] * Kr), rngs)
    assert int(stK["round"]) == int(st["round"]) == Kr
    for a, b in zip(jax.tree.leaves(stK["params"]),
                    jax.tree.leaves(st["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("algo", ["uga", "fedavg"])
def test_epoch_cycling_equals_tiled_path(key, seed, algo):
    """local_epochs=E with the in-scan modulo schedule must equal the old
    materialized path, which is exactly local_steps*E steps over the
    example-tiled batch (same microbatch sequence, same step rngs)."""
    model = make_mlp_model()
    rng = np.random.default_rng(seed)
    batch = {"x": jnp.asarray(rng.normal(0, 1, (16, 10)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 4, 16), jnp.int32)}
    steps, epochs = 2, 3
    fn = uga_update if algo == "uga" else fedavg_update
    g_new, l_new = fn(model.loss, model.init(key), batch, 0.05,
                      local_steps=steps, local_epochs=epochs)
    g_old, l_old = fn(model.loss, model.init(key), _tile_batch(batch, epochs),
                      0.05, local_steps=steps * epochs, local_epochs=1)
    np.testing.assert_allclose(float(l_new), float(l_old),
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
