"""Round/driver-level tests for the communication-compression subsystem:

  * codec='none' (the default) matches the plain tree-map reference round
    on every {legacy, fused} x {vmap, scan} combination to fp32
    reassociation — reusing test_plugin_api's reference round as the
    oracle, so the codec wiring cannot perturb the uncompressed paths;
  * vmap and scan executors agree under every lossy codec (+/- EF);
  * measured comm_bytes metric == the transport arithmetic, int8 <= 30%
    of fp32;
  * error-feedback: residual norm non-increasing on a quadratic, and the
    state["comm"] slot checkpoint/resumes bit-identically mid-run;
  * capability guards: lossy codecs reject through_aggregation and the
    legacy_tree engine with actionable errors (sharded cohorts now BUILD —
    the two-tier executor streams a per-client uplink); error_feedback
    rejects codec='none';
  * satellite regression: participation Bernoulli streams are bit-equal
    across rounds_per_call in {1, 4} (audit result: the mask folds off the
    PER-ROUND rng — which the chunked scan threads per round — so chunking
    cannot perturb it; this test pins that);
  * the fedagg example plugin composes with codecs end to end.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import comm_bytes_per_client, resolve_codec
from repro.configs.base import FedConfig
from repro.core import (FederatedTrainer, init_server_state,
                        make_federated_round)
from repro.core.flat import flat_sq_norm, make_flat_spec
from repro.models.model import Model
from test_plugin_api import (_round_inputs, _toy_fed_data, assert_close,
                             make_mlp_model, make_reference_round,
                             tree_equal)


# ---------------------------------------------------------------------------
# codec='none' == the reference round (equivalence-matrix style)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused,strat", [(False, "vmap"), (False, "scan"),
                                         (True, "vmap"), (True, "scan")])
def test_codec_none_bit_identical_to_precodec_round(key, fused, strat):
    """An EXPLICIT codec='none' round == the plain tree-map reference round
    to fp32 reassociation, on every executor/engine — and it must neither
    emit comm metrics nor grow a comm state slot."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt="adam", clip_norm=1.0, lr_decay=0.9,
                    cohort_strategy=strat, fused_update=fused,
                    codec="none")
    batch, meta, wts = _round_inputs()
    new_rf = jax.jit(make_federated_round(model, fed))
    ref_rf = jax.jit(make_reference_round(model, fed))
    st_new = init_server_state(model, fed, key)
    assert "comm" not in st_new
    st_ref = jax.tree.map(jnp.copy, st_new)
    for r in range(2):
        st_new, m_new = new_rf(st_new, batch, meta, wts,
                               jax.random.fold_in(key, r))
        st_ref, m_ref = ref_rf(st_ref, batch, meta, wts,
                               jax.random.fold_in(key, r))
    assert jax.tree.structure(st_new) == jax.tree.structure(st_ref)
    assert_close(st_new, st_ref)
    assert "comm_bytes" not in m_new
    assert sorted(m_new) == sorted(m_ref)
    for name in m_new:
        assert_close(m_new[name], m_ref[name])


# ---------------------------------------------------------------------------
# lossy codecs: executor agreement + measured bytes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec,ef", [("int8", False), ("int8", True),
                                      ("sign1bit", True), ("topk", True)])
def test_vmap_and_scan_coded_rounds_agree(key, codec, ef):
    """Both executors run the identical per-client encode/decode/accumulate
    math (same clients, same order), so coded rounds agree to fp32
    reduction noise across strategies."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    outs = {}
    for strat in ("vmap", "scan"):
        fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                        client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                        clip_norm=1.0, cohort_strategy=strat,
                        fused_update=True, codec=codec, error_feedback=ef)
        st = init_server_state(model, fed, key)
        assert ("comm" in st) == ef
        st, m = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        outs[strat] = (st, m)
    for a, b in zip(jax.tree.leaves(outs["vmap"][0]),
                    jax.tree.leaves(outs["scan"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert float(outs["vmap"][1]["comm_bytes"]) == \
        float(outs["scan"][1]["comm_bytes"])


def test_comm_bytes_metric_measures_transport(key):
    """comm_bytes == cohort * sum-over-groups payload bytes, and the int8
    uplink is <= 30% of shipping fp32 (the acceptance budget)."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    spec = make_flat_spec(model.init(key))
    fp32 = comm_bytes_per_client(resolve_codec(None, codec="none"), spec)
    for codec in ("int8", "sign1bit", "topk"):
        fed = FedConfig(algorithm="uga", meta=False, cohort=4,
                        local_steps=2, client_lr=0.05, fused_update=True,
                        codec=codec)
        st = init_server_state(model, fed, key)
        _, m = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        expect = 4 * comm_bytes_per_client(resolve_codec(fed), spec)
        assert float(m["comm_bytes"]) == float(expect), codec
        if codec == "int8":
            assert float(m["comm_bytes"]) <= 0.30 * 4 * fp32


def test_comm_bytes_counts_only_participants(key):
    """Under participation<1 only reporting clients ship bytes."""
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    fed = FedConfig(algorithm="uga", meta=False, cohort=4, local_steps=2,
                    client_lr=0.05, fused_update=True, codec="int8",
                    participation=0.5)
    st = init_server_state(model, fed, key)
    _, m = jax.jit(make_federated_round(model, fed))(
        st, batch, meta, wts, key)
    spec = make_flat_spec(model.init(key))
    per_client = comm_bytes_per_client(resolve_codec(fed), spec)
    assert float(m["comm_bytes"]) == \
        float(m["participants"]) * per_client


# ---------------------------------------------------------------------------
# error feedback: contraction + checkpoint/resume
# ---------------------------------------------------------------------------
def make_quadratic_model(d=24):
    """L(w) = 0.5 ||w - t||^2 per client target t — gradients decay along
    training, so EF residuals (one quantization error behind) must not
    grow."""
    def init(k):
        return {"w": jax.random.normal(k, (d,)) * 2.0}

    def loss(w, batch, rng=None):
        diff = w["w"][None, :] - batch["t"]
        return 0.5 * jnp.mean(jnp.sum(diff * diff, axis=-1)), {}

    return Model(name="quad", init=init, loss=loss)


@pytest.mark.parametrize("codec", ["int8", "sign1bit"])
def test_error_feedback_residual_contraction_on_quadratic(key, codec):
    """Residual norm is non-increasing after the short EF warm-up on the
    quadratic: the memory builds to its steady-state fraction of ||g||
    over the first ~3 rounds, then never makes a new high and contracts
    with the decaying gradient — and training still converges."""
    model = make_quadratic_model()
    rng = np.random.default_rng(0)
    batch = {"t": jnp.asarray(rng.normal(0, 1, (4, 8, 24)), jnp.float32)}
    wts = jnp.ones((4,), jnp.float32)
    fed = FedConfig(algorithm="uga", meta=False, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.3, fused_update=True,
                    codec=codec, error_feedback=True)
    st = init_server_state(model, fed, key)
    rf = jax.jit(make_federated_round(model, fed))
    norms, losses = [], []
    for r in range(14):
        st, m = rf(st, batch, None, wts, jax.random.fold_in(key, r))
        norms.append(float(jnp.sqrt(sum(
            float(flat_sq_norm([b])) for b in st["comm"]["residual"]))))
        losses.append(float(m["client_loss"]))
    peak = max(norms)
    assert norms.index(peak) <= 2, norms        # growth only during warm-up
    assert norms[-1] <= 0.6 * peak, norms       # genuine contraction after
    assert losses[-1] < 0.25 * losses[0]


@pytest.mark.parametrize("strat", ["vmap", "scan"])
def test_resume_with_comm_state_continues_bit_identically(key, tmp_path,
                                                          strat):
    """save at round 2 of 6 with state['comm'] populated, restore into a
    FRESH trainer, finish: the EF residuals round-trip the msgpack
    checkpoint and the tail == the uninterrupted run, bit for bit."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    server_opt="adam", cohort_strategy=strat,
                    fused_update=True, codec="int8", error_feedback=True)
    data = _toy_fed_data()
    kw = dict(cohort=4, batch=16, meta_batch=8)

    straight = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    full_hist = straight.run(data, rounds=6, **kw)

    part = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    part.run(data, rounds=2, **kw)
    assert float(flat_sq_norm(part.state["comm"]["residual"])) > 0.0
    path = os.path.join(tmp_path, "state.msgpack")
    part.save(path, extra={"arch": "mlp"})

    resumed = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    resumed.restore(path)
    tail = resumed.run(data, rounds=6, **kw)
    assert tree_equal(resumed.state, straight.state)
    assert tail == full_hist[2:]


@pytest.mark.parametrize("strat", ["vmap", "scan"])
def test_dropped_clients_keep_their_ef_residual(key, strat):
    """EF x participation: a straggler dropped by the participation mask
    did NOT transmit, so its error-feedback memory must stay byte-for-byte
    unchanged that round — overwriting it would discard the decoded part
    of the error as if the server had received it (regression for the EF
    telescoping under partial participation)."""
    from repro.core import participation_mask
    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    fed = FedConfig(algorithm="uga", meta=False, cohort=4, local_steps=2,
                    client_lr=0.05, fused_update=True, codec="int8",
                    error_feedback=True, participation=0.5,
                    cohort_strategy=strat)
    mask = np.asarray(participation_mask(key, 4, 0.5))
    assert 0 < mask.sum() < 4, "seed gives a non-trivial mask"
    st = init_server_state(model, fed, key)
    st, _ = jax.jit(make_federated_round(model, fed))(
        st, batch, meta, wts, key)
    for buf in st["comm"]["residual"]:
        res = np.asarray(buf)                       # (cohort, rows, LANES)
        np.testing.assert_array_equal(res[mask == 0.0], 0.0)
        assert np.all(np.any(res[mask == 1.0] != 0.0, axis=(1, 2)))


# ---------------------------------------------------------------------------
# capability guards
# ---------------------------------------------------------------------------
def test_error_feedback_requires_lossy_codec():
    with pytest.raises(ValueError, match="error_feedback"):
        FedConfig(error_feedback=True)                  # codec defaults none


def test_lossy_codec_rejects_through_aggregation():
    with pytest.raises(ValueError, match="through_aggregation"):
        FedConfig(meta=True, meta_mode="through_aggregation",
                  fused_update=True, codec="int8")


def test_lossy_codec_rejects_legacy_tree_engine():
    with pytest.raises(ValueError, match="fused_update"):
        FedConfig(codec="int8")                         # legacy engine


def test_lossy_codec_on_sharded_cohorts_builds(key):
    """Sharded cohorts used to reject lossy codecs (no per-client uplink
    after the per-leaf pre-aggregate); the two-tier sharded executor runs
    the chunk-local decode-FMA, so the same config now builds."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=False, cohort=2, local_steps=2,
                    fused_update=True, codec="sign1bit")
    round_fn = make_federated_round(model, fed,
                                    grad_shardings={"w1": None,
                                                    "w2": None})
    assert callable(round_fn)


def test_unknown_codec_actionable_at_config_time():
    with pytest.raises(ValueError, match="register_codec"):
        FedConfig(codec="gzip")
    with pytest.raises(ValueError, match="topk_ratio"):
        FedConfig(topk_ratio=0.0)


# ---------------------------------------------------------------------------
# satellite: participation streams vs rounds_per_call chunking
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strat", ["vmap", "scan"])
def test_participation_stream_bit_equal_across_rounds_per_call(strat):
    """participation<1 + cohort_strategy=scan (and vmap): the Bernoulli
    mask folds off each ROUND's rng, which the rounds_per_call lax.scan
    threads per round, so chunk size must not perturb the participation
    stream.  Audit regression: history AND final state bit-equal across
    rounds_per_call in {1, 4}."""
    model = make_mlp_model()
    fed = FedConfig(algorithm="uga", meta=True, cohort=4, local_steps=2,
                    client_lr=0.05, server_lr=0.1, meta_lr=0.05,
                    cohort_strategy=strat, fused_update=True,
                    participation=0.5)
    data = _toy_fed_data()
    runs = {}
    for k in (1, 4):
        tr = FederatedTrainer(model, fed, rounds_per_call=k, seed=0)
        hist = tr.run(data, rounds=4, cohort=4, batch=16, meta_batch=8)
        runs[k] = (hist, tr.state)
    assert runs[1][0] == runs[4][0]
    assert tree_equal(runs[1][1], runs[4][1])
    # the stream is non-trivial (some round actually dropped a client)
    assert any(h["participants"] < 4 for h in runs[1][0])


# ---------------------------------------------------------------------------
# fedagg example plugin x codec composition
# ---------------------------------------------------------------------------
def test_fedagg_plugin_composes_with_codecs(key):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import importlib
    importlib.import_module("examples.plugins.fedagg")

    model = make_mlp_model()
    batch, meta, wts = _round_inputs()
    p0 = model.init(key)

    def delta_norm(algo, codec="none", ef=False):
        fed = FedConfig(algorithm=algo, meta=False, cohort=4, local_steps=2,
                        client_lr=0.05, fused_update=True, codec=codec,
                        error_feedback=ef)
        st = init_server_state(model, fed, key)
        st, m = jax.jit(make_federated_round(model, fed))(
            st, batch, meta, wts, key)
        assert np.isfinite(float(m["client_loss"]))
        return float(jnp.sqrt(sum(
            jnp.sum(jnp.square(a - b)) for a, b in
            zip(jax.tree.leaves(st["params"]), jax.tree.leaves(p0)))))

    # drift damping: a_k = 1/(1 + ||delta_k||) < 1 strictly shrinks the
    # aggregated step vs fedavg on the same cohort
    assert delta_norm("fedagg") < delta_norm("fedavg")
    # and the adaptive weighting composes with a lossy uplink end to end
    assert delta_norm("fedagg", codec="int8", ef=True) > 0.0
