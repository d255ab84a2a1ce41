"""Chunked streaming cohort core (the one core vmap/scan/sharded register
over) — the properties the refactor must keep forever:

  * chunk-size invariance, BITWISE: the streaming core accumulates clients
    in global cohort order whatever ``cohort_chunk`` is, so the chunk size
    can never change a round — params, opt state, ctrl and every metric
    agree across chunk in {1, 3, cohort} on {legacy_tree, fused_flat} x
    {post, through_aggregation}, including rounds_per_call > 1 and the
    ragged cohort % chunk != 0 case (zero-weight padding);
  * vmap and scan are pins of the same core: the vmap executor runs it at
    chunk = cohort and the scan executor at chunk = 1, so each equals the
    chunked executor at that chunk bitwise (and chunk = cohort is the
    default FedConfig's round);
  * rng audit: the participation and fault streams fold out of the ROUND
    rng before the executor runs, so partial participation and fault
    injection are chunking-invariant bitwise (counts and state);
  * two-tier sharded topology == chunked bitwise on a debug mesh, with
    lossy codec + error feedback (residual carry) and through_aggregation
    ctrl hypergradients;
  * guards: cohort_chunk=0, cohort_chunk + cohort_strategy='scan' (config
    time, naming both fields), cohort_chunk + buffered_async (build time).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import init_server_state, make_federated_round
from repro.launch.mesh import make_debug_mesh
from repro.sharding.specs import cohort_grad_shardings

from test_plugin_api import make_mlp_model, sample_batch, tree_equal

COHORT = 5          # chunk=3 is the ragged case: 5 % 3 != 0


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def _inputs(seed=0, cohort=COHORT, b=8):
    rng = np.random.default_rng(seed)
    batch = sample_batch(rng, cohort, b)
    meta = {"x": jnp.asarray(rng.normal(0, 1, (8, 10)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 4, 8), jnp.int32)}
    wts = jnp.asarray(rng.uniform(1.0, 5.0, cohort), jnp.float32)
    return batch, meta, wts


def _fed(chunk=None, *, fused=True, mode="post", cohort=COHORT, **kw):
    return FedConfig(algorithm="uga", meta=True, cohort=cohort,
                     local_steps=2, client_lr=0.05, server_lr=0.1,
                     meta_lr=0.05, clip_norm=1.0, lr_decay=0.9,
                     fused_update=fused, meta_mode=mode,
                     cohort_chunk=chunk, **kw)


def _run(model, fed, key, *, rounds=2, rounds_per_call=1, seed_inputs=0,
         **mk_kwargs):
    """Chained rounds (round-1 state feeds round 2) -> (state, metrics)."""
    rf = jax.jit(make_federated_round(model, fed,
                                      rounds_per_call=rounds_per_call,
                                      **mk_kwargs))
    batch, meta, wts = _inputs(seed_inputs, cohort=fed.cohort)
    if rounds_per_call > 1:
        stack = lambda t: jax.tree.map(
            lambda x: jnp.stack([x] * rounds_per_call), t)
        batch, meta = stack(batch), stack(meta)
        wts = jnp.stack([wts] * rounds_per_call)
    state = init_server_state(model, fed, key)
    metrics = None
    for r in range(rounds):
        rngs = jax.random.fold_in(key, r)
        if rounds_per_call > 1:
            rngs = jnp.stack([jax.random.fold_in(rngs, k)
                              for k in range(rounds_per_call)])
        state, metrics = rf(state, batch, meta, wts, rngs)
    return state, metrics


def _assert_identical(out_a, out_b):
    (st_a, m_a), (st_b, m_b) = out_a, out_b
    assert tree_equal(st_a, st_b)
    assert sorted(m_a) == sorted(m_b)
    for name in m_a:
        np.testing.assert_array_equal(np.asarray(m_a[name]),
                                      np.asarray(m_b[name]), err_msg=name)


# ---------------------------------------------------------------------------
# chunk-size invariance matrix (bitwise)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused,mode",
                         [(False, "post"),            # legacy_tree engine
                          (True, "post"),             # fused_flat engine
                          (True, "through_aggregation")])
def test_chunk_invariance_matrix_bitwise(key, fused, mode):
    """params + opt + ctrl + every metric identical across chunk sizes,
    two chained rounds; chunk=3 exercises the ragged zero-weight pad."""
    model = make_mlp_model()
    outs = {c: _run(model, _fed(c, fused=fused, mode=mode), key)
            for c in (1, 3, COHORT)}
    _assert_identical(outs[1], outs[3])
    _assert_identical(outs[3], outs[COHORT])


def test_chunk_invariance_rounds_per_call(key):
    """Same gate under the K-chunked round driver (lax.scan over rounds
    wrapping lax.scan over chunks)."""
    model = make_mlp_model()
    outs = {c: _run(model, _fed(c), key, rounds=1, rounds_per_call=2)
            for c in (1, 3, COHORT)}
    _assert_identical(outs[1], outs[3])
    _assert_identical(outs[3], outs[COHORT])


def test_ragged_final_chunk_pads_with_zero_weight(key):
    """Regression for the ragged pad: the pad slot replicates client 0's
    batch with aggregation weight 0, so doubling client 0's weight in the
    REAL slots changes the round, while the pad slot never contributes —
    ragged == exact-divisor bitwise even when client 0 dominates."""
    model = make_mlp_model()
    batch, meta, wts = _inputs()
    wts = wts.at[0].set(100.0)  # if the pad (a client-0 copy) leaked into
    #                             the weighted mean, ragged would diverge
    fed_r, fed_e = _fed(3), _fed(COHORT)
    st = init_server_state(model, fed_r, key)
    rng = jax.random.fold_in(key, 0)
    out_r = jax.jit(make_federated_round(model, fed_r))(
        st, batch, meta, wts, rng)
    out_e = jax.jit(make_federated_round(model, fed_e))(
        st, batch, meta, wts, rng)
    _assert_identical(out_r, out_e)


# ---------------------------------------------------------------------------
# vmap and scan: chunk-size pins of the one core
# ---------------------------------------------------------------------------
def test_chunk1_matches_scan_strategy_bitwise(key):
    """chunk=1 IS the scan executor's round."""
    model = make_mlp_model()
    _assert_identical(_run(model, _fed(1), key),
                      _run(model, _fed(None, cohort_strategy="scan"), key))


@pytest.mark.parametrize("mode", ["post", "through_aggregation"])
def test_chunk_eq_cohort_matches_vmap(key, mode):
    """chunk=cohort vs the default FedConfig (cohort_chunk=None, the vmap
    executor): the same core at the same chunk, identical to the bit."""
    model = make_mlp_model()
    _assert_identical(_run(model, _fed(COHORT, mode=mode), key),
                      _run(model, _fed(None, mode=mode), key))


@pytest.mark.parametrize("mode", ["post", "through_aggregation"])
@pytest.mark.parametrize("strategy,chunk", [("vmap", COHORT), ("scan", 1)])
def test_vmap_and_scan_are_chunk_pins(key, strategy, chunk, mode):
    """The vmap and scan registrations resolve to the chunked core pinned at
    chunk = cohort and chunk = 1, and two chained rounds equal the chunked
    executor at that chunk: params, opt, ctrl and every metric, bitwise."""
    from repro.core.executors import ChunkedExecutor, resolve_executor
    fed = _fed(None, mode=mode, cohort_strategy=strategy)
    exe = resolve_executor(fed)
    assert exe.name == strategy and isinstance(exe, ChunkedExecutor)
    assert exe._chunk_for(COHORT) == chunk
    model = make_mlp_model()
    _assert_identical(_run(model, fed, key),
                      _run(model, _fed(chunk, mode=mode), key))


# ---------------------------------------------------------------------------
# rng audit: participation / fault streams are chunking-invariant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("knobs", [dict(participation=0.6),
                                   dict(fault_profile="flaky"),
                                   dict(participation=0.6,
                                        fault_profile="flaky")])
def test_rng_streams_chunking_invariant(key, knobs):
    """The participation mask and fault streams fold out of the ROUND rng
    before the executor runs (weight zeroing), so which clients drop — and
    the participants/arrivals/fault_* counts — cannot depend on the chunk
    size; neither can the per-client training rng streams."""
    model = make_mlp_model()
    outs = {c: _run(model, _fed(c, cohort=8, **knobs), key)
            for c in (1, 3, 8)}
    _assert_identical(outs[1], outs[3])
    _assert_identical(outs[3], outs[8])
    audited = {"participants", "arrivals", "fault_crashed", "fault_dropped"}
    assert audited & set(outs[8][1]), sorted(outs[8][1])


# ---------------------------------------------------------------------------
# two-tier sharded topology
# ---------------------------------------------------------------------------
def _sharded_kwargs(model, key):
    mesh = make_debug_mesh(1, 1)
    shape = jax.eval_shape(model.init, key)
    return {"grad_shardings": cohort_grad_shardings(shape, mesh)}


@pytest.mark.parametrize("mode", ["post", "through_aggregation"])
def test_sharded_two_tier_matches_chunked_bitwise(key, mode):
    """shard_map + psum partial accumulators reduce to the same flat
    buffers as the single-host streaming core (incl. the ctrl
    hypergradients through the aggregation)."""
    model = make_mlp_model()
    _assert_identical(
        _run(model, _fed(3, mode=mode), key, **_sharded_kwargs(model, key)),
        _run(model, _fed(3, mode=mode), key))


def test_sharded_lossy_codec_error_feedback_matches_chunked(key):
    """sharded declares 'lossy' codec capability: int8 + error feedback
    streams per-client residuals through the two-tier topology — residual
    carry across chained rounds matches the chunked executor bitwise."""
    model = make_mlp_model()
    kw = dict(codec="int8", error_feedback=True)
    _assert_identical(
        _run(model, _fed(3, **kw), key, **_sharded_kwargs(model, key)),
        _run(model, _fed(3, **kw), key))


def test_sharded_supports_reweight_capability():
    from repro.core.executors import get_executor
    fac = get_executor("sharded")
    ex = fac(_fed(3))
    assert ex.supports_reweight
    assert "lossy" in ex.codec_capabilities


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------
def test_cohort_chunk_must_be_positive():
    with pytest.raises(ValueError, match="cohort_chunk"):
        _fed(0)
    with pytest.raises(ValueError, match="cohort_chunk"):
        _fed(-2)
    assert _fed(7).cohort_chunk == 7          # > cohort is fine (one chunk)


def test_cohort_chunk_with_scan_strategy_names_both_fields():
    with pytest.raises(ValueError) as e:
        _fed(2, cohort_strategy="scan")
    assert "cohort_chunk" in str(e.value)
    assert "cohort_strategy" in str(e.value)


def test_buffered_async_rejects_cohort_chunk(key):
    model = make_mlp_model()
    fed = dataclasses.replace(_fed(2, fused=True), meta=False,
                              engine="buffered_async", async_buffer=2)
    with pytest.raises(ValueError, match="cohort_chunk"):
        make_federated_round(model, fed)
