"""Fault-tolerant async federation runtime (PR 6):

  * buffered_async bit-identity: a FAULT-FREE tick with K = capacity =
    cohort over the scan base reproduces the synchronous fused-scan round
    bit-exactly (params + opt + meta), and tracks the vmap base to fp32
    reduction tolerance;
  * fault determinism: the seeded fault streams are pure functions of the
    round rng (invariant to rounds_per_call chunking, distinct per round);
  * EF interaction: a crashed/dropped client's ``state["comm"]`` residual
    slot stays byte-identical (it never transmitted);
  * degradation policy: an all-dropped round (participation mask or
    faults) leaves params/opt bit-unchanged on every executor x engine,
    the trainer's retry-with-backoff re-enqueues failed clients, and
    ``sample_round(include=...)`` lands them without perturbing the
    retry-free sampling streams;
  * crash-safe checkpointing: a failed save leaves the previous
    checkpoint restorable (atomic rename, no temp litter), and truncated /
    corrupted blobs fail with errors naming the path and what was
    expected; a mid-run async save/resume (pool + staleness counters
    included) is bit-identical to never stopping;
  * config guards: K > capacity deadlock, round_deadline under async,
    explicit garble on a sync engine, unknown staleness_mode.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore as ckpt_restore
from repro.checkpoint import save as ckpt_save
from repro.configs.base import FedConfig
from repro.core import (FederatedTrainer, init_server_state,
                        make_federated_round, staleness_discount)
from repro.data.pipeline import FederatedData
from repro.models.model import Model
from repro.sim.faults import (FAULT_PROFILES, FaultConfig, fault_streams,
                              heavy_tail_speeds, resolve_faults)

COHORT, BATCH = 4, 16


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


def _round_inputs(seed=0, cohort=COHORT, b=BATCH):
    rng = np.random.default_rng(seed)
    batch = {"x": jnp.asarray(rng.normal(0, 1, (cohort, b, 10)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, 4, (cohort, b)), jnp.int32)}
    meta = {"x": jnp.asarray(rng.normal(0, 1, (8, 10)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 4, 8), jnp.int32)}
    wts = jnp.asarray(rng.uniform(1.0, 5.0, cohort), jnp.float32)
    return batch, meta, wts


def tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _toy_fed_data(n=256, clients=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, 10)).astype(np.float32)
    y = rng.integers(0, 4, n).astype(np.int32)
    parts = np.array_split(rng.permutation(n), clients)
    meta = rng.choice(n, 32, replace=False)
    return FederatedData(arrays={"x": x, "y": y}, client_indices=parts,
                         meta_indices=meta, seed=seed)


def _run_rounds(model, fed, rounds, seed=1, **mk_kwargs):
    state = init_server_state(model, fed, jax.random.PRNGKey(seed),
                              engine=mk_kwargs.get("engine"))
    fn = jax.jit(make_federated_round(model, fed, **mk_kwargs))
    key = jax.random.PRNGKey(0)
    metrics = None
    for r in range(rounds):
        batch, meta, wts = _round_inputs(seed=r)
        state, metrics = fn(state, batch, meta, wts,
                            jax.random.fold_in(key, r))
    return state, metrics


# ---------------------------------------------------------------------------
# bit-identity of the fault-free async tick
# ---------------------------------------------------------------------------
def test_async_cleanroom_bit_identical_to_sync_scan():
    model = make_mlp_model()
    fed_sync = FedConfig(cohort=COHORT, fused_update=True,
                         cohort_strategy="scan", server_opt="adam",
                         meta=True)
    fed_async = dataclasses.replace(fed_sync, engine="buffered_async",
                                    async_buffer=COHORT,
                                    async_capacity=COHORT)
    s_sync, m_sync = _run_rounds(model, fed_sync, 3)
    s_async, m_async = _run_rounds(model, fed_async, 3)
    assert tree_equal(s_sync["params"], s_async["params"])
    assert tree_equal(s_sync["opt"], s_async["opt"])
    assert np.array_equal(np.asarray(m_sync["client_loss"]),
                          np.asarray(m_async["client_loss"]))
    assert np.array_equal(np.asarray(m_sync["meta_loss"]),
                          np.asarray(m_async["meta_loss"]))
    assert float(m_async["server_steps"]) == 1.0
    assert float(m_async["arrivals"]) == COHORT


def test_async_cleanroom_tracks_vmap_base():
    model = make_mlp_model()
    fed_sync = FedConfig(cohort=COHORT, fused_update=True,
                         cohort_strategy="vmap", meta=False)
    fed_async = dataclasses.replace(fed_sync, engine="buffered_async",
                                    async_buffer=COHORT,
                                    async_capacity=COHORT)
    s_sync, _ = _run_rounds(model, fed_sync, 2)
    s_async, _ = _run_rounds(model, fed_async, 2)
    # the vmap executor aggregates in parallel (flat_weighted_aggregate)
    # while the pool flush streams sequentially: same math, different
    # reduction order -> fp32 tolerance, not bit-identity
    for a, b in zip(jax.tree.leaves(s_sync["params"]),
                    jax.tree.leaves(s_async["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# fault streams: deterministic, chunk-invariant
# ---------------------------------------------------------------------------
def test_fault_streams_deterministic_and_per_round():
    fc = resolve_faults(FedConfig(fault_profile="flaky"))
    assert fc.active
    k0 = jax.random.fold_in(jax.random.PRNGKey(7), 0)
    k1 = jax.random.fold_in(jax.random.PRNGKey(7), 1)
    a, b = fault_streams(k0, 16, fc), fault_streams(k0, 16, fc)
    assert tree_equal(a, b)
    c = fault_streams(k1, 16, fc)
    assert not np.array_equal(np.asarray(a.latency), np.asarray(c.latency))
    # ungarbled multipliers are EXACTLY 1.0 (IEEE identity on the deltas)
    mult = np.asarray(a.garble_mult)
    garbled = np.asarray(a.garbled)
    assert np.all(mult[~garbled] == 1.0)
    # crashed and dropped are disjoint
    assert not np.any(np.asarray(a.crashed) & np.asarray(a.dropped))


@pytest.mark.parametrize("engine", [None, "buffered_async"])
def test_faulty_run_chunking_invariant(engine):
    """rounds_per_call=1 vs 3 under the flaky profile: fault streams fold
    off per-round rngs, so chunking cannot perturb them (sync AND async)."""
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=True,
                    fault_profile="flaky", engine=engine,
                    async_capacity=2 * COHORT if engine else 0)
    data = _toy_fed_data()
    final = []
    for k in (1, 3):
        tr = FederatedTrainer(model, fed, rounds_per_call=k, seed=0)
        tr.run(data, rounds=6, cohort=COHORT, batch=8, meta_batch=8)
        final.append(tr.state)
    assert tree_equal(final[0], final[1])


# ---------------------------------------------------------------------------
# EF residuals under faults
# ---------------------------------------------------------------------------
def test_crashed_client_residual_byte_identical():
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=False,
                    engine="buffered_async", async_buffer=2,
                    async_capacity=2 * COHORT, codec="int8",
                    error_feedback=True, fault_crash=0.6, fault_drop=0.2)
    state = init_server_state(model, fed, jax.random.PRNGKey(1),
                              engine="buffered_async")
    fn = jax.jit(make_federated_round(model, fed))
    faults = resolve_faults(fed)
    key = jax.random.PRNGKey(0)
    saw_failed = False
    for r in range(4):
        batch, meta, wts = _round_inputs(seed=r)
        rng = jax.random.fold_in(key, r)
        fs = fault_streams(rng, COHORT, faults)
        res_before = [np.asarray(g) for g in state["comm"]["residual"]]
        state, _ = fn(state, batch, meta, wts, rng)
        failed = ~np.asarray(fs.alive, bool)
        saw_failed = saw_failed or failed.any()
        for gb, ga in zip(res_before, state["comm"]["residual"]):
            # a client that never transmitted keeps its EF memory bitwise
            assert np.array_equal(gb[failed], np.asarray(ga)[failed])
            if (~failed).any() and r > 0:
                assert not np.array_equal(gb[~failed],
                                          np.asarray(ga)[~failed])
    assert saw_failed  # crash=0.6 over 4 rounds x 4 clients: certain-ish


# ---------------------------------------------------------------------------
# degradation policy: empty-cohort rounds, retry, include=
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy,fused", [("vmap", True), ("scan", True),
                                            ("vmap", False)])
def test_all_dropped_round_is_noop_server_step(strategy, fused):
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=fused,
                    cohort_strategy=strategy, meta=True,
                    participation=0.05)
    state = init_server_state(model, fed, jax.random.PRNGKey(1))
    fn = jax.jit(make_federated_round(model, fed))
    from repro.core import participation_mask
    key = jax.random.PRNGKey(0)
    hit = False
    for r in range(40):
        batch, meta, wts = _round_inputs(seed=r)
        rng = jax.random.fold_in(key, r)
        mask = participation_mask(rng, COHORT, fed.participation)
        before = jax.tree.map(np.asarray, state)
        state, metrics = fn(state, batch, meta, wts, rng)
        if float(jnp.sum(mask)) == 0:
            hit = True
            assert tree_equal(before["params"], state["params"])
            assert tree_equal(before["opt"], state["opt"])
            assert float(metrics["participants"]) == 0
            assert float(metrics["meta_loss"]) == 0
            assert int(state["round"]) == int(before["round"]) + 1
    assert hit, "participation=0.05 never produced an all-dropped round"


def test_sample_round_include_semantics():
    data = _toy_fed_data()
    base = data.sample_round(3, cohort=COHORT, batch=8)
    again = data.sample_round(3, cohort=COHORT, batch=8, include=None)
    empty = data.sample_round(3, cohort=COHORT, batch=8, include=[])
    assert tree_equal(base, again) and tree_equal(base, empty)
    # force specific clients in: they land, cohort size unchanged
    want = [c for c in range(data.num_clients)
            if c not in set(base["clients"].tolist())][:2]
    inc = data.sample_round(3, cohort=COHORT, batch=8, include=want)
    assert set(want) <= set(inc["clients"].tolist())
    assert len(inc["clients"]) == COHORT
    assert len(set(inc["clients"].tolist())) == COHORT


def test_trainer_retry_reenqueues_failed_clients():
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=False,
                    fault_crash=0.5, fault_max_delay=0,
                    retry_backoff=1, retry_max=2)
    data = _toy_fed_data()
    tr = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    hist = tr.run(data, rounds=8, cohort=COHORT, batch=8)
    assert all("retried" in h for h in hist)
    assert sum(h["retried"] for h in hist) > 0
    # the policy is deterministic: an identical run retries identically
    tr2 = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    hist2 = tr2.run(data, rounds=8, cohort=COHORT, batch=8)
    assert [h["retried"] for h in hist] == [h["retried"] for h in hist2]
    assert tree_equal(tr.state, tr2.state)


def test_client_speeds_ship_with_sample():
    data = _toy_fed_data()
    speeds = heavy_tail_speeds(0, data.num_clients)
    assert speeds.shape == (data.num_clients,) and (speeds > 0).all()
    data.client_speeds = speeds
    s = data.sample_round(0, cohort=COHORT, batch=8)
    assert np.array_equal(s["client_speeds"], speeds[s["clients"]])


# ---------------------------------------------------------------------------
# async runtime metrics + staleness machinery
# ---------------------------------------------------------------------------
def test_async_metrics_and_staleness_histogram():
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=True,
                    engine="buffered_async", async_buffer=2,
                    async_capacity=2 * COHORT, fault_profile="flaky")
    data = _toy_fed_data()
    tr = FederatedTrainer(model, fed, rounds_per_call=2, seed=0)
    hist = tr.run(data, rounds=4, cohort=COHORT, batch=8, meta_batch=8)
    for h in hist:
        assert isinstance(h["staleness_hist"], list)
        assert len(h["staleness_hist"]) == 8
        for k in ("arrivals", "server_steps", "buffer_fill",
                  "overflow_dropped", "staleness_mean", "staleness_max",
                  "fault_crashed", "fault_dropped", "fault_delayed"):
            assert isinstance(h[k], float), k
    assert sum(h["arrivals"] for h in hist) > 0


def test_staleness_discount_modes():
    z = jnp.float32(0.0)
    for mode in ("none", "inv", "invsqrt"):
        assert float(staleness_discount(mode)(z)) == 1.0
    assert float(staleness_discount("inv")(jnp.float32(3.0))) == 0.25
    with pytest.raises(ValueError, match="staleness_mode"):
        staleness_discount("quadratic")


def test_async_max_staleness_evicts():
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=False,
                    engine="buffered_async", async_buffer=2,
                    async_capacity=2 * COHORT, async_max_staleness=1,
                    fault_profile="flaky")
    _, metrics = _run_rounds(model, fed, 5)
    assert "expired" in metrics
    assert np.isfinite(float(metrics["expired"]))


# ---------------------------------------------------------------------------
# config guards
# ---------------------------------------------------------------------------
def test_async_deadlock_and_deadline_config_errors():
    with pytest.raises(ValueError, match="deadlock"):
        FedConfig(engine="buffered_async", fused_update=True,
                  async_buffer=9, async_capacity=4)
    with pytest.raises(ValueError, match="async_max_staleness"):
        FedConfig(engine="buffered_async", fused_update=True,
                  round_deadline=2.0)
    with pytest.raises(ValueError, match="staleness_mode"):
        FedConfig(staleness_mode="quadratic")
    with pytest.raises(ValueError, match="fault_profile"):
        FedConfig(fault_profile="catastrophic")
    with pytest.raises(ValueError, match="fault_crash"):
        FedConfig(fault_crash=1.5)
    with pytest.raises(ValueError, match="fault_max_delay"):
        FedConfig(fault_delay=0.5)


def test_explicit_garble_requires_async_engine():
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True, fault_garble=0.3)
    with pytest.raises(ValueError, match="buffered_async"):
        make_federated_round(model, fed)
    # profile-carried garble downgrades silently on sync engines...
    fed_prof = FedConfig(cohort=COHORT, fused_update=True,
                         cohort_strategy="scan", meta=False,
                         fault_profile="flaky")
    _run_rounds(make_mlp_model(), fed_prof, 1)
    # ...and garble runs fine under the async runtime
    fed_async = dataclasses.replace(fed, engine="buffered_async",
                                    cohort_strategy="scan", meta=False,
                                    async_capacity=2 * COHORT)
    state, _ = _run_rounds(model, fed_async, 2)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(state))


def test_fault_profiles_registry():
    assert set(FAULT_PROFILES) >= {"none", "flaky", "stragglers"}
    assert not resolve_faults(FedConfig()).active
    fc = resolve_faults(FedConfig(fault_profile="flaky", fault_crash=0.5))
    assert fc.crash == 0.5 and fc.drop == FAULT_PROFILES["flaky"]["drop"]
    assert FaultConfig(delay=0.5, max_delay=2).active


# ---------------------------------------------------------------------------
# crash-safe checkpointing + async save/resume
# ---------------------------------------------------------------------------
def test_async_save_resume_bit_identical(tmp_path):
    model = make_mlp_model()
    fed = FedConfig(cohort=COHORT, fused_update=True,
                    cohort_strategy="scan", meta=True,
                    engine="buffered_async", async_buffer=2,
                    async_capacity=2 * COHORT, fault_profile="flaky")
    data = _toy_fed_data()
    ref = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    ref.run(data, rounds=6, cohort=COHORT, batch=8, meta_batch=8)

    tr = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    # save at the first round that leaves deltas pending: which rounds do
    # depends on the fault draws, and an empty pool would not test resume
    for stop in range(1, 6):
        tr.run(data, rounds=stop, cohort=COHORT, batch=8, meta_batch=8)
        if float(jnp.sum(tr.state["async"]["weight"])) > 0:
            break
    assert float(jnp.sum(tr.state["async"]["weight"])) > 0, \
        "pool should hold pending deltas mid-run for the resume to matter"
    path = str(tmp_path / "async.ckpt")
    tr.save(path)
    tr2 = FederatedTrainer(model, fed, rounds_per_call=1, seed=0)
    tr2.restore(path)
    tr2.run(data, rounds=6, cohort=COHORT, batch=8, meta_batch=8)
    assert tree_equal(ref.state, tr2.state)  # pool + staleness included


def test_ckpt_corrupt_blob_actionable(tmp_path):
    path = str(tmp_path / "state.ckpt")
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    ckpt_save(path, tree)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    with pytest.raises(ValueError) as ei:
        ckpt_restore(path, tree)
    msg = str(ei.value)
    assert path in msg and ("msgpack" in msg or "truncated" in msg)
    # a decodable blob that is not a checkpoint payload
    import msgpack
    with open(path, "wb") as f:
        f.write(msgpack.packb({"not": "a checkpoint"}))
    with pytest.raises(ValueError, match="leaves"):
        ckpt_restore(path, tree)


def test_ckpt_failed_save_preserves_previous(tmp_path, monkeypatch):
    path = str(tmp_path / "state.ckpt")
    tree0 = {"w": jnp.arange(8, dtype=jnp.float32)}
    ckpt_save(path, tree0, extra={"gen": 0})

    import repro.checkpoint.ckpt as ckpt_mod

    def boom(*a, **kw):
        raise RuntimeError("disk full (simulated)")
    monkeypatch.setattr(ckpt_mod.msgpack, "packb", boom)
    with pytest.raises(RuntimeError, match="disk full"):
        ckpt_save(path, {"w": jnp.zeros(8, jnp.float32)}, extra={"gen": 1})
    monkeypatch.undo()
    # the previous checkpoint survives a mid-write failure, intact
    restored, extra = ckpt_restore(path, tree0)
    assert extra == {"gen": 0}
    assert np.array_equal(np.asarray(restored["w"]), np.arange(8))
    # and no temp litter for a retry to trip over
    assert [f for f in os.listdir(tmp_path) if ".tmp" in f] == []
