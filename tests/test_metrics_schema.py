"""Round-metrics schema pins (PR 9).

``repro.obs.schema.round_metric_keys`` documents exactly which keys a
tracker sees per FedConfig; these tests pin REAL trainer records — sync
``fused_flat`` and ``legacy_tree``, the through-aggregation meta mode,
fault/participation/retry counters, lossy-codec ``comm_bytes``, and the
``buffered_async`` runtime's ``staleness_*`` family — against it, so a
round refactor that drops or renames a metric fails here instead of
silently breaking every downstream consumer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import FederatedTrainer
from repro.data.pipeline import FederatedData
from repro.models.model import Model
from repro.obs import VECTOR_METRICS, round_metric_keys

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


def _toy_fed_data(n=256, clients=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, 10)).astype(np.float32)
    y = rng.integers(0, 4, n).astype(np.int32)
    parts = np.array_split(rng.permutation(n), clients)
    meta = rng.choice(n, 32, replace=False)
    return FederatedData(arrays={"x": x, "y": y}, client_indices=parts,
                         meta_indices=meta, seed=seed)


def _records(fed, rounds=2, rounds_per_call=1):
    model, data = make_mlp_model(), _toy_fed_data()
    tr = FederatedTrainer(model, fed, rounds_per_call=rounds_per_call,
                          seed=0)
    return tr.run(data, rounds=rounds, cohort=COHORT, batch=BATCH,
                  meta_batch=8)


def _assert_schema(fed, recs):
    want = round_metric_keys(fed)
    for rec in recs:
        assert frozenset(rec) == want, (sorted(rec), sorted(want))
        for k, v in rec.items():
            if k == "round":
                assert isinstance(v, int)
            elif k in VECTOR_METRICS:
                assert isinstance(v, list)
            else:
                assert isinstance(v, float)


BASE = FedConfig(cohort=COHORT, local_steps=2, client_lr=0.05,
                 server_lr=0.1, meta_lr=0.05, clip_norm=1.0)


@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused_flat", "legacy_tree"])
def test_sync_plain_and_meta_schema(fused):
    fed = dataclasses.replace(BASE, algorithm="uga", meta=True,
                              fused_update=fused)
    recs = _records(fed)
    _assert_schema(fed, recs)
    assert round_metric_keys(fed) == frozenset(
        {"round", "client_loss", "grad_norm", "meta_loss"})


def test_sync_no_meta_schema():
    fed = dataclasses.replace(BASE, algorithm="fedavg", meta=False)
    _assert_schema(fed, _records(fed, rounds_per_call=2))
    assert round_metric_keys(fed) == frozenset(
        {"round", "client_loss", "grad_norm"})


def test_through_aggregation_ctrl_schema():
    fed = dataclasses.replace(BASE, algorithm="uga", meta=True,
                              fused_update=True,
                              meta_mode="through_aggregation")
    recs = _records(fed)
    _assert_schema(fed, recs)
    assert {"ctrl_w_gnorm", "ctrl_lr_grad", "server_lr_eff",
            "meta_loss"} <= round_metric_keys(fed)


def test_sync_fault_retry_participation_schema():
    fed = dataclasses.replace(BASE, algorithm="fedavg", meta=False,
                              fused_update=True, participation=0.75,
                              fault_profile="flaky", round_deadline=2.0,
                              retry_backoff=2)
    recs = _records(fed, rounds=3)
    _assert_schema(fed, recs)
    assert {"participants", "arrivals", "fault_crashed", "fault_dropped",
            "fault_timeout", "retried"} <= round_metric_keys(fed)


def test_lossy_codec_comm_bytes_schema():
    fed = dataclasses.replace(BASE, algorithm="uga", meta=False,
                              fused_update=True, codec="int8",
                              error_feedback=True)
    recs = _records(fed)
    _assert_schema(fed, recs)
    assert "comm_bytes" in round_metric_keys(fed)
    assert all(rec["comm_bytes"] > 0 for rec in recs)


def test_buffered_async_schema():
    fed = dataclasses.replace(BASE, algorithm="uga", meta=True,
                              fused_update=True, cohort_strategy="scan",
                              engine="buffered_async",
                              async_buffer=COHORT // 2,
                              async_capacity=2 * COHORT,
                              async_max_staleness=4,
                              fault_profile="stragglers")
    recs = _records(fed, rounds=3)
    _assert_schema(fed, recs)
    keys = round_metric_keys(fed)
    assert {"arrivals", "server_steps", "buffer_fill", "overflow_dropped",
            "staleness_mean", "staleness_max", "staleness_hist",
            "fault_crashed", "fault_dropped", "fault_delayed", "expired",
            "meta_loss"} <= keys
    assert "staleness_hist" in VECTOR_METRICS


def test_schema_is_frozen_and_trainer_flag():
    fed = dataclasses.replace(BASE, algorithm="uga", meta=True)
    keys = round_metric_keys(fed)
    assert isinstance(keys, frozenset)
    # trainer=False drops the host-side additions
    raw = round_metric_keys(fed, trainer=False)
    assert "round" not in raw and raw <= keys


# ---------------------------------------------------------------------------
# analysis-event schemas (PR 10): the roofline / profile_summary payloads
# the trainer emits are pinned to the frozensets in repro.obs.schema, the
# same way round records are pinned to round_metric_keys above.
# ---------------------------------------------------------------------------
def test_roofline_event_schema_matches_live_payload():
    from repro.obs import ROOFLINE_EVENT_KEYS
    from repro.roofline.live import round_roofline_event

    fn = jax.jit(lambda x: (x @ x.T).sum())
    ev = round_roofline_event(
        fn, (jax.ShapeDtypeStruct((8, 8), jnp.float32),),
        rounds_per_call=2)
    assert ev is not None
    # live.py produces everything except the trainer's measured_* triple
    measured = {"measured_rounds_per_s", "measured_s_per_round",
                "rounds_measured"}
    assert set(ev) == set(ROOFLINE_EVENT_KEYS) - measured
    assert measured < ROOFLINE_EVENT_KEYS
    assert ev["rounds_per_call"] == 2
    # the event names the device it compiled for and the peaks it used
    dev = jax.devices()[0]
    assert ev["device"] == {"platform": dev.platform,
                            "kind": dev.device_kind}
    if dev.platform != "tpu":
        from repro.roofline import WHATIF_KIND
        assert ev["peaks_kind"] == WHATIF_KIND
    assert ev["tpu_custom_calls"] == 0          # no Pallas kernel in fn

    # a callable without .lower (sanitize-mode closure) is skipped, and
    # the skip is a None — not a crash, not a partial event
    assert round_roofline_event(lambda x: x, (1.0,)) is None


def test_roofline_peaks_keyed_by_device_kind():
    """A TPU kind without a published row is an error, never a default;
    off the TPU the what-if row applies and says so."""
    from repro.roofline import PEAKS, WHATIF_KIND, peaks_for
    v5e = PEAKS["TPU v5 lite"]
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9) and v5e.source
    assert peaks_for("tpu", "TPU v5 lite") == ("TPU v5 lite", v5e)
    with pytest.raises(ValueError, match="no roofline peaks"):
        peaks_for("tpu", "TPU v0 unknown")
    assert peaks_for("cpu", "cpu") == (WHATIF_KIND, PEAKS[WHATIF_KIND])


def test_profile_summary_event_schema_matches_summarizer():
    from repro.obs import PROFILE_SUMMARY_EVENT_KEYS
    from repro.obs.trace_analysis import summarize

    payload = summarize({"traceEvents": []})
    assert set(payload) | {"trace"} == set(PROFILE_SUMMARY_EVENT_KEYS)
