"""The round program's layer scopes, the host spans in a profiler capture,
and the trainer's compile events.

  * every matmul and convolution of a compiled round (a tiny smollm-shaped
    round with the fused engine, the int8 codec and the meta step; a CNN
    round) sits under one of the ``repro.*`` named scopes, and each scope
    the round runs appears;
  * a ``jax.profiler`` capture of a trained run holds the trainer's
    ``repro.phase.*`` annotations with their ``round`` and ``parent``
    args, ``stage`` nested in ``sample_stack``;
  * a dispatch that compiled a program logs a ``compile`` event at its
    round, and only then;
  * scopes and annotations are metadata: a run under a capture is
    bit-identical to an untracked one.
"""
import dataclasses
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs import paper_models as pm
from repro.configs.base import FedConfig
from repro.core import FederatedTrainer
from repro.core.round import init_server_state, make_federated_round
from repro.models.model import build_model, build_paper_cnn

from test_obs import (BASE, BATCH, COHORT, _toy_fed_data, make_mlp_model,
                      read_jsonl, tree_equal)

SCOPES = ("repro.client", "repro.attention", "repro.flatten", "repro.codec",
          "repro.meta", "repro.server")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MATMUL = re.compile(r"= \S+ (dot|convolution)\(")


def scopes_of(op_name: str) -> set:
    """The layer scopes in an op's name stack, each as a whole token (under
    autodiff a scope appears wrapped: ``transpose(jvp(repro.client))``)."""
    return {s for s in SCOPES
            if re.search(r"(?<![\w.])" + re.escape(s) + r"(?![\w.])",
                         op_name)}


def compiled_round_text(model, fed, cohort_batch, meta_batch) -> str:
    key = jax.random.PRNGKey(0)
    state = init_server_state(model, fed, key)
    lowered = jax.jit(make_federated_round(model, fed)).lower(
        state, cohort_batch, meta_batch, jnp.ones((fed.cohort,)), key)
    # two CPU passes rebuild some dots and convolutions (degenerate batch
    # dimensions; non-canonical dimension labels) and drop their metadata;
    # they are off here so every matmul keeps the name stack it was traced
    # under
    return lowered.compile(compiler_options={
        "xla_disable_hlo_passes":
        "batch-dot-simplification,convolution-canonicalization"}).as_text()


def _smollm_round():
    model = build_model(get_arch("smollm-360m-smoke"), dtype=jnp.float32)
    fed = FedConfig(algorithm="uga", meta=True, cohort=2, local_steps=2,
                    client_lr=0.01, server_lr=0.001, meta_lr=0.01,
                    server_opt="adam", codec="int8", cohort_chunk=1,
                    fused_update=True)
    seq = 16
    return (model, fed, {"tokens": jnp.zeros((2, 2, seq + 1), jnp.int32)},
            {"tokens": jnp.zeros((2, seq + 1), jnp.int32)})


def _cnn_round():
    model = build_paper_cnn(pm.FEMNIST_CNN_SMOKE)
    fed = FedConfig(algorithm="uga", meta=True, cohort=2, local_steps=2,
                    client_lr=0.01, server_lr=0.05, meta_lr=0.01,
                    cohort_chunk=2, fused_update=True)
    return (model, fed,
            {"x": jnp.zeros((2, 4, 28, 28, 1)),
             "y": jnp.zeros((2, 4), jnp.int32)},
            {"x": jnp.zeros((4, 28, 28, 1)), "y": jnp.zeros((4,), jnp.int32)})


@pytest.mark.parametrize("build,want", [
    (_smollm_round, set(SCOPES)),
    (_cnn_round, set(SCOPES) - {"repro.attention", "repro.codec"}),
], ids=["smollm_int8_meta", "cnn_meta"])
def test_round_ops_carry_layer_scopes(build, want):
    text = compiled_round_text(*build())
    matmuls = [ln for ln in text.splitlines() if _MATMUL.search(ln)]
    assert matmuls
    for ln in matmuls:
        m = _OP_NAME.search(ln)
        assert m and scopes_of(m.group(1)), ln[:300]
    seen = set().union(*(scopes_of(n) for n in _OP_NAME.findall(text)))
    assert seen == want


def _phase_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.phase."):
                    out.append((ev.name[len("repro.phase."):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_phase_annotations_in_profiler_capture(tmp_path):
    model, data = make_mlp_model(), _toy_fed_data()
    tr = FederatedTrainer(model, BASE, seed=0)
    with jax.profiler.trace(str(tmp_path)):
        tr.run(data, rounds=2, cohort=COHORT, batch=BATCH, meta_batch=8)
    events = _phase_events(str(tmp_path))
    by = {(name, st.get("round")): (s, e, st) for name, s, e, st in events}
    for r in (0, 1):
        for name, parent in (("sample_stack", None),
                             ("stage", "sample_stack"),
                             ("dispatch", None), ("device_sync", None)):
            assert (name, r) in by, (name, r, sorted(by))
            assert by[(name, r)][2].get("parent") == parent
        s0, e0, _ = by[("sample_stack", r)]
        s1, e1, _ = by[("stage", r)]
        assert s0 <= s1 <= e1 <= e0
    tr.finish()


def test_tracker_span_events_carry_parent(tmp_path):
    model, data = make_mlp_model(), _toy_fed_data()
    tr = FederatedTrainer(model, BASE, seed=0, tracker="jsonl",
                          run_dir=str(tmp_path))
    tr.run(data, rounds=2, cohort=COHORT, batch=BATCH, meta_batch=8)
    tr.finish()
    phases = [ln for ln in read_jsonl(tmp_path / "metrics.jsonl")
              if ln.get("event") == "phase"]
    stage = [p for p in phases if p["phase"] == "stage"]
    assert [p["round"] for p in stage] == [0, 1]
    assert all(p["parent"] == "sample_stack" for p in stage)
    assert all(p["parent"] is None for p in phases if p["phase"] != "stage")
    # sampling and stacking hold the staging span
    for r in (0, 1):
        outer = [p for p in phases
                 if p["phase"] == "sample_stack" and p["round"] == r][0]
        inner = [p for p in stage if p["round"] == r][0]
        assert inner["dur_s"] <= outer["dur_s"]


def test_compile_event_at_the_round_whose_shape_changed(tmp_path):
    model, data = make_mlp_model(), _toy_fed_data()
    tr = FederatedTrainer(model, BASE, seed=0, tracker="jsonl",
                          run_dir=str(tmp_path))
    tr.run(data, rounds=3, cohort=COHORT, batch=BATCH, meta_batch=8)
    tr.run(data, rounds=5, cohort=COHORT - 2, batch=BATCH, meta_batch=8)
    tr.finish()
    compiles = [ln for ln in read_jsonl(tmp_path / "metrics.jsonl")
                if ln.get("event") == "compile"]
    assert [c["round"] for c in compiles] == [0, 3]
    for c in compiles:
        assert c["k"] == 1 and c["n"] >= 0 and c["dur_s"] > 0
    assert json.dumps(compiles)


def test_compile_counter_listens_once():
    from repro.obs import compile_counter
    c = compile_counter()
    assert compile_counter() is c
    x = jnp.arange(7.0).block_until_ready()
    n0, s0 = c.reading()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    n1, s1 = c.reading()
    assert n1 == n0 + 1 and s1 > s0


def test_captured_coded_run_bit_identical_to_untracked(tmp_path):
    """With a capture open over every round, the spans annotating it and
    the scopes in the program, an int8-coded chunked run computes the same
    bits as an untracked one."""
    fed = dataclasses.replace(BASE, codec="int8", cohort_chunk=2,
                              server_opt="adam", server_lr=0.01)
    model, data = make_mlp_model(), _toy_fed_data()
    a = FederatedTrainer(model, fed, seed=0)
    b = FederatedTrainer(model, fed, seed=0, tracker="jsonl",
                         run_dir=str(tmp_path), profile=3, profile_start=0)
    ha = a.run(data, rounds=3, cohort=COHORT, batch=BATCH, meta_batch=8)
    hb = b.run(data, rounds=3, cohort=COHORT, batch=BATCH, meta_batch=8)
    b.finish()
    assert glob.glob(os.path.join(str(tmp_path), "profile", "**",
                                  "*.xplane.pb"), recursive=True)
    assert tree_equal(a.state, b.state)
    assert ha == hb
    assert all(np.isfinite(h["client_loss"]) for h in ha)


def test_profiler_records_the_captured_programs_op_names(tmp_path):
    """A device op in a TPU trace is named by its HLO instruction only; the
    profiler keeps each instruction's op_name, layer scopes included, for
    the round programs its capture ran."""
    from repro.obs.profiler import captured_op_names, op_names_of
    model, data = make_mlp_model(), _toy_fed_data()
    tr = FederatedTrainer(model, BASE, seed=0, run_dir=str(tmp_path),
                          profile=1, profile_start=1)
    tr.run(data, rounds=3, cohort=COHORT, batch=BATCH, meta_batch=8)
    tr.finish()
    names = captured_op_names()
    assert names
    seen = set().union(*(scopes_of(n) for n in names.values()))
    assert {"repro.client", "repro.flatten", "repro.meta",
            "repro.server"} <= seen
    line = ('  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(f)/repro.codec/abs" '
            'source_file="x.py" source_line=3}')
    assert op_names_of(line) == {"fusion.7": "jit(f)/repro.codec/abs"}
