"""The layer-scope metrics and ``stage_ms``, read from a trace written by
hand.  Each op's JAX op path (plain, nested and wrapped by autodiff) comes
either from the program's profiler record (``captured_op_names``, keyed by
the op's instruction name, as on a v5e) or from a stat on the op event."""
import types

import pytest
from jax.profiler import ProfileData
from lib import harness, trace

from repro.obs import profiler
from tiny import ROOT

# One device; ops of 100 ns each unless said otherwise, all inside the
# window [1000, 2000) that the phase annotations span.  The op path rides
# in a stat, as on the chip.
OPS = [
    # (name, duration ns, op path)
    ("fusion.1", 100, "jit(one_round)/repro.client/dot_general"),
    ("fusion.2", 200, "jit(one_round)/repro.client/while/body/"
                      "transpose(jvp(repro.client))/dot_general"),
    ("fusion.3", 50, "jit(one_round)/repro.client/repro.attention/exp"),
    ("fusion.4", 30, "jit(one_round)/repro.meta/jvp()/repro.attention/mul"),
    ("fusion.5", 70, "jit(one_round)/repro.meta/jvp()/dot_general"),
    ("pad.6", 40, "jit(one_round)/repro.flatten/jit(_pad)/pad"),
    ("fusion.7", 60, "jit(one_round)/repro.codec/reduce_max"),
    ("quantize_i8_pass.8", 90, "jit(one_round)/repro.codec/pallas_call"),
    ("copy.9", 25, "src/repro/core/flat.py"),
    ("fusion.10", 15, "jit(one_round)/repro.server/add"),
    ("all-reduce.11", 35, "jit(one_round)/repro.client/psum"),
]


def _space(ops, stats):
    evs, meta, off = [], [], 0
    for i, (name, dur, path) in enumerate(ops, start=1):
        custom = ("custom_call_target=\\\"tpu_custom_call\\\""
                  if name.startswith("quantize") else "")
        stat = (f"stats {{ metadata_id: 1 str_value: \"{path}\" }}"
                if stats else "")
        evs.append(f"events {{ metadata_id: {i} offset_ps: {off * 1000} "
                   f"duration_ps: {dur * 1000} {stat} }}")
        meta.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: \"%{name} = f32[8] op() {custom}\" }} }}")
        off += dur
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    {" ".join(evs)} }}
  {" ".join(meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 500000 }}
    events {{ metadata_id: 2 offset_ps: 500000 duration_ps: 500000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "repro.phase.dispatch" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "repro.phase.device_sync" }} }}
}}
'''


@pytest.fixture(params=["profiler", "stat"], autouse=True)
def source(request, monkeypatch):
    """Where the op paths come from; the profiler's record is cleared for
    the stat case, as on a program without it."""
    monkeypatch.setattr(profiler, "_CAPTURED", {})
    return request.param


def _ctx(source, ops=OPS, rounds=2, phases=()):
    if source == "profiler":
        profiler._CAPTURED.update({n: p for n, _, p in ops})
    red = trace.reduce_profile(ProfileData.from_text_proto(
        _space(ops, stats=source == "stat")))
    return types.SimpleNamespace(trace=red, rounds=rounds,
                                 phases=list(phases))


def read(name, ctx):
    return harness.metric_reader(name, ROOT)(ctx)


MS = 1e-6 / 2          # ns over two rounds, in ms


def test_reduction_keeps_the_kernel_calls(source):
    ctx = _ctx(source)
    assert ctx.trace.custom_calls == {"quantize_i8_pass.8"}


def test_client_ms_counts_plain_and_autodiff_wrapped_paths(source):
    # fusion.1, fusion.2 (transpose(jvp(...))) and fusion.3 (nested
    # attention); not the collective
    assert read("client_ms", _ctx(source)) == pytest.approx((100 + 200 + 50) * MS)


def test_attention_ms_counts_it_wherever_it_nests(source):
    assert read("attention_ms", _ctx(source)) == pytest.approx((50 + 30) * MS)


def test_meta_ms_counts_the_meta_scope(source):
    assert read("meta_ms", _ctx(source)) == pytest.approx((30 + 70) * MS)


def test_flatten_ms_ignores_a_source_path(source):
    # copy.9 carries only src/repro/core/flat.py: not the flatten scope
    assert read("flatten_ms", _ctx(source)) == pytest.approx(40 * MS)


def test_codec_ms_leaves_the_kernels_to_the_roofline(source):
    assert read("codec_ms", _ctx(source)) == pytest.approx(60 * MS)


def test_scopes_partition_xla_ops_ms_here(source):
    ctx = _ctx(source)
    total = read("xla_ops_ms", ctx)
    parts = sum(read(m, ctx) for m in ("client_ms", "flatten_ms",
                                       "codec_ms", "meta_ms"))
    # what no scope holds: the source-path copy and the server's add
    assert total - parts == pytest.approx((25 + 15) * MS)


def test_scope_metrics_give_nothing_without_scopes(source):
    bare = [(n, d, "jit(one_round)/dot_general") for n, d, _ in OPS]
    ctx = _ctx(source, bare)
    for m in ("client_ms", "attention_ms", "flatten_ms", "codec_ms",
              "meta_ms"):
        assert read(m, ctx) is None


def test_scope_token_boundaries(source):
    from lib.scopes import holds
    assert holds("repro.client", "transpose(jvp(repro.client))")
    assert holds("repro.client", "a/repro.client")
    assert not holds("repro.client", "repro.clients/x")
    assert not holds("repro.flatten", "src/repro/flatten.py")
    assert not holds("repro.flatten", "repro.flatten.py")
    assert not holds("repro.meta", "xrepro.meta/y")


def test_stage_ms_is_the_mean_stage_span(source):
    phases = [{"phase": "sample_stack", "round": 3, "dur_s": 0.010},
              {"phase": "stage", "round": 3, "dur_s": 0.004,
               "parent": "sample_stack"},
              {"phase": "stage", "round": 4, "dur_s": 0.002,
               "parent": "sample_stack"}]
    assert read("stage_ms", _ctx(source, phases=phases)) == pytest.approx(3.0)
    assert read("stage_ms", _ctx(source, phases=phases[:1])) is None
