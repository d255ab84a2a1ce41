"""Each FLOP and byte count against a count by hand."""
import math

import jax
import jax.numpy as jnp
import pytest
from lib import harness, work

from tiny import ROOT


def _builder(name):
    return harness.load_module(f"{ROOT}/bench/configs/{name}.py", "b_" + name)


def test_smollm_forward_flops_by_hand():
    b = _builder("smollm-360m")
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "vocab_size": 32}
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 -> 64+32+32+64+384
    per_token = 2 * (2 * 576 + 8 * 32)
    # attention per layer and sequence: 4 * H * D * S (S + 1) / 2, S = 3
    attn = 2 * 4 * 2 * 4 * 3 * 4 / 2
    assert b.forward_flops(cfg, 1, 3) == pytest.approx(3 * per_token + attn)
    assert b.forward_flops(cfg, 5, 3) == pytest.approx(
        5 * (3 * per_token + attn))


def test_smollm_round_flops_by_hand():
    b = _builder("smollm-360m")
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "vocab_size": 32}
    t = {"local_steps": 2, "local_epochs": 1, "client_batch": 4, "seq": 3,
         "meta_batch": 6, "meta": True, "cohort": 5}
    F = lambda n: b.forward_flops(cfg, n, 3)
    want = 5 * (1 * 9 * F(2) + 3 * F(4)) + 3 * F(6)
    assert b.round_flops(cfg, t) == pytest.approx(want)


def test_smollm_silo_round_flops_near_27_tflop():
    b = _builder("smollm-360m")
    cell = harness.find_cell("smollm360m.silo", ROOT)
    assert b.round_flops(cell.cfg, cell.traffic) == pytest.approx(27.25e12,
                                                                  rel=0.02)


def test_cnn_forward_flops_by_hand():
    b = _builder("paper-femnist-cnn")
    cfg = harness.load_json(f"{ROOT}/bench/configs/paper-femnist-cnn.json")
    conv1 = 2 * 28 * 28 * 25 * 1 * 32
    conv2 = 2 * 14 * 14 * 25 * 32 * 64
    fc = 2 * 7 * 7 * 64 * 512 + 2 * 512 * 62
    assert b.forward_flops(cfg) == conv1 + conv2 + fc
    t = {"local_steps": 3, "local_epochs": 5, "client_batch": 192,
         "meta_batch": 64, "meta": True, "cohort": 100}
    f = conv1 + conv2 + fc
    assert b.round_flops(cfg, t) == pytest.approx(
        100 * (14 * 9 * 64 * f + 3 * 192 * f) + 3 * 64 * f)


def test_group_rows_pad_to_the_row_tile():
    tree = {"a": jax.ShapeDtypeStruct((300, 128), jnp.float32),
            "b": jax.ShapeDtypeStruct((5,), jnp.float32),
            "c": jax.ShapeDtypeStruct((7,), jnp.bfloat16)}
    # fp32: 38405 elements -> 301 rows -> 512; bf16: 7 -> 1 row -> 256
    assert work.group_rows(tree) == [512, 256]


def test_round_work_bytes_by_hand():
    tree = {"a": jax.ShapeDtypeStruct((256, 128), jnp.float32)}
    n = 256 * 128
    w = work.round_work(tree, {"cohort": 3, "server_opt": "adam",
                               "codec": "none"})
    assert w["accumulate"] == (2.0 * n * 3, 12.0 * n * 3)
    assert w["update_adam"] == (11.0 * n, 28.0 * n)
    w = work.round_work(tree, {"cohort": 2, "server_opt": "sgd",
                               "codec": "int8"})
    assert w["quantize_i8"] == (2.0 * n * 2, 5.0 * n * 2)
    assert w["dequant_i8_fma"] == (2.0 * n * 2, 9.0 * n * 2)
    assert w["update_sgd"] == (2.0 * n, 12.0 * n)
    assert "accumulate" not in w


def test_roofline_share_by_hand():
    import types
    from lib.peaks import PEAKS
    from lib.roofline import share
    trace = types.SimpleNamespace(
        op_ns={"custom-call.1": 2e6, "custom-call.2": 1e6,
               "fusion_update": 5e6},
        op_count={"custom-call.1": 8, "custom-call.2": 2,
                  "fusion_update": 2},
        custom_calls={"custom-call.1", "custom-call.2"},
        op_text={"custom-call.1": "custom-call.1 _accumulate_kernel"})
    ctx = types.SimpleNamespace(
        trace=trace, rounds=2, peaks=PEAKS["TPU v5 lite"], matched={},
        work={"accumulate": (1e9, 819e6), "update_adam": (0.0, 819e6)})
    # bytes bound: 819e6 / 819e9 = 1 ms, over 1 ms of kernel a round
    assert share(ctx, ("accumulate",), ("_accumulate_kernel",)) == \
        pytest.approx(100)
    assert ctx.matched == {("_accumulate_kernel",): ["custom-call.1"]}
    # no Mosaic call is named for the update: nothing is reported, neither
    # the unnamed call nor the XLA fusion is taken for it
    assert share(ctx, ("update_adam",), ("_update_kernel",)) is None
    assert share(ctx, ("update_sgd",), ("_update_kernel",)) is None
    # a name that is only part of a word does not match
    assert share(ctx, ("accumulate",), ("accumulate",)) is None


def test_unknown_device_kind_is_an_error():
    from lib.peaks import peaks_for
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
    assert math.isclose(peaks_for("TPU v5 lite").flops, 197e12)
