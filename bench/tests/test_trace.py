"""The trace reduction: device busy and idle, per-op time, gap labels."""
import os

import pytest
from jax.profiler import ProfileData
from lib import trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIXTURE = os.path.join(FIXTURES, "v5e_round.xplane.pb")

# two devices; a host thread with the trainer's phase annotations.  Device
# 0 runs 100 ns of fusion, then 50 ns of a kernel; device 1 one 60 ns op
# that starts before the window.  The window is [1000, 1320].
SPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 200000 duration_ps: 50000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 300000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "accumulate_pass" } }
  event_metadata { key: 3 value { id: 3 name: "jit_round" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 960
    events { metadata_id: 1 offset_ps: 0 duration_ps: 60000 } }
  event_metadata { key: 1 value { id: 1 name: "all-reduce.3" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 200000 }
    events { metadata_id: 1 offset_ps: 290000 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "repro.phase.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "repro.phase.device_sync" } }
}
'''


def test_reduction_by_hand():
    r = trace.reduce_profile(ProfileData.from_text_proto(SPACE))
    assert r.window == (1000, 1320)
    assert r.devices == 2
    # device 0: 150 ns busy; device 1: 20 ns inside the window
    assert r.busy_ns == pytest.approx((150 + 20) / 2)
    assert r.op_ns["fusion.1"] == pytest.approx(50)
    assert r.op_ns["accumulate_pass"] == pytest.approx(25)
    assert r.op_ns["all-reduce.3"] == pytest.approx(10)
    assert "jit_round" not in r.op_ns          # modules are not ops
    assert r.is_collective("all-reduce.3")
    # device 0's gaps: [1100, 1200) under device_sync, [1250, 1320) mostly
    # under nothing until the last dispatch at 1290
    assert r.gaps[0] == ("device_sync", 100.0)
    assert r.gaps[1] == ("host between rounds", 70.0)


def test_kernel_names_from_compiled_hlo():
    """The custom calls of a program compiled for a v5e (accumulate,
    quantize, dequant-FMA and the Adam update) are named after the jitted
    function; their kernel names are read from the serialized kernels."""
    with open(os.path.join(FIXTURES, "kernels_v5e.hlo.txt")) as f:
        names = trace.kernels_from_hlo(f.read())
    found = {op: [k for k in ("_accumulate_kernel", "_quantize_i8_kernel",
                              "_dequant_i8_fma_kernel", "_update_kernel")
                  if k in words.split()]
             for op, words in names.items()}
    assert found == {"prog.4": ["_accumulate_kernel"],
                     "prog.5": ["_quantize_i8_kernel"],
                     "prog.6": ["_dequant_i8_fma_kernel"],
                     "prog.7": ["_update_kernel"]}
    r = trace.reduce_profile(ProfileData.from_text_proto(
        SPACE.replace('"accumulate_pass"', '"prog.4"')))
    assert "prog.4" not in r.custom_calls
    r.name_kernels(names)
    assert r.custom_calls == {"prog.4"}
    assert "_accumulate_kernel" in r.op_text["prog.4"].split()


def test_no_device_plane_is_an_error():
    host_only = SPACE.split("planes { id: 3")[0].replace(
        "/device:TPU:", "/host:X")
    with pytest.raises(ValueError):
        trace.reduce_profile(ProfileData.from_text_proto(host_only))


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded trace beside the tests")
def test_recorded_v5e_trace():
    r = trace.reduce_file(FIXTURE)
    assert r.devices == 1
    assert 0 < r.busy_ns <= r.window_ns
    assert r.op_ns
    assert all(t >= 0 for t in r.op_ns.values())


# a TPU trace names each op event by its whole HLO instruction, and a
# ``while`` holds its body's ops on the same line
NESTED = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 50000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 80000 }
    events { metadata_id: 4 offset_ps: 250000 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.7 = (f32[8]) while(f32[8] %p), body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion(f32[8] %x)" } }
  event_metadata { key: 3 value { id: 3 name: "%closed_call.9 = f32[8] custom-call(f32[8] %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%custom-call.3 = f32[8] custom-call(f32[2] %y), custom_call_target=\\"ConcatBitcast\\"" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 300000 } }
  event_metadata { key: 1 value { id: 1 name: "repro.phase.device_sync" } }
}
'''


def test_nested_ops_count_their_self_time():
    r = trace.reduce_profile(ProfileData.from_text_proto(NESTED))
    assert r.op_ns == pytest.approx({"while.7": 70, "fusion.2": 50,
                                     "closed_call.9": 80,
                                     "custom-call.3": 30})
    assert sum(r.op_ns.values()) == pytest.approx(r.busy_ns)
    assert r.custom_calls == {"closed_call.9"}    # Mosaic calls only
    assert "tpu_custom_call" in r.op_text["closed_call.9"]
