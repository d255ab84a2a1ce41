"""The Pallas kernels of the main path, compiled for a TPU v5e at real width.

Nothing runs: each test lowers one kernel for a described ``v5e:2x2``
topology (no chip attached) at smollm-360m's flat row count and compiles
it, so the TPU compiler's refusals (block shapes off the tiling, scalar
stores to VMEM, too much fast memory) surface here instead of on a chip.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this module.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (the kernel modules import through core)
from repro.configs import get_arch
from repro.core.flat import make_flat_spec
from repro.kernels.comm import kernel as CK
from repro.kernels.fused_update import kernel as FK
from repro.models.model import build_model

COHORT = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setitem(os.environ, "TPU_LOG_DIR",
               os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def shapes(topo):
    """Abstract operands on one described chip, sized by smollm-360m's one
    fp32 flat group."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    model = build_model(get_arch("smollm-360m"), dtype=jnp.float32)
    spec = make_flat_spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert len(spec.groups) == 1
    rows = spec.groups[0].rows

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return {"rows": rows, "buf": sds((rows, 128)), "scalar": sds(()),
            "stack": sds((COHORT, rows, 128)), "w": sds((COHORT,)),
            "scal4": sds((1, FK.N_SCALARS)),
            "q": sds((rows, 128), jnp.int8),
            "packed": sds((rows // CK.SIGN_PACK, 128), jnp.uint8)}


def _adam(fn):
    return lambda *a: fn(*a, opt="adam")


# kernel name -> (callable, operand names in the shapes fixture)
KERNELS = {
    "accumulate_pass": (FK.accumulate_pass, ("buf", "buf", "scalar")),
    "accumulate_pass_bwd": (FK.accumulate_pass_bwd,
                            ("buf", "scalar", "buf")),
    "update_pass[adam]": (_adam(FK.update_pass),
                          ("buf", "buf", "buf", "buf", "scal4")),
    "update_pass_bwd[adam]": (_adam(FK.update_pass_bwd),
                              ("buf", "buf", "buf", "scal4", "buf", "buf",
                               "buf")),
    "aggregate_pass": (FK.aggregate_pass, ("stack", "w")),
    "aggregate_pass_bwd": (FK.aggregate_pass_bwd,
                           ("stack", "w", "buf", "buf", "scalar")),
    "quantize_i8_pass": (
        lambda g, inv, s: CK.quantize_i8_pass(g, inv, s, with_error=True),
        ("buf", "scalar", "scalar")),
    "dequant_i8_fma_pass": (CK.dequant_i8_fma_pass,
                            ("buf", "q", "scalar")),
    "sign_pack_pass": (
        lambda g, mu: CK.sign_pack_pass(g, mu, 1000, with_error=True),
        ("buf", "scalar")),
    "sign_unpack_fma_pass": (
        lambda acc, p, mu: CK.sign_unpack_fma_pass(acc, p, mu, 1000),
        ("buf", "packed", "scalar")),
}


def kernel_bodies(hlo_text: str) -> dict:
    """{custom-call instruction name: the words of its kernel's serialized
    module}, read by the benchmark's own trace library."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lib.trace import kernels_from_hlo
    return kernels_from_hlo(hlo_text)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(shapes, name):
    fn, operands = KERNELS[name]
    compiled = jax.jit(fn).lower(*(shapes[o] for o in operands)).compile()
    text = compiled.as_text()
    # Mosaic compiled the kernel: it is a custom call, not an interpreted
    # loop of XLA ops
    assert 'custom_call_target="tpu_custom_call"' in text
    # the call carries its wrapper's name, and its body the kernel
    # function's name
    wrapper = name.split("[")[0]
    (instr, words), = kernel_bodies(text).items()
    assert re.fullmatch(re.escape(wrapper) + r"(\.\d+)?", instr), instr
    assert "_" + wrapper.replace("_pass", "") + "_kernel" in words.split()


def test_kernel_families_found_in_v5e_program(shapes):
    """One program holding the accumulate, int8 quantize and dequant-FMA
    and Adam update kernels: the benchmark's HLO reading finds each family
    by its kernel function's name, under the call's stable name."""
    def prog(acc, g, w, m, v, scal):
        acc = FK.accumulate_pass(acc, g, w)
        q, _ = CK.quantize_i8_pass(g, w, w, with_error=True)
        acc = CK.dequant_i8_fma_pass(acc, q, w)
        return FK.update_pass(acc, g, m, v, scal, opt="adam")

    text = jax.jit(prog).lower(
        shapes["buf"], shapes["buf"], shapes["scalar"], shapes["buf"],
        shapes["buf"], shapes["scal4"]).compile().as_text()
    found = {}
    for instr, words in kernel_bodies(text).items():
        for fam in ("accumulate", "quantize_i8", "dequant_i8_fma", "update"):
            if f"_{fam}_kernel" in words.split():
                found[fam] = instr
    assert sorted(found) == ["accumulate", "dequant_i8_fma", "quantize_i8",
                             "update"]
    for fam, instr in found.items():
        assert instr.startswith(f"{fam}_pass"), (fam, instr)


def test_sharded_server_update_compiles_for_v5e_mesh(topo):
    """The sharded executor's server step over a (4, 1) mesh of the
    described chips: Mosaic kernels are not partitioned automatically, so
    the update kernel must run per device inside a shard_map."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core.flat import with_pspecs
    from repro.kernels.fused_update.ops import flat_apply_groups
    from repro.sharding.specs import flat_group_pspecs

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())
    model = build_model(get_arch("smollm-360m"), dtype=jnp.float32)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = make_flat_spec(params)
    spec = with_pspecs(spec, flat_group_pspecs(spec, mesh), mesh)
    buf = jax.ShapeDtypeStruct((spec.groups[0].rows, 128), jnp.float32,
                               sharding=rep)
    opt = {"m": (buf,), "v": (buf,),
           "t": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}

    def step(G, p, o):
        return flat_apply_groups(spec, [G], jnp.float32(1.0), p, o,
                                 opt="adam", lr=0.01, interpret=False)

    compiled = jax.jit(step).lower(buf, params, opt).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_flat_rows_give_full_tiles(shapes):
    """The row alignment makes every kernel take the 256-row tile at real
    width, so the 1-bit codec's packed uint8 tile is (32, 128)."""
    assert FK._block_rows(shapes["rows"]) == 256
