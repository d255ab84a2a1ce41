"""Unit tests for the communication-compression subsystem (repro.comm):

  * Pallas pack/unpack kernels == pure-jnp ref oracles (bit-exact for the
    integer stages, fp32-exact for the FMA stages), on buffers WITH layout
    padding so the pad-inertness convention is exercised;
  * per-codec round-trip properties: quantization error bounds (int8),
    two-point alphabet + strict contraction (sign1bit), exact top-k
    support recovery (topk), exact identity (none);
  * the fused encode_ef sweep == the generic encode/decode residual
    definition for every codec;
  * measured payload bytes match the transport arithmetic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import (Int8Codec, NoneCodec, Sign1BitCodec,
                               TopKCodec, available_codecs, get_codec)
from repro.core.flat import LANES, flatten_tree, make_flat_spec
from repro.kernels.comm import kernel as K
from repro.kernels.comm import ref as R

# a mixed-shape tree whose single fp32 group pads 68 -> 8*128 elements,
# so every test below covers real layout padding
TREE = {"a": jnp.zeros((5, 7), jnp.float32), "b": jnp.zeros((33,),
                                                            jnp.float32)}
SPEC = make_flat_spec(TREE)
GROUP = SPEC.groups[0]


def rand_group(seed=0, scale=1.0):
    """(rows, LANES) fp32 buffer with the group's pad zeroed, like every
    real flatten_tree output."""
    rng = np.random.default_rng(seed)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(0, scale, x.shape), jnp.float32),
        TREE)
    return flatten_tree(SPEC, tree)[0]


def valid_mask():
    flat_idx = np.arange(GROUP.rows * LANES).reshape(GROUP.rows, LANES)
    return flat_idx < GROUP.size


# ---------------------------------------------------------------------------
# kernels == ref oracles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("with_error", [False, True])
def test_quantize_i8_kernel_matches_ref(with_error):
    g = rand_group(1)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    out_k = K.quantize_i8_pass(g, 1.0 / scale, scale,
                               with_error=with_error, interpret=True)
    out_r = R.quantize_i8_ref(g, 1.0 / scale, scale, with_error=with_error)
    if with_error:
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        # the error output is fp32: interpret-mode Pallas may contract the
        # g - q*scale FMA differently from plain jnp (~1 ulp)
        np.testing.assert_allclose(np.asarray(out_k[1]),
                                   np.asarray(out_r[1]),
                                   rtol=1e-6, atol=1e-7)
    else:
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert np.asarray(out_k).dtype == np.int8


def test_dequant_i8_fma_kernel_matches_ref():
    g = rand_group(2)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    q = R.quantize_i8_ref(g, 1.0 / scale, scale)
    acc = rand_group(3)
    out_k = K.dequant_i8_fma_pass(acc, q, scale * 0.37, interpret=True)
    out_r = R.dequant_i8_fma_ref(acc, q, scale * 0.37)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("with_error", [False, True])
def test_sign_pack_kernel_matches_ref(with_error):
    g = rand_group(4)
    mu = float(jnp.sum(jnp.abs(g))) / GROUP.size
    out_k = K.sign_pack_pass(g, mu, GROUP.size, with_error=with_error,
                             interpret=True)
    out_r = R.sign_pack_ref(g, mu, GROUP.size, with_error=with_error)
    if with_error:
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        np.testing.assert_array_equal(np.asarray(out_k[1]),
                                      np.asarray(out_r[1]))
    else:
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert np.asarray(out_k).dtype == np.uint8
        assert out_k.shape == (GROUP.rows // K.SIGN_PACK, LANES)


def test_sign_unpack_fma_kernel_matches_ref():
    g = rand_group(5)
    packed = R.sign_pack_ref(g, 1.0, GROUP.size)
    acc = rand_group(6)
    out_k = K.sign_unpack_fma_pass(acc, packed, 0.21, GROUP.size,
                                   interpret=True)
    out_r = R.sign_unpack_fma_ref(acc, packed, 0.21, GROUP.size)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def _payloads(seed):
    """Two int8 payloads and two packed sign payloads of fresh gradients."""
    out = []
    for s in (seed, seed + 1):
        g = rand_group(s)
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        out.append((R.quantize_i8_ref(g, 1.0 / scale, scale),
                    R.sign_pack_ref(g, 1.0, GROUP.size)))
    return out


# kernel name -> (the kernel on (acc, payload, scalar), its ref oracle)
DECODE_FMA = {
    "dequant_i8_fma_pass": (
        lambda a, p, s: K.dequant_i8_fma_pass(a, p[0], s, interpret=True),
        jax.jit(lambda a, p, s: R.dequant_i8_fma_ref(a, p[0], s))),
    "sign_unpack_fma_pass": (
        lambda a, p, s: K.sign_unpack_fma_pass(a, p[1], s, GROUP.size,
                                               interpret=True),
        jax.jit(lambda a, p, s: R.sign_unpack_fma_ref(a, p[1], s,
                                                      GROUP.size))),
}


@pytest.mark.parametrize("jit", [False, True])
@pytest.mark.parametrize("kernel", sorted(DECODE_FMA))
def test_decode_fma_keeps_a_live_accumulator(kernel, jit):
    """The decode-FMA kernels write into ``acc``'s buffer; a caller that
    keeps ``acc`` alive still reads it unchanged (XLA copies it first), and
    both calls on the one ``acc`` equal the ref oracle exactly."""
    fn, ref = DECODE_FMA[kernel]
    acc = rand_group(11)
    acc_before = np.asarray(acc).copy()
    p1, p2 = _payloads(12)
    s1, s2 = jnp.float32(0.37), jnp.float32(-1.25)

    def two(a, x, y):
        return fn(a, x, s1), fn(a, y, s2), a

    out1, out2, acc_in = (jax.jit(two) if jit else two)(acc, p1, p2)
    np.testing.assert_array_equal(np.asarray(acc_in), acc_before)
    np.testing.assert_array_equal(np.asarray(acc), acc_before)
    np.testing.assert_array_equal(np.asarray(out1),
                                  np.asarray(ref(acc, p1, s1)))
    np.testing.assert_array_equal(np.asarray(out2),
                                  np.asarray(ref(acc, p2, s2)))


@pytest.mark.parametrize("chunk", [1, 3])
def test_coded_stream_bitmatches_folded_dequant_ref(key, chunk):
    """The coded streaming core with the int8 uplink (the accumulator
    updated in place by the dequant-FMA kernel) == each client's int8
    payload folded through ``dequant_i8_fma_ref`` in client order, bit for
    bit; chunk 3 of a cohort of 5 is the ragged case."""
    from test_plugin_api import make_mlp_model, sample_batch

    from repro.core.aggregate import (chunked_cohort_gradient_coded,
                                      scan_cohort_deltas_flat)
    from repro.core.client import make_client_update
    from repro.core.flat import zeros_flat

    model = make_mlp_model()
    params = model.init(key)
    spec = make_flat_spec(params)
    rng = np.random.default_rng(7)
    batch = sample_batch(rng, cohort=5, b=16)
    wts = jnp.asarray(rng.uniform(1.0, 5.0, 5), jnp.float32)
    cu = make_client_update("uga", model.loss, local_steps=2)
    codec = Int8Codec()

    # the weights enter as arguments: as constants of the program XLA
    # folds their normalization at compile time, which may round apart
    # from the same division made at run time
    G, _, _ = jax.jit(lambda p, w: chunked_cohort_gradient_coded(
        cu, p, batch, w, 0.05, key, spec=spec, chunk=chunk,
        codec=codec))(params, wts)
    deltas, _ = jax.jit(lambda p, w: scan_cohort_deltas_flat(
        cu, p, batch, w, 0.05, key, spec=spec))(params, wts)
    wn = wts / jnp.maximum(jnp.sum(wts), 1e-30)

    @jax.jit
    def fold(acc, d, w):
        payload = codec.encode(GROUP, d)
        return R.dequant_i8_fma_ref(acc, payload["q"],
                                    payload["scale"] * w)

    want = zeros_flat(spec)
    for k in range(5):
        want = [fold(a, d[k], wn[k]) for a, d in zip(want, deltas)]
    assert len(G) == len(want)
    for a, b in zip(G, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sign_pack_unpack_roundtrip_and_pad_inert():
    """pack -> unpack recovers sign(g) * mu on the valid elements and EXACT
    zero on the layout pad (the invariant flat_sq_norm / opt slots / EF
    state rely on)."""
    g = rand_group(7)
    packed = R.sign_pack_ref(g, 1.0, GROUP.size)
    dec = np.asarray(K.sign_unpack_fma_pass(
        jnp.zeros_like(g), packed, 0.5, GROUP.size, interpret=True))
    m = valid_mask()
    expect = np.where(np.asarray(g) >= 0, 0.5, -0.5)
    np.testing.assert_array_equal(dec[m], expect[m])
    np.testing.assert_array_equal(dec[~m], 0.0)


# ---------------------------------------------------------------------------
# per-codec round-trip properties
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_int8_roundtrip_error_bound(seed):
    """Symmetric round-to-nearest: |decode(encode(g)) - g| <= scale / 2
    everywhere (amax maps to exactly 127, so clipping never adds error)."""
    codec = Int8Codec()
    g = rand_group(seed, scale=0.5)
    p = codec.encode(GROUP, g)
    dec = codec.decode(GROUP, p)
    scale = float(p["scale"])
    err = np.abs(np.asarray(dec) - np.asarray(g))
    assert err.max() <= scale / 2 * (1 + 1e-5)
    np.testing.assert_array_equal(np.asarray(dec)[~valid_mask()], 0.0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sign1bit_roundtrip_alphabet_and_contraction(seed):
    """decode is the two-point alphabet {-mu, +mu} with g's signs, and the
    compression error strictly contracts: ||g - dec||^2 = ||g||^2 - n*mu^2
    < ||g||^2 (the EF convergence ingredient)."""
    codec = Sign1BitCodec()
    g = rand_group(seed)
    dec = np.asarray(codec.decode(GROUP, codec.encode(GROUP, g)))
    mu = float(jnp.sum(jnp.abs(g))) / GROUP.size
    m = valid_mask()
    np.testing.assert_allclose(dec[m],
                               np.where(np.asarray(g)[m] >= 0, mu, -mu),
                               rtol=1e-6)
    np.testing.assert_array_equal(dec[~m], 0.0)
    gn = np.asarray(g)
    assert np.linalg.norm(gn - dec) < np.linalg.norm(gn)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_topk_roundtrip_support_recovery(seed):
    """decode equals g exactly on the k largest-|g| elements, zero off the
    support, so the error never exceeds ||g||."""
    class FedStub:
        topk_ratio = 0.05
    codec = TopKCodec(FedStub())
    g = rand_group(seed)
    k = codec._k(GROUP)
    dec = np.asarray(codec.decode(GROUP, codec.encode(GROUP, g)))
    gn = np.asarray(g)
    kept = np.argsort(-np.abs(gn).reshape(-1))[:k]
    np.testing.assert_array_equal(dec.reshape(-1)[kept],
                                  gn.reshape(-1)[kept])
    off = np.setdiff1d(np.arange(gn.size), kept)
    np.testing.assert_array_equal(dec.reshape(-1)[off], 0.0)
    assert np.linalg.norm(gn - dec) <= np.linalg.norm(gn)


def test_none_codec_identity():
    codec = NoneCodec()
    g = rand_group(9)
    assert not codec.lossy
    np.testing.assert_array_equal(
        np.asarray(codec.decode(GROUP, codec.encode(GROUP, g))),
        np.asarray(g))


@pytest.mark.parametrize("codec_cls", [Int8Codec, Sign1BitCodec, TopKCodec])
def test_encode_ef_matches_generic_residual(codec_cls):
    """The fused encode+error sweep must equal the definitional residual
    e - decode(encode(e)) — the gate that keeps the one-sweep EF kernels
    honest against the generic GradientCodec contract."""
    codec = codec_cls()
    e = rand_group(11)
    payload, res = codec.encode_ef(GROUP, e)
    dec = codec.decode(GROUP, payload)
    np.testing.assert_allclose(np.asarray(res),
                               np.asarray(e) - np.asarray(dec),
                               rtol=1e-6, atol=1e-7)


def test_payload_bytes_arithmetic():
    assert NoneCodec().payload_bytes(GROUP) == 4 * GROUP.size
    assert Int8Codec().payload_bytes(GROUP) == GROUP.size + 4
    assert Sign1BitCodec().payload_bytes(GROUP) == -(-GROUP.size // 8) + 4

    class FedStub:
        topk_ratio = 0.1
    tk = TopKCodec(FedStub())
    assert tk.payload_bytes(GROUP) == 8 * max(1, round(GROUP.size * 0.1))


def test_registry_names_and_unknown_error():
    assert set(available_codecs()) >= {"none", "int8", "sign1bit", "topk"}
    with pytest.raises(ValueError, match="register_codec"):
        get_codec("zstd")
