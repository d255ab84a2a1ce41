"""Pallas TPU kernels for the communication-compression uplink
(``repro.comm``) over flat fp32 buffers (layout: ``repro.core.flat``).

The uplink simulation is: client encodes its flat gradient group, the
server decodes and folds it into the Eq. (14) accumulator.  Each codec
stage is ONE HBM sweep, mirroring the ``kernels/fused_update`` structure:

  * :func:`quantize_i8_pass` — symmetric per-group int8 quantization
    ``q = clip(round(g / scale), -127, 127)``; with ``with_error=True`` it
    also emits the quantization residual ``g - q * scale`` in the same
    sweep (the error-feedback memory, so EF costs no extra pass).
  * :func:`dequant_i8_fma_pass` — decode fused into the streaming FMA of
    the scan cohort strategy: ``acc + (scale * w_k) * q`` — the int8
    analogue of ``fused_update.accumulate_pass`` (scale and the normalized
    client weight fold into ONE scalar, so decode costs nothing extra).
  * :func:`sign_pack_pass` — signSGD-style 1-bit pack: 8 consecutive rows
    of sign bits pack into one uint8 row ``(rows // 8, LANES)``; with
    ``with_error=True`` also emits ``g - mu * sign(g)`` (valid elements
    only — see the padding note below).
  * :func:`sign_unpack_fma_pass` — unpack + decode + FMA in one sweep:
    ``acc + (mu * w_k) * sign``.

Padding note: the flat layout zero-pads each group to a row multiple.  For
int8 the pad is self-inert (g = 0 -> q = 0 -> decode 0), but a sign bit
decodes 0 to ``+mu``, so the unpack kernels mask elements ``>= n_valid``
(the group's true size) back to zero — keeping the "pad is mathematically
inert" invariant every downstream consumer (``flat_sq_norm``, optimizer
slots, error-feedback state) relies on.

All kernels run on CPU with ``interpret=True`` (the tier-1 path) and are
written to lower through Mosaic on TPU (2D ``broadcasted_iota``, sublane
reshapes only); TPU timing is a ROADMAP item alongside the fused-update
backward pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import LANES
from repro.kernels.fused_update.kernel import _block_rows, _scalar_spec

SIGN_PACK = 8         # rows of sign bits per packed uint8 row


# ---------------------------------------------------------------------------
# int8: quantize (+ error) / dequantize-FMA
# ---------------------------------------------------------------------------
def _quantize_i8_kernel(scal_ref, g_ref, *out_refs, with_error: bool):
    inv = scal_ref[0, 0]
    g = g_ref[...]
    q = jnp.clip(jnp.round(g * inv), -127.0, 127.0)
    out_refs[0][...] = q.astype(jnp.int8)
    if with_error:
        scale = scal_ref[0, 1]
        out_refs[1][...] = g - q * scale


def quantize_i8_pass(g: jax.Array, inv_scale, scale, *,
                     with_error: bool = False, block_rows: int = 256,
                     interpret: bool = False):
    """g: (rows, LANES) fp32; inv_scale/scale: scalars (scale = amax/127).
    Returns q (rows, LANES) int8, plus the residual ``g - q * scale`` when
    ``with_error`` (error feedback fused into the quantize sweep)."""
    rows, lanes = g.shape
    assert lanes == LANES, g.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), jnp.int8)]
    out_specs = [tile]
    if with_error:
        out_shape.append(jax.ShapeDtypeStruct((rows, LANES), jnp.float32))
        out_specs.append(tile)
    scalars = jnp.stack([jnp.asarray(inv_scale, jnp.float32),
                         jnp.asarray(scale, jnp.float32)]).reshape(1, 2)
    outs = pl.pallas_call(
        functools.partial(_quantize_i8_kernel, with_error=with_error),
        name="quantize_i8_pass",
        grid=(rows // br,),
        in_specs=[_scalar_spec(2, interpret), tile],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(scalars, g)
    return (outs[0], outs[1]) if with_error else outs[0]


def _dequant_i8_fma_kernel(sw_ref, acc_ref, q_ref, out_ref):
    out_ref[...] = acc_ref[...] + sw_ref[0, 0] * q_ref[...].astype(jnp.float32)


def dequant_i8_fma_pass(acc: jax.Array, q: jax.Array, scale_w, *,
                        block_rows: int = 256, interpret: bool = False
                        ) -> jax.Array:
    """Streaming decode+accumulate: ``acc + scale_w * q`` with
    ``scale_w = scale * w_k`` folded into one scalar — the codec analogue
    of ``fused_update.accumulate_pass``."""
    rows, lanes = acc.shape
    assert lanes == LANES, acc.shape
    br = _block_rows(rows, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _dequant_i8_fma_kernel,
        name="dequant_i8_fma_pass",
        grid=(rows // br,),
        in_specs=[_scalar_spec(1, interpret), tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(scale_w, jnp.float32).reshape(1, 1), acc, q)


# ---------------------------------------------------------------------------
# sign1bit: pack (+ error) / unpack-FMA
# ---------------------------------------------------------------------------
def _sign_bits(g: jax.Array) -> jax.Array:
    """1 where g >= 0 else 0 (int32).  sign(0) := +1 so decode is a pure
    two-point alphabet {-mu, +mu}; the pad mask restores exact zeros."""
    return (g >= 0.0).astype(jnp.int32)


def _valid_mask(i, rows_block: int, lanes: int, n_valid) -> jax.Array:
    """Elements of this (rows_block, lanes) tile whose row-major flat index
    (within the whole group buffer) is < n_valid."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows_block, lanes), 0) \
        + i * rows_block
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_block, lanes), 1)
    return (row * lanes + lane) < n_valid


def _sign_pack_kernel(scal_ref, n_ref, g_ref, *out_refs, with_error: bool,
                      rows_block: int):
    i = pl.program_id(0)
    g = g_ref[...]                                    # (rows_block, LANES)
    bits = _sign_bits(g).reshape(rows_block // SIGN_PACK, SIGN_PACK, LANES)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, SIGN_PACK, 1), 1)
    out_refs[0][...] = jnp.sum(bits << shifts, axis=1).astype(jnp.uint8)
    if with_error:
        mu = scal_ref[0, 0]
        s = (2 * _sign_bits(g) - 1).astype(jnp.float32)
        dec = mu * jnp.where(
            _valid_mask(i, rows_block, LANES, n_ref[0, 0]), s, 0.0)
        out_refs[1][...] = g - dec


def sign_pack_pass(g: jax.Array, mu, n_valid: int, *,
                   with_error: bool = False, block_rows: int = 256,
                   interpret: bool = False):
    """g: (rows, LANES) fp32 -> packed sign bits (rows // 8, LANES) uint8
    (row r of g lands in bit ``r % 8`` of packed row ``r // 8``).  ``mu``
    is the per-group magnitude (mean |g| over the n_valid true elements);
    with ``with_error`` also emits ``g - mu * sign(g)`` (pad masked to 0)
    in the same sweep."""
    rows, lanes = g.shape
    assert lanes == LANES and rows % SIGN_PACK == 0, g.shape
    br = _block_rows(rows, block_rows)
    if br % SIGN_PACK:                     # rows is a multiple of 8, so a
        br = SIGN_PACK                     # full-pack tile always exists
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    pack_tile = pl.BlockSpec((br // SIGN_PACK, LANES), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows // SIGN_PACK, LANES), jnp.uint8)]
    out_specs = [pack_tile]
    if with_error:
        out_shape.append(jax.ShapeDtypeStruct((rows, LANES), jnp.float32))
        out_specs.append(tile)
    outs = pl.pallas_call(
        functools.partial(_sign_pack_kernel, with_error=with_error,
                          rows_block=br),
        name="sign_pack_pass",
        grid=(rows // br,),
        in_specs=[_scalar_spec(1, interpret), _scalar_spec(1, interpret),
                  tile],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(mu, jnp.float32).reshape(1, 1),
      jnp.asarray(n_valid, jnp.int32).reshape(1, 1), g)
    return (outs[0], outs[1]) if with_error else outs[0]


def _sign_unpack_fma_kernel(muw_ref, n_ref, acc_ref, p_ref, out_ref, *,
                            rows_block: int):
    i = pl.program_id(0)
    packed = p_ref[...].astype(jnp.int32)             # (rows_block/8, LANES)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, SIGN_PACK, 1), 1)
    bits = (packed[:, None, :] >> shifts) & 1
    s = (2 * bits - 1).astype(jnp.float32).reshape(rows_block, LANES)
    dec = jnp.where(_valid_mask(i, rows_block, LANES, n_ref[0, 0]), s, 0.0)
    out_ref[...] = acc_ref[...] + muw_ref[0, 0] * dec


def sign_unpack_fma_pass(acc: jax.Array, packed: jax.Array, mu_w,
                         n_valid: int, *, block_rows: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Unpack + decode + streaming FMA: ``acc + mu_w * sign`` with
    ``mu_w = mu * w_k`` folded into one scalar; packed-pad elements
    (flat index >= n_valid) contribute exact zeros."""
    rows, lanes = acc.shape
    assert lanes == LANES and rows % SIGN_PACK == 0, acc.shape
    assert packed.shape == (rows // SIGN_PACK, LANES), packed.shape
    br = _block_rows(rows, block_rows)
    if br % SIGN_PACK:
        br = SIGN_PACK
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    pack_tile = pl.BlockSpec((br // SIGN_PACK, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_sign_unpack_fma_kernel, rows_block=br),
        name="sign_unpack_fma_pass",
        grid=(rows // br,),
        in_specs=[_scalar_spec(1, interpret), _scalar_spec(1, interpret),
                  tile, pack_tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(mu_w, jnp.float32).reshape(1, 1),
      jnp.asarray(n_valid, jnp.int32).reshape(1, 1), acc, packed)
