"""Uplink simulation glue: per-client encode -> decode -> Eq. (14)
accumulate over the flat dtype-group buffers, plus the per-client
error-feedback state and the measured-bytes accounting.

The server-side aggregate of decoded gradients is a streaming accumulation
(one client at a time): the synchronous cohort core
(:func:`repro.core.aggregate.chunked_cohort_gradient_coded`, which every
synchronous executor and each shard of the two-tier sharded topology runs)
computes a chunk of client gradients in parallel, then calls
:func:`client_coded_accumulate` for each client of the chunk inside a
``lax.scan`` (encode/decode is a few flat sweeps per client, and the scan
keeps the Pallas codec kernels un-batched).  The buffered-async pool
decodes each delta without aggregating (:func:`coded_decode_stacked`).

Error-feedback state layout (``state["comm"]``): ``{"residual": tuple}``
with one ``(cohort, rows, LANES)`` fp32 buffer per dtype group — client k's
residual lives in slot k of the stack, exactly like ``ctrl["w_logits"]``
keys clients by cohort slot.  It threads through ``init_server_state`` and
checkpoint save/restore like every other server-state entry.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.codecs import GradientCodec
from repro.core.flat import LANES, FlatSpec

PyTree = Any


def init_comm_state(fed, spec: FlatSpec) -> PyTree:
    """Zero per-client error-feedback residuals in the comm-state layout."""
    return {"residual": tuple(
        jnp.zeros((fed.cohort, g.rows, LANES), jnp.float32)
        for g in spec.groups)}


def comm_bytes_per_client(codec: GradientCodec, spec: FlatSpec) -> int:
    """Measured uplink bytes ONE client ships per round under ``codec``
    (static python int — payload shapes/dtypes are trace-time constants)."""
    return sum(codec.payload_bytes(g) for g in spec.groups)


def client_coded_accumulate(codec: GradientCodec, spec: FlatSpec,
                            accs, g_bufs, w, residuals
                            ) -> Tuple[tuple, Optional[tuple]]:
    """One client's uplink across all dtype groups.

    accs/g_bufs: per-group (rows, LANES) fp32 accumulators / gradient;
    w: this client's normalized aggregation weight; residuals: per-group
    error-feedback memory or None.  Returns (new_accs, new_residuals).

    The decode always fuses straight into the aggregate FMA
    (``decode_fma`` — e.g. the int8 ``dequant_i8_fma_pass``), which writes
    into ``acc``'s buffer: a loop-carried ``acc`` is updated in place, and
    a caller that reads ``acc`` again pays XLA's copy; with EF the
    encode additionally emits the residual in its own sweep
    (``encode_ef``), so EF costs no extra HBM pass over the plain path.

    A client with w == 0 did not transmit — a straggler dropped by the
    participation mask (``repro.core.round``), or a zero-n_k client.  Its
    aggregate contribution is already zero, and its EF memory must stay
    UNCHANGED: overwriting it would discard the decoded part of the error
    as if the server had received it, breaking the EF telescoping for
    every dropped round.

    Traced under the ``repro.codec`` named scope (the scale reductions
    beside the codec kernels carry it).
    """
    new_accs, new_res = [], []
    with jax.named_scope("repro.codec"):
        if residuals is None:
            for group, acc, g in zip(spec.groups, accs, g_bufs):
                payload = codec.encode(group, g)
                new_accs.append(codec.decode_fma(group, acc, payload, w))
            return tuple(new_accs), None
        transmitted = (jnp.asarray(w, jnp.float32) > 0.0
                       ).astype(jnp.float32)
        for group, acc, g, res in zip(spec.groups, accs, g_bufs, residuals):
            payload, r_new = codec.encode_ef(group, g + res)
            new_accs.append(codec.decode_fma(group, acc, payload, w))
            new_res.append(transmitted * r_new + (1.0 - transmitted) * res)
    return tuple(new_accs), tuple(new_res)


def coded_decode_stacked(codec: GradientCodec, spec: FlatSpec,
                         g_groups, client_weights: jax.Array,
                         residuals: Optional[tuple]
                         ) -> Tuple[List[jax.Array], Optional[tuple]]:
    """The buffered-async executor's codec stage: encode/decode each
    client's delta INDIVIDUALLY, without aggregating — the async delta pool
    (``repro.core.async_round``) must store what the server actually
    received, because pooled deltas from different rounds are combined only
    at flush time with staleness-dependent weights unknown at encode time.

    Same per-client uplink as :func:`client_coded_accumulate` minus the
    FMA: with EF the payload is error-compensated against the client's
    ``state["comm"]`` slot, and a non-transmitting client (w == 0: masked
    out, crashed, or dropped by fault injection) keeps its residual
    byte-identical — the server received nothing, so no error was
    committed.

    Returns (decoded stacks — list of (cohort, rows, LANES) fp32 per dtype
    group — and new_residuals stacked per group, or None without EF).
    Traced under the ``repro.codec`` named scope."""
    w = client_weights.astype(jnp.float32)

    def body(carry, xs):
        g_k, w_k, res_k = xs
        dec_k, res_out = [], []
        if res_k is None:
            for group, g in zip(spec.groups, g_k):
                dec_k.append(codec.decode(group, codec.encode(group, g)))
            return carry, (tuple(dec_k), None)
        transmitted = (jnp.asarray(w_k, jnp.float32) > 0.0
                       ).astype(jnp.float32)
        for group, g, res in zip(spec.groups, g_k, res_k):
            payload, r_new = codec.encode_ef(group, g + res)
            dec_k.append(codec.decode(group, payload))
            res_out.append(transmitted * r_new + (1.0 - transmitted) * res)
        return carry, (tuple(dec_k), tuple(res_out))

    with jax.named_scope("repro.codec"):
        _, (dec, new_res) = lax.scan(body, (),
                                     (tuple(g_groups), w, residuals))
    return list(dec), new_res
