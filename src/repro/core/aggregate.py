"""The synchronous cohort core: unbiased weighted aggregation over the
cohort (Eq. 14), streamed through the flat-buffer accumulators.

:func:`chunked_cohort_gradient_flat` is the one core every synchronous
executor runs (``repro.core.executors``; ``FedConfig.cohort_chunk``): the
cohort is split into chunk-sized slices, client training is vmapped
*within* a chunk, and each chunk's flat gradients stream into the
per-dtype-group ``(rows, LANES)`` fp32 accumulators (``repro.core.flat``)
with the Pallas FMA (``kernels/fused_update`` ``accumulate_pass``).  Peak
gradient memory is one chunk, and the accumulation order is global client
order, so every fp32 bit is invariant to the chunk size: chunk = cohort
(the ``vmap`` registration) and chunk = 1 (``scan``, one client trajectory
alive at a time) give the same round.  A ragged final chunk is padded with
zero-weight clients (``acc + 0*g == acc`` bitwise).  The normalized
weights are computed OUTSIDE the scans (one vectorized divide) so the
through-aggregation backward never accumulates a shared-constant cotangent
inside the nested scans — that is what keeps the ctrl hypergradients
chunk-invariant too, not just the forward pass.

:func:`chunked_cohort_gradient_coded` puts the lossy uplink codec between
each client and the accumulator; :func:`scan_cohort_deltas_flat` keeps the
per-client deltas for the buffered-async pool instead of accumulating them.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def _chunk_cohort_inputs(cohort_batch: PyTree, wn: jax.Array, lwn: jax.Array,
                         rngs: jax.Array, chunk: int):
    """(cohort, ...) round inputs -> (n_chunks, chunk, ...) slices.

    A ragged final chunk is ZERO-WEIGHT padded: pad slots replicate client
    0's batch/rng (their gradients stay finite) but carry normalized weight
    0, and ``acc + 0 * g == acc`` bitwise for finite g — the padding is
    mathematically inert, never silently-wrong math (regression-tested in
    tests/test_chunked_executor.py)."""
    cohort = wn.shape[0]
    n_chunks = -(-cohort // chunk)
    pad = n_chunks * chunk - cohort

    def rep0(x):
        if pad:
            x = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)], axis=0)
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    def zero(v):
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        return v.reshape(n_chunks, chunk)

    return (jax.tree.map(rep0, cohort_batch), zero(wn), zero(lwn),
            rep0(rngs), n_chunks, pad)


def _stream_flat_chunks(client_update: Callable, w_t: PyTree, lr,
                        batch_c: PyTree, wn_c: jax.Array, lwn_c: jax.Array,
                        rng_c: jax.Array, *, spec, has_rng: bool,
                        spmd_axis_name=None, use_ref: bool = False,
                        interpret: Optional[bool] = None, codec=None,
                        residuals_c: Optional[tuple] = None):
    """The chunked streaming core shared by every synchronous executor
    and each shard of the two-tier sharded executor.

    Outer ``lax.scan`` over chunks; within a chunk the clients vmap, then an
    inner ``lax.scan`` FMAs each client's flat gradient into the per-dtype-
    group accumulators IN GLOBAL CLIENT ORDER — so the fp32 accumulation
    sequence (and every bit of the result) is invariant to the chunk size.
    Weights arrive pre-normalized (see the module docstring for why that
    also makes the through-aggregation backward chunk-invariant).  Each
    chunk body runs under ``jax.checkpoint``: the backward sweep recomputes
    one chunk of client trajectories at a time, which is where the per-chunk
    dw_k hypergradient recompute of ``meta_mode='through_aggregation'``
    comes from.

    ``codec`` switches the inner step to the lossy uplink
    (:func:`repro.comm.transport.client_coded_accumulate`, decode fused into
    the FMA); ``residuals_c`` are the matching (n_chunks, chunk, rows,
    LANES) error-feedback slices.  Returns (accs, loss, new_residuals_c)."""
    from repro.core import flat as flat_mod           # lazy: import cycle
    from repro.kernels.fused_update.ops import flat_accumulate
    if codec is not None:
        from repro.comm.transport import client_coded_accumulate
    accum = flat_accumulate(use_ref, interpret)
    coded = codec is not None
    # Keep the normalized loss weights opaque to the algebraic simplifier
    # so the metric accumulation below stays a literal mul-then-add in
    # every chunk graph (defensive: the loss chain is plain XLA ops, unlike
    # the gradient FMA whose Pallas call is already an optimization
    # boundary).
    lwn_c = lax.optimization_barrier(lwn_c)

    def chunk_body(carry, inp):
        accs, l_acc = carry
        if coded:
            cb, wc, lwc, rc, res_c = inp
        else:
            cb, wc, lwc, rc = inp

        def one(batch, r):
            return client_update(w_t, batch, lr, r if has_rng else None)

        if wn_c.shape[1] == 1 and spmd_axis_name is None:
            # chunk width 1: run the client UNBATCHED.  A width-1 vmap
            # changes how XLA:CPU emits the client loss reduction (observed
            # 1-ulp per-client loss drift), and this path is pinned
            # bit-identical to the unbatched async-delta body.
            g_one, l_one = one(jax.tree.map(lambda x: x[0], cb), rc[0])
            g_stack = jax.tree.map(lambda x: x[None], g_one)
            losses = l_one[None]
        else:
            g_stack, losses = jax.vmap(one, spmd_axis_name=spmd_axis_name)(
                cb, rc)
        g_bufs = tuple(flat_mod.flatten_stacked(spec, g_stack))

        def client_body(c2, kin):
            a2, l2 = c2
            if coded:
                gk, wk, lwk, lk, res_k = kin
                a2, r_new = client_coded_accumulate(codec, spec, a2, gk,
                                                    wk, res_k)
            else:
                gk, wk, lwk, lk = kin
                a2 = tuple(accum(a, g, wk) for a, g in zip(a2, gk))
                r_new = None
            return (a2, l2 + lwk * lk), r_new

        xs = ((g_bufs, wc, lwc, losses, res_c) if coded
              else (g_bufs, wc, lwc, losses))
        (accs, l_acc), r_new_c = lax.scan(client_body, (accs, l_acc), xs)
        return (accs, l_acc), r_new_c

    chunk_body = jax.checkpoint(chunk_body, prevent_cse=False)
    acc0 = tuple(flat_mod.zeros_flat(spec))
    xs = ((batch_c, wn_c, lwn_c, rng_c, residuals_c) if coded
          else (batch_c, wn_c, lwn_c, rng_c))
    (G, mean_loss), res_out = lax.scan(
        chunk_body, (acc0, jnp.zeros((), jnp.float32)), xs)
    return G, mean_loss, res_out


def chunked_cohort_gradient_flat(client_update: Callable, w_t: PyTree,
                                 cohort_batch: PyTree,
                                 client_weights: jax.Array, lr, rng, *,
                                 spec, chunk: int,
                                 loss_weights: Optional[jax.Array] = None,
                                 spmd_axis_name=None, use_ref: bool = False,
                                 interpret: Optional[bool] = None
                                 ) -> Tuple[list, jax.Array]:
    """Chunked streaming cohort execution — the core behind every
    synchronous executor (``vmap`` pins chunk = cohort, ``scan`` chunk = 1).

    The per-client rng split runs over the TRUE cohort, so rng streams are
    chunking-invariant; with the pre-normalized FMA weights and the
    client-order loss accumulation, every chunk size gives the same bits,
    and larger chunks trade peak gradient memory (one chunk of
    trajectories) for vmap throughput.  Differentiable w.r.t.
    ``client_weights``: the accumulate custom VJP emits dw_k = <g_k, dG>
    with g_k recomputed per chunk under ``jax.checkpoint`` — the
    ``meta_mode="through_aggregation"`` hypergradient.  ``loss_weights``
    (default: ``client_weights``) weight the loss metric separately, so
    through_aggregation aggregates with the controllable eff_w but reports
    the n_k-weighted loss.  Returns (G_groups, mean_loss): the Eq. (14)
    weighted-mean flat buffers, one per dtype group of ``spec``."""
    cohort = client_weights.shape[0]
    chunk = max(1, min(int(chunk), cohort))
    rngs = (jax.random.split(rng, cohort) if rng is not None
            else jnp.zeros((cohort, 2), jnp.uint32))
    w32 = client_weights.astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w32), 1e-30)
    # the loss-metric normalization is issued as its own reduce rather
    # than aliasing wsum (defensive): the metric chain then keeps the same
    # shape in every chunk graph no matter how the gradient normalization
    # fuses with its chunk-size-dependent consumers
    lw32 = (w32 if loss_weights is None
            else loss_weights.astype(jnp.float32))
    lwsum = jnp.maximum(jnp.sum(lw32), 1e-30)
    batch_c, wn_c, lwn_c, rng_c, _, _ = _chunk_cohort_inputs(
        cohort_batch, w32 / wsum, lw32 / lwsum, rngs, chunk)
    G, mean_loss, _ = _stream_flat_chunks(
        client_update, w_t, lr, batch_c, wn_c, lwn_c, rng_c, spec=spec,
        has_rng=rng is not None, spmd_axis_name=spmd_axis_name,
        use_ref=use_ref, interpret=interpret)
    return list(G), mean_loss


def chunked_cohort_gradient_coded(client_update: Callable, w_t: PyTree,
                                  cohort_batch: PyTree,
                                  client_weights: jax.Array, lr, rng, *,
                                  spec, chunk: int, codec,
                                  residuals: Optional[tuple] = None,
                                  spmd_axis_name=None
                                  ) -> Tuple[list, jax.Array,
                                             Optional[tuple]]:
    """:func:`chunked_cohort_gradient_flat` with the lossy uplink codec
    between each client and the accumulator (chunk-local decode-FMA via
    ``kernels/comm``); the loss is weighted by the aggregation weights.
    Not differentiable w.r.t. the weights — lossy codecs are
    ``meta_mode='post'``-only (guarded by the round builder).

    ``residuals``: per-group ``(cohort, rows, LANES)`` error-feedback
    stacks or None.  Pad slots of a ragged chunk carry weight 0, so the
    codec's transmitted-gate leaves their (zero) residuals untouched and
    the unpad slice drops them.  Returns (G_groups, mean_loss,
    new_residuals) in cohort order."""
    cohort = client_weights.shape[0]
    chunk = max(1, min(int(chunk), cohort))
    rngs = (jax.random.split(rng, cohort) if rng is not None
            else jnp.zeros((cohort, 2), jnp.uint32))
    w32 = client_weights.astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w32), 1e-30)
    wn = w32 / wsum
    batch_c, wn_c, lwn_c, rng_c, n_chunks, pad = _chunk_cohort_inputs(
        cohort_batch, wn, wn, rngs, chunk)
    res_c = None
    if residuals is not None:
        def pad_res(x):
            if pad:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
            return x.reshape((n_chunks, chunk) + x.shape[1:])
        res_c = jax.tree.map(pad_res, tuple(residuals))
    G, mean_loss, res_out = _stream_flat_chunks(
        client_update, w_t, lr, batch_c, wn_c, lwn_c, rng_c, spec=spec,
        has_rng=rng is not None, spmd_axis_name=spmd_axis_name,
        codec=codec, residuals_c=res_c)
    new_res = None
    if residuals is not None:
        new_res = jax.tree.map(
            lambda x: x.reshape((n_chunks * chunk,) + x.shape[2:])[:cohort],
            res_out)
    return list(G), mean_loss, new_res


def scan_cohort_deltas_flat(client_update: Callable, w_t: PyTree,
                            cohort_batch: PyTree,
                            client_weights: jax.Array, lr, rng, *,
                            spec, loss_weights: Optional[jax.Array] = None
                            ) -> Tuple[list, jax.Array]:
    """Client-sequential local updates that KEEP the per-client flat deltas
    — ``(cohort, rows, LANES)`` stacked buffers per dtype group — instead
    of accumulating them: the buffered-async pool
    (:mod:`repro.core.async_round`) needs each delta individually, so the
    scan's ys-stacking replaces the carry accumulation.  (This gives up the
    scan strategy's one-delta-alive memory profile; the async runtime pays
    it because the pool holds per-delta state anyway.)

    The per-client rng split is the one :func:`chunked_cohort_gradient_flat`
    uses, so feeding these deltas through the same streaming-FMA sequence
    reproduces the synchronous aggregation bit-for-bit — the fault-free
    async == sync gate."""
    from repro.core import flat as flat_mod           # lazy: import cycle

    cohort = client_weights.shape[0]
    rngs = (jax.random.split(rng, cohort) if rng is not None
            else jnp.zeros((cohort, 2), jnp.uint32))
    lw32 = (client_weights if loss_weights is None
            else loss_weights).astype(jnp.float32)
    lwsum = jnp.maximum(jnp.sum(lw32), 1e-30)

    def body(l_acc, inp):
        batch, lweight, r = inp
        g_k, l_k = client_update(
            w_t, batch, lr, r if rng is not None else None)
        g_bufs = flat_mod.flatten_tree(spec, g_k)
        return l_acc + (lweight / lwsum) * l_k, tuple(g_bufs)

    body = jax.checkpoint(body, prevent_cse=False)
    mean_loss, stacked = lax.scan(
        body, jnp.zeros((), jnp.float32), (cohort_batch, lw32, rngs))
    return list(stacked), mean_loss
