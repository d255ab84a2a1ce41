"""Cohort-executor plugin registry — HOW a round runs its cohort.

A :class:`CohortExecutor` owns the execution strategy for the per-client
local updates and always yields a **uniform aggregate handle** so server
engines never inspect the strategy:

  * :class:`FlatAggregate` — the fused engine's per-dtype-group
    ``(rows, LANES)`` fp32 buffers holding the Eq. (14) weighted mean
    (``sq_norm`` carries ``||G||^2`` when pass 1 already reduced it);
  * :class:`TreeAggregate` — the weighted-mean pytree (the form the legacy
    tree-map engine consumes).

Every synchronous strategy is a registration over ONE chunked streaming
core (:class:`ChunkedExecutor` — ``repro.core.aggregate``'s
``_stream_flat_chunks``): the cohort is split into ``FedConfig.
cohort_chunk``-sized slices, clients vmap within a slice, and each slice's
flat gradients stream into the dtype-group accumulators via the Pallas FMA
kernels, so peak gradient memory is one chunk no matter the cohort.

  * ``chunked`` — the core itself (``chunk = cohort_chunk``);
  * ``vmap``    — ``chunk = cohort`` (whole cohort in one slice; keeps the
    retained-stack aggregate kernel for its handles);
  * ``scan``    — ``chunk = 1`` (one client trajectory alive at a time);
  * ``sharded`` — the two-tier topology: the cohort axis splits across the
    mesh batch axes under ``shard_map``, each shard streams its slice
    through the same core into per-shard partial accumulators, and a
    ``psum`` reduces them into one :class:`FlatAggregate` whose group
    buffers carry ``PartitionSpec``s (``repro.sharding.specs.
    flat_group_pspecs``).

Because all four share the streaming core, they ALL declare
``supports_reweight = True`` (per-client ``dw_k`` hypergradients via the
accumulate custom VJP, client trajectories recomputed per chunk under
``jax.checkpoint``) and lossy ``codec_capabilities`` (chunk-local
decode-FMA via ``kernels/comm``) — including ``sharded``, which used to
pre-aggregate per leaf and declare both unsupported.

Register a new strategy with :func:`register_executor`; the factory
receives the :class:`~repro.configs.base.FedConfig` plus the round
builder's sharding arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregate import (_chunk_cohort_inputs, _stream_flat_chunks,
                                  chunked_cohort_gradient_coded,
                                  chunked_cohort_gradient_flat,
                                  cohort_gradient, scan_cohort_deltas_flat,
                                  scan_cohort_gradient_flat)
from repro.core.flat import (FlatSpec, constrain_groups, make_flat_spec,
                             unflatten_tree, with_pspecs)
from repro.core.registry import Registry
from repro.kernels.fused_update.ops import flat_weighted_aggregate

PyTree = Any

__all__ = ["FlatAggregate", "TreeAggregate", "ReweightableCohort",
           "CohortExecutor", "register_executor", "get_executor",
           "available_executors", "resolve_executor"]


# ---------------------------------------------------------------------------
# aggregate handles
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FlatAggregate:
    """Eq. (14) weighted mean in the fused engine's flat layout."""
    groups: list                       # per-dtype-group (rows, LANES) fp32
    spec: FlatSpec
    sq_norm: Optional[jax.Array] = None   # ||G||^2 if pass 1 computed it


@dataclasses.dataclass
class TreeAggregate:
    """Eq. (14) weighted mean as a pytree (sharding constraints intact)."""
    tree: PyTree


@dataclasses.dataclass
class ReweightableCohort:
    """A cohort whose aggregation can be re-run under different weights.

    ``aggregate(weights)`` is differentiable w.r.t. ``weights`` and returns
    ``(handle, client_loss)`` where the loss metric is weighted by the raw
    n_k the cohort was created with, so it reports the same number no
    matter what effective weights the controllable state chose."""
    aggregate: Callable      # (weights,) -> (handle, client_loss)


# ---------------------------------------------------------------------------
# executor protocol + registry
# ---------------------------------------------------------------------------
class CohortExecutor:
    """Protocol.  Subclass (or duck-type) and register a factory."""
    name: str = "?"
    produces: frozenset = frozenset()        # subset of {"flat", "tree"}
    supports_reweight: bool = False
    # which GradientCodec classes this executor can run: {"none"} means the
    # plain (uncompressed) path only; {"none", "lossy"} adds run_coded —
    # per-client encode/decode on the uplink (repro.comm)
    codec_capabilities: frozenset = frozenset({"none"})

    def run(self, client_update, params, cohort_batch, client_weights,
            lr, rng, *, kind: str) -> Tuple[Any, jax.Array]:
        """Run every client and aggregate.  Returns (handle, client_loss);
        ``kind`` is one of this executor's ``produces``."""
        raise NotImplementedError

    def run_coded(self, client_update, params, cohort_batch, client_weights,
                  lr, rng, *, codec, comm) -> Tuple[Any, jax.Array, Any]:
        """Run every client, pass each gradient through ``codec``'s
        encode/decode (the uplink simulation) and aggregate the decoded
        gradients.  ``comm`` is the error-feedback state
        (``state["comm"]``) or None.  Returns (flat handle, client_loss,
        new_comm).  Only executors declaring the 'lossy' codec capability
        implement this."""
        raise NotImplementedError(
            f"cohort executor {self.name!r} does not support lossy "
            "gradient codecs (declares codec_capabilities="
            f"{sorted(self.codec_capabilities)})")

    def reweightable(self, client_update, params, cohort_batch,
                     client_weights, lr, rng) -> ReweightableCohort:
        """Run (or prepare) the cohort so aggregation can be repeated under
        different weights; ``client_weights`` (n_k) weight the loss
        metric."""
        raise NotImplementedError(
            f"cohort executor {self.name!r} does not support reweightable "
            "aggregation")


_EXECUTORS = Registry("cohort executor",
                      "repro.core.executors.register_executor")


def register_executor(name: str):
    """Decorator registering an executor factory:
    ``factory(fed, *, spmd_axis_name, grad_shardings) -> CohortExecutor``."""
    def deco(factory: Callable) -> Callable:
        _EXECUTORS.register(name, factory)
        return factory
    return deco


def get_executor(name: str) -> Callable:
    return _EXECUTORS.get(name)


def available_executors() -> tuple:
    return _EXECUTORS.names()


def resolve_executor(fed, *, spmd_axis_name=None, grad_shardings=None,
                     executor: Optional[str] = None) -> CohortExecutor:
    """Pick the executor for a round: an explicit registry ``executor``
    name wins; otherwise ``grad_shardings`` selects the two-tier sharded
    executor, ``fed.cohort_chunk`` selects the chunked streaming executor,
    and ``fed.cohort_strategy`` selects vmap/scan."""
    if executor is None:
        if grad_shardings is not None:
            executor = "sharded"
        elif fed.cohort_chunk is not None:
            executor = "chunked"
        else:
            executor = fed.cohort_strategy
    elif grad_shardings is not None and executor != "sharded":
        # an explicit override would silently drop the constraints: only
        # the 'sharded' executor turns grad_shardings into its two-tier
        # shard_map topology (cohort split across the mesh batch axes,
        # partial flat accumulators psum-reduced).  Any other executor
        # ignores them and GSPMD would replicate the per-chunk gradient
        # buffers on every shard — the HBM blow-up the sharded executor
        # exists to prevent; fail loudly instead
        raise ValueError(
            f"grad_shardings is set but executor={executor!r} was "
            "explicitly requested; only the 'sharded' executor honors "
            "per-leaf gradient sharding constraints (two-tier shard_map "
            "aggregation). Drop the executor override (grad_shardings "
            "selects it automatically) or drop grad_shardings.")
    return get_executor(executor)(fed, spmd_axis_name=spmd_axis_name,
                                  grad_shardings=grad_shardings)


# ---------------------------------------------------------------------------
# built-in executors
# ---------------------------------------------------------------------------
@register_executor("chunked")
class ChunkedExecutor(CohortExecutor):
    """The chunked streaming core: ``cohort_chunk`` clients vmap per slice,
    each slice's flat gradients FMA into the per-dtype-group accumulators
    (Pallas streaming kernels), chunks run under an outer ``lax.scan`` with
    ``jax.checkpoint`` — peak gradient memory is ONE chunk, and the fp32
    accumulation order (hence every output bit) is invariant to the chunk
    size.  vmap/scan/sharded subclass this with pinned chunk sizes."""
    name = "chunked"
    produces = frozenset({"flat", "tree"})
    supports_reweight = True
    codec_capabilities = frozenset({"none", "lossy"})

    def __init__(self, fed, *, spmd_axis_name=None, grad_shardings=None):
        self._agg_dtype = jnp.dtype(fed.grad_agg_dtype)
        self._spmd = spmd_axis_name
        self._shardings = grad_shardings
        self._chunk = (None if fed.cohort_chunk is None
                       else int(fed.cohort_chunk))

    def _chunk_for(self, cohort: int) -> int:
        return cohort if self._chunk is None else self._chunk

    def _make_spec(self, params) -> FlatSpec:
        return make_flat_spec(params)

    # -- the one streaming primitive subclasses override -------------------
    def _flat(self, client_update, params, cohort_batch, client_weights,
              lr, rng, *, spec, loss_weights=None):
        return chunked_cohort_gradient_flat(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec, chunk=self._chunk_for(client_weights.shape[0]),
            loss_weights=loss_weights, spmd_axis_name=self._spmd)

    def _coded(self, client_update, params, cohort_batch, client_weights,
               lr, rng, *, spec, codec, residuals):
        return chunked_cohort_gradient_coded(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec, chunk=self._chunk_for(client_weights.shape[0]),
            codec=codec, residuals=residuals, spmd_axis_name=self._spmd)

    # -- uniform handle construction on top --------------------------------
    def run(self, client_update, params, cohort_batch, client_weights,
            lr, rng, *, kind):
        spec = self._make_spec(params)
        Gs, loss = self._flat(client_update, params, cohort_batch,
                              client_weights, lr, rng, spec=spec)
        if kind == "tree":
            # same streamed fp32 buffers, viewed as a pytree in agg dtype
            return TreeAggregate(
                unflatten_tree(spec, Gs, dtype=self._agg_dtype)), loss
        return FlatAggregate(Gs, spec, sq_norm=None), loss

    def run_coded(self, client_update, params, cohort_batch, client_weights,
                  lr, rng, *, codec, comm):
        spec = self._make_spec(params)
        res = comm["residual"] if comm is not None else None
        Gs, loss, new_res = self._coded(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec, codec=codec, residuals=res)
        new_comm = {"residual": new_res} if comm is not None else None
        return FlatAggregate(Gs, spec, sq_norm=None), loss, new_comm

    def reweightable(self, client_update, params, cohort_batch,
                     client_weights, lr, rng):
        # nothing is retained: aggregate() re-streams the chunks under the
        # new weights; the accumulate custom VJP supplies per-client weight
        # cotangents with g_k recomputed chunk-by-chunk under
        # jax.checkpoint — through_aggregation at one chunk of memory
        spec = self._make_spec(params)

        def aggregate(weights):
            Gs, loss = self._flat(client_update, params, cohort_batch,
                                  weights, lr, rng, spec=spec,
                                  loss_weights=client_weights)
            return FlatAggregate(Gs, spec, sq_norm=None), loss

        return ReweightableCohort(aggregate=aggregate)


@register_executor("vmap")
class VmapExecutor(ChunkedExecutor):
    """Client-parallel: the whole cohort is one chunk.  Keeps the
    retained-stack fast path for its plain/reweightable handles — every
    local trajectory runs simultaneously, the (cohort, *param) gradient
    stack stays live, and the differentiable aggregate kernel (pass 1)
    reduces it, fusing the clip-norm ``||G||^2``.  The coded path streams
    through the chunked core (chunk = cohort: one vmap, then the
    per-client uplink scan)."""
    name = "vmap"

    def __init__(self, fed, *, spmd_axis_name=None, grad_shardings=None):
        super().__init__(fed, spmd_axis_name=spmd_axis_name,
                         grad_shardings=grad_shardings)
        self._chunk = None               # whole cohort in one slice

    def _stack(self, client_update, params, cohort_batch, client_weights,
               lr, rng):
        return cohort_gradient(
            client_update, params, cohort_batch, client_weights, lr, rng,
            strategy="vmap", agg_dtype=self._agg_dtype,
            spmd_axis_name=self._spmd, aggregate=False)

    def run(self, client_update, params, cohort_batch, client_weights,
            lr, rng, *, kind):
        if kind == "tree":
            G, loss = cohort_gradient(
                client_update, params, cohort_batch, client_weights, lr,
                rng, strategy="vmap", agg_dtype=self._agg_dtype,
                spmd_axis_name=self._spmd, grad_shardings=self._shardings)
            return TreeAggregate(G), loss
        g_stack, loss = self._stack(client_update, params, cohort_batch,
                                    client_weights, lr, rng)
        spec = make_flat_spec(params)
        Gs, ssq = flat_weighted_aggregate(spec, g_stack, client_weights)
        return FlatAggregate(Gs, spec, sq_norm=ssq), loss

    def reweightable(self, client_update, params, cohort_batch,
                     client_weights, lr, rng):
        # clients run ONCE here (loss already n_k-weighted); aggregate()
        # only re-reduces the retained stack under new weights (cheap,
        # differentiable via the aggregate kernel's custom VJP)
        spec = make_flat_spec(params)
        g_stack, loss = self._stack(client_update, params, cohort_batch,
                                    client_weights, lr, rng)

        def aggregate(weights):
            Gs, ssq = flat_weighted_aggregate(spec, g_stack, weights)
            return FlatAggregate(Gs, spec, sq_norm=ssq), loss

        return ReweightableCohort(aggregate=aggregate)


@register_executor("scan")
class ScanExecutor(ChunkedExecutor):
    """Client-sequential: the chunked core pinned at chunk = 1, one
    trajectory alive at a time.  The streamed forward (plain and coded)
    is inherited; the reweightable form keeps the dedicated cohort scan
    (:func:`repro.core.aggregate.scan_cohort_gradient_flat`) whose
    backward accumulation order the through_aggregation ctrl tests pin."""
    name = "scan"

    def __init__(self, fed, *, spmd_axis_name=None, grad_shardings=None):
        super().__init__(fed, spmd_axis_name=None, grad_shardings=None)
        self._chunk = 1                  # one client per slice

    def reweightable(self, client_update, params, cohort_batch,
                     client_weights, lr, rng):
        spec = self._make_spec(params)

        def aggregate(weights):
            Gs, loss = scan_cohort_gradient_flat(
                client_update, params, cohort_batch, weights, lr, rng,
                spec=spec, loss_weights=client_weights)
            return FlatAggregate(Gs, spec, sq_norm=None), loss

        return ReweightableCohort(aggregate=aggregate)


def _mesh_from_shardings(shardings) -> Optional[Any]:
    """The device mesh behind a grad_shardings pytree (first NamedSharding
    leaf), or None when the constraints carry no mesh (e.g. plain
    PartitionSpecs or placeholder trees) — then the sharded executor
    degrades to the single-process chunked core."""
    from jax.sharding import NamedSharding
    for leaf in jax.tree.leaves(shardings):
        if isinstance(leaf, NamedSharding):
            return leaf.mesh
    return None


@register_executor("sharded")
class ShardedExecutor(ChunkedExecutor):
    """Two-tier aggregation topology for explicitly sharded cohorts
    (``grad_shardings``).

    Tier 1: ``shard_map`` over the mesh batch axes splits the cohort —
    every shard runs its slice of clients through the chunked streaming
    core into PARTIAL per-dtype-group flat accumulators (the pre-normalized
    client weights make partial sums combine exactly).  Tier 2: one
    ``psum`` over the batch axes reduces the partials into the same
    :class:`FlatAggregate` handle every engine consumes, and the group
    buffers keep ``PartitionSpec``s (rows over the model axis, via
    :func:`repro.sharding.specs.flat_group_pspecs`) so GSPMD never
    replicates them.

    Because tier 1 IS the chunked core, the two-tier path supports
    everything the single-process executors do: ``through_aggregation``
    reweighting (per-client dw_k hypergradients recomputed per chunk under
    ``jax.checkpoint``, differentiated straight through the psum) and lossy
    codecs (chunk-local decode-FMA, per-client error-feedback residuals
    sharded over the cohort axis)."""
    name = "sharded"

    def __init__(self, fed, *, spmd_axis_name=None, grad_shardings=None):
        super().__init__(fed, spmd_axis_name=None,
                         grad_shardings=grad_shardings)
        self._mesh = _mesh_from_shardings(grad_shardings)
        if self._mesh is not None:
            from repro.sharding.specs import batch_axes
            ba = batch_axes(self._mesh)
            self._ba = ba[0] if len(ba) == 1 else ba

    def _make_spec(self, params) -> FlatSpec:
        spec = make_flat_spec(params)
        if self._mesh is not None:
            from repro.sharding.specs import flat_group_pspecs
            spec = with_pspecs(spec, flat_group_pspecs(spec, self._mesh),
                               self._mesh)
        return spec

    def _two_tier(self, client_update, params, cohort_batch, client_weights,
                  lr, rng, *, spec, loss_weights=None, codec=None,
                  residuals=None):
        from jax.sharding import PartitionSpec as P
        from repro.sharding.specs import axis_size

        mesh, ba = self._mesh, self._ba
        n_shards = axis_size(mesh, ba)
        cohort = client_weights.shape[0]
        has_rng = rng is not None
        rngs = (jax.random.split(rng, cohort) if has_rng
                else jnp.zeros((cohort, 2), jnp.uint32))
        # normalize weights GLOBALLY (over the true cohort) so per-shard
        # partial FMAs psum to exactly the Eq. (14) weighted mean
        w32 = client_weights.astype(jnp.float32)
        wsum = jnp.maximum(jnp.sum(w32), 1e-30)
        # loss normalization issued as its own reduce (not aliased to
        # wsum), exactly like chunked_cohort_gradient_flat, keeping the
        # loss metric bit-identical to the single-host chunked core
        lw32 = (w32 if loss_weights is None
                else loss_weights.astype(jnp.float32))
        lwsum = jnp.maximum(jnp.sum(lw32), 1e-30)
        wn, lwn = w32 / wsum, lw32 / lwsum
        # pad the cohort to a shard multiple: replicated client-0 rows with
        # weight 0 (inert — acc + 0*g == acc; residual slots stay zero)
        pad = (-cohort) % n_shards
        if pad:
            def rep0(x):
                return jnp.concatenate(
                    [x, jnp.repeat(x[:1], pad, axis=0)], axis=0)
            cohort_batch = jax.tree.map(rep0, cohort_batch)
            rngs = rep0(rngs)
            wn = jnp.concatenate([wn, jnp.zeros((pad,), wn.dtype)])
            lwn = jnp.concatenate([lwn, jnp.zeros((pad,), lwn.dtype)])
        per_shard = (cohort + pad) // n_shards
        lchunk = max(1, min(self._chunk_for(cohort), per_shard))
        res_p = None
        if residuals is not None:
            res_p = jax.tree.map(
                lambda x: (jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
                    if pad else x),
                tuple(residuals))

        def tier1(w_t, batch_l, wn_l, lwn_l, rngs_l, res_l):
            # local slice -> chunked stream -> partial accumulators
            batch_c, wn_c, lwn_c, rng_c, n_chunks, lpad = \
                _chunk_cohort_inputs(batch_l, wn_l, lwn_l, rngs_l, lchunk)
            res_c = None
            if res_l is not None:
                res_c = jax.tree.map(
                    lambda x: (jnp.concatenate(
                        [x, jnp.zeros((lpad,) + x.shape[1:], x.dtype)])
                        if lpad else x).reshape(
                            (n_chunks, lchunk) + x.shape[1:]),
                    res_l)
            G, loss, res_out = _stream_flat_chunks(
                client_update, w_t, lr, batch_c, wn_c, lwn_c, rng_c,
                spec=spec, has_rng=has_rng, codec=codec, residuals_c=res_c)
            # tier 2: the cross-shard reduce into the global aggregate
            G = tuple(jax.lax.psum(g, ba) for g in G)
            loss = jax.lax.psum(loss, ba)
            if res_out is not None:
                res_out = jax.tree.map(
                    lambda x: x.reshape((n_chunks * lchunk,) + x.shape[2:])
                    [:per_shard], res_out)
            return G, loss, res_out

        # the jit is required even under an outer jit: shard_map bodies
        # containing remat/custom_vjp calls cannot be evaluated eagerly
        fn = jax.jit(jax.shard_map(
            tier1, mesh=mesh,
            in_specs=(P(), P(self._ba), P(self._ba), P(self._ba),
                      P(self._ba), P(self._ba)),
            out_specs=(P(), P(), P(self._ba)),
            # the accumulate/aggregate custom_vjp kernels inside the shard
            # body break shard_map's varying-axis inference
            check_vma=False))
        G, loss, res_out = fn(params, cohort_batch, wn, lwn, rngs, res_p)
        G = constrain_groups(spec, G)
        new_res = None
        if residuals is not None:
            new_res = jax.tree.map(lambda x: x[:cohort], res_out)
        return list(G), loss, new_res

    def _flat(self, client_update, params, cohort_batch, client_weights,
              lr, rng, *, spec, loss_weights=None):
        if self._mesh is None:
            return super()._flat(
                client_update, params, cohort_batch, client_weights, lr,
                rng, spec=spec, loss_weights=loss_weights)
        Gs, loss, _ = self._two_tier(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec, loss_weights=loss_weights)
        return Gs, loss

    def _coded(self, client_update, params, cohort_batch, client_weights,
               lr, rng, *, spec, codec, residuals):
        if self._mesh is None:
            return super()._coded(
                client_update, params, cohort_batch, client_weights, lr,
                rng, spec=spec, codec=codec, residuals=residuals)
        return self._two_tier(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec, codec=codec, residuals=residuals)


@register_executor("buffered_async")
class BufferedAsyncExecutor(CohortExecutor):
    """The buffered-async runtime's cohort stage: runs the local updates
    with the configured base strategy (``fed.cohort_strategy``: vmap or
    scan) but returns the **per-client flat deltas** ``(cohort, rows,
    LANES)`` instead of an aggregate handle — the delta pool
    (:mod:`repro.core.async_round`) consumes each delta individually, with
    its own staleness-weighted flush.  Not selectable as a synchronous
    executor: :meth:`run` raises, pointing at ``engine='buffered_async'``
    (the round builder routes async engines through the tick program)."""
    name = "buffered_async"
    produces = frozenset({"flat"})
    supports_reweight = False
    codec_capabilities = frozenset({"none", "lossy"})

    def __init__(self, fed, *, spmd_axis_name=None, grad_shardings=None):
        if grad_shardings is not None:
            raise ValueError(
                "the buffered_async executor keeps a replicated delta pool "
                "(per-client staleness slots), so per-leaf grad_shardings "
                "cannot apply; drop grad_shardings or use a synchronous "
                "engine")
        if fed.cohort_chunk is not None:
            raise ValueError(
                "cohort_chunk streams clients through an aggregate "
                "accumulator, but the buffered_async executor must keep "
                "every client's delta individually for the staleness pool "
                "— there is nothing to chunk. Drop cohort_chunk or use a "
                "synchronous engine.")
        if fed.cohort_strategy not in ("vmap", "scan"):
            raise ValueError(
                "the buffered_async executor wraps a base cohort_strategy "
                f"of 'vmap' or 'scan', got {fed.cohort_strategy!r}")
        self._base = fed.cohort_strategy
        self._spmd = spmd_axis_name

    def run(self, client_update, params, cohort_batch, client_weights,
            lr, rng, *, kind):
        raise NotImplementedError(
            "the buffered_async executor produces per-delta stacks for the "
            "async tick program (repro.core.async_round), not a "
            "synchronous aggregate; select engine='buffered_async' so the "
            "round builder routes through it")

    def run_deltas(self, client_update, params, cohort_batch,
                   client_weights, lr, rng, *, spec):
        """(stacked flat deltas per dtype group, weighted client loss).
        ``client_weights`` only weight the loss metric here — aggregation
        weights are the pool's business at flush time."""
        if self._base == "vmap":
            from repro.core.flat import flatten_stacked
            g_stack, loss = cohort_gradient(
                client_update, params, cohort_batch, client_weights, lr,
                rng, strategy="vmap", spmd_axis_name=self._spmd,
                aggregate=False)
            return flatten_stacked(spec, g_stack), loss
        return scan_cohort_deltas_flat(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec)

    def run_deltas_coded(self, client_update, params, cohort_batch,
                         client_weights, lr, rng, *, spec, codec, comm):
        """:meth:`run_deltas` + the lossy uplink: every delta is encoded,
        (optionally) error-compensated against its ``state["comm"]`` slot
        and decoded server-side BEFORE pooling — the pool stores what the
        server actually received.  Returns (decoded stacks, loss,
        new_residuals)."""
        from repro.comm.transport import coded_decode_stacked
        g_groups, loss = self.run_deltas(
            client_update, params, cohort_batch, client_weights, lr, rng,
            spec=spec)
        res = comm["residual"] if comm is not None else None
        dec, new_res = coded_decode_stacked(codec, spec, g_groups,
                                            client_weights, res)
        return dec, loss, new_res
