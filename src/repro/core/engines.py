"""Server-engine plugin registry — WHAT the server does with an aggregate.

A :class:`ServerEngine` consumes the uniform aggregate handle a cohort
executor produced (:mod:`repro.core.executors`) and applies the server-side
update (clip -> optimizer -> parameter write).  Engines declare:

  * ``accepts`` / ``preferred`` — which handle kinds they consume, so the
    round builder can ask the executor for the right one (``FusedFlatEngine``
    prefers flat buffers but still accepts a tree handle, which it
    flattens);
  * ``meta_capabilities`` — which FedMeta modes the engine can power.
    ``"through_aggregation"`` means the engine's apply is differentiable
    w.r.t. the aggregate and the step size (the fused engine's hand-written
    custom VJP), so hypergradients of the D_meta loss can flow into the
    controllable per-client-weights state.  What used to be a ValueError
    maze over ``fused_update`` flags is now this capability check.

Built-ins:

  * ``legacy_tree`` — the tree-map stages (weighted mean consumed as a
    pytree -> clip-norm scale -> fp32 cast -> ``server_opt.apply``);
  * ``fused_flat`` — the flat-buffer Pallas engine
    (``repro.kernels.fused_update``): clip + optimizer + param write in one
    HBM pass over per-dtype-group buffers, differentiable end to end.

Register alternatives with :func:`register_engine` (e.g. a sign-SGD or
quantized engine) and select them via
``make_federated_round(..., engine="name")``.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import server_opt
from repro.core.executors import FlatAggregate, TreeAggregate
from repro.core.flat import flat_sq_norm, flatten_tree, make_flat_spec
from repro.core.registry import Registry
from repro.kernels.fused_update.ops import (flat_apply_groups,
                                            init_flat_opt_state)

PyTree = Any

__all__ = ["ServerEngine", "LegacyTreeEngine", "FusedFlatEngine",
           "BufferedAsyncEngine",
           "register_engine", "get_engine", "available_engines",
           "resolve_engine", "tree_global_norm"]


def tree_global_norm(g: PyTree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(g)))


class ServerEngine:
    """Protocol.  Engines are constructed per-config via the registry
    factory ``factory(fed) -> ServerEngine``."""
    name: str = "?"
    accepts: frozenset = frozenset()          # handle kinds consumed
    preferred: str = "tree"                   # kind to request if available
    meta_capabilities: frozenset = frozenset({"post"})
    # which GradientCodec classes this engine can sit behind: lossy codecs
    # decode into flat dtype-group buffers (repro.comm), so only engines
    # consuming flat handles can declare "lossy"
    codec_capabilities: frozenset = frozenset({"none"})
    # True routes the round builder through the async tick program
    # (repro.core.async_round) instead of the synchronous barrier round —
    # part of the declared capability surface (fedlint FL301)
    is_async: bool = False

    def init_state(self, params: PyTree) -> PyTree:
        raise NotImplementedError

    def apply(self, params: PyTree, handle, opt_state: PyTree, *, lr
              ) -> Tuple[PyTree, PyTree, jax.Array]:
        """Clip + optimizer + write.  Returns (new_params, new_opt_state,
        grad_norm_after_clip)."""
        raise NotImplementedError


_ENGINES = Registry("server engine", "repro.core.engines.register_engine")


def register_engine(name: str):
    """Decorator registering an engine factory ``factory(fed) -> engine``."""
    def deco(factory: Callable) -> Callable:
        _ENGINES.register(name, factory)
        return factory
    return deco


def get_engine(name: str) -> Callable:
    return _ENGINES.get(name)


def available_engines() -> tuple:
    return _ENGINES.names()


def resolve_engine(fed, *, engine: Optional[str] = None) -> ServerEngine:
    """An explicit registry name wins, then ``fed.engine``, then
    ``fed.fused_update`` selects fused_flat / legacy_tree."""
    if engine is None:
        engine = getattr(fed, "engine", None)
    if engine is None:
        engine = "fused_flat" if fed.fused_update else "legacy_tree"
    return get_engine(engine)(fed)


# ---------------------------------------------------------------------------
# built-in engines
# ---------------------------------------------------------------------------
@register_engine("legacy_tree")
class LegacyTreeEngine(ServerEngine):
    """Tree-map reference engine: clip-norm scale over the aggregate pytree
    then ``server_opt.apply`` — 5+ full-model traversals, no custom VJP, so
    only ``meta_mode="post"`` is available."""
    name = "legacy_tree"
    accepts = frozenset({"tree"})
    preferred = "tree"
    meta_capabilities = frozenset({"post"})

    def __init__(self, fed):
        self._opt = fed.server_opt
        self._clip = fed.clip_norm
        self._momentum = fed.server_momentum

    def init_state(self, params):
        return server_opt.init_state(self._opt, params)

    def apply(self, params, handle, opt_state, *, lr):
        assert isinstance(handle, TreeAggregate), type(handle)
        G = handle.tree
        if self._clip > 0:
            gn = tree_global_norm(G)
            scale = jnp.minimum(1.0,
                                self._clip / jnp.maximum(gn, 1e-9))
            G = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                        ).astype(g.dtype), G)
        new_params, new_opt = server_opt.apply(
            self._opt, opt_state, params, G, lr, momentum=self._momentum)
        return new_params, new_opt, tree_global_norm(G)


@register_engine("fused_flat")
class FusedFlatEngine(ServerEngine):
    """Flat-buffer Pallas engine (``repro.kernels.fused_update``): one HBM
    pass for clip + sgd/sgdm/adam/yogi + param write, hand-written custom
    VJP — declares the ``through_aggregation`` capability."""
    name = "fused_flat"
    accepts = frozenset({"flat", "tree"})
    preferred = "flat"
    meta_capabilities = frozenset({"post", "through_aggregation"})
    codec_capabilities = frozenset({"none", "lossy"})

    def __init__(self, fed):
        self._opt = fed.server_opt
        self._clip = fed.clip_norm
        self._momentum = fed.server_momentum

    def init_state(self, params):
        return init_flat_opt_state(self._opt, make_flat_spec(params))

    def apply(self, params, handle, opt_state, *, lr):
        if isinstance(handle, TreeAggregate):
            # pre-aggregated tree handles (custom executors; the built-in
            # ones all produce flat): flatten into the engine's layout
            spec = make_flat_spec(params)
            handle = FlatAggregate(flatten_tree(spec, handle.tree), spec)
        assert isinstance(handle, FlatAggregate), type(handle)
        gn = jnp.sqrt(flat_sq_norm(handle.groups))
        return flat_apply_groups(
            handle.spec, handle.groups, gn, params, opt_state,
            opt=self._opt, lr=lr, clip_norm=self._clip,
            momentum=self._momentum)


@register_engine("buffered_async")
class BufferedAsyncEngine(FusedFlatEngine):
    """Fault-tolerant buffered-asynchronous server engine (FedBuff-style).

    The per-flush apply — staleness-weighted mean already streamed into
    flat buffers, then clip -> optimizer -> parameter write — is inherited
    unchanged from :class:`FusedFlatEngine`; what changes is the ROUND
    SHAPE: ``is_async = True`` makes the round builder
    (``repro.core.round.make_federated_round``) route through the tick
    program in :mod:`repro.core.async_round`, which holds the bounded
    delta pool (``state["async"]``), per-delta staleness counters and the
    every-K-arrivals flush policy.  ``meta_mode='post'`` only: the flush is
    conditional (``lax.cond``), so there is no fixed aggregation graph for
    through-aggregation hypergradients to flow through."""
    name = "buffered_async"
    is_async = True
    accepts = frozenset({"flat"})
    preferred = "flat"
    meta_capabilities = frozenset({"post"})
    codec_capabilities = frozenset({"none", "lossy"})
