"""Sequence-parallel flash decode (the long_500k B=1 path, optimized form).

The baseline decode path lets GSPMD handle a sequence-sharded KV cache
(softmax over the sharded axis becomes compiler-chosen collectives).  This
module is the explicit shard_map version: every device computes the
online-softmax partials (m, l, o) over its local cache shard and the
partials are combined with pmax/psum — one small collective per layer
instead of whatever GSPMD infers.

Used by the perf experiments; exact vs ``decode_attention`` (tested).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.attention import combine_partials, flash_decode_partial


def sharded_flash_decode(q, k_cache, v_cache, index, *, mesh: Mesh,
                         axis: str = "data"):
    """q: (B, H, Dk); caches: (B, S, Hkv, D*) with S sharded over ``axis``;
    index: scalar int32 (global).  Returns (B, H, Dv)."""
    n = mesh.shape[axis]
    S = k_cache.shape[1]
    assert S % n == 0, (S, n)
    loc = S // n

    def local(q, k, v, index):
        shard = jax.lax.axis_index(axis)
        m, l, o = flash_decode_partial(q, k, v, index, shard * loc)
        return combine_partials(m, l, o, axis)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, index).astype(v_cache.dtype)
