"""Flat-buffer view of parameter/gradient pytrees for the fused server
update engine.

The server hot path (aggregate -> clip -> optimizer apply) is element-wise
over every parameter, so the pytree structure only costs traversals there.
This module gives the round engine a *flat* view: leaves are grouped by
their original dtype, raveled, cast to fp32 and packed into one contiguous
``(rows, 128)`` fp32 buffer per dtype group with **static** element offsets
computed at trace time.  ``rows`` is padded to a multiple of
:data:`ROW_ALIGN` so the Pallas kernels in ``repro.kernels`` can tile the
buffer directly; the zero pad
is mathematically inert for every supported optimizer (0-gradient => 0
update) and is dropped again by :func:`unflatten_tree`.

Round-trip contract (property-tested): ``unflatten_tree(spec,
flatten_tree(spec, tree))`` preserves structure, shapes and dtypes, with
values equal up to the fp32 cast the legacy tree-map path performs anyway.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

LANES = 128           # TPU lane dimension; last axis of every flat buffer
# Row multiple of every group buffer.  The kernels tile rows by 256: the
# 1-bit codec packs 8 fp32 rows into one uint8 row, and Mosaic tiles 8-bit
# arrays by (32, 128), so a packed tile needs 32 * 8 input rows.  A smaller
# alignment leaves some models with a row count that only an 8-row (and a
# 1-row packed) tile divides, which the TPU compiler refuses.
ROW_ALIGN = 256


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    index: int                     # position in jax.tree flatten order
    shape: Tuple[int, ...]
    dtype: str                     # original dtype (cast-back target)
    offset: int                    # element offset inside the group buffer
    size: int


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    dtype: str                     # shared original dtype of the leaves
    leaves: Tuple[LeafSpec, ...]
    size: int                      # total elements (before padding)
    rows: int                      # padded row count: rows * LANES >= size
    # optional jax.sharding.PartitionSpec for the (rows, LANES) buffer —
    # attached by the two-tier sharded executor (via
    # repro.sharding.specs.flat_group_pspecs) so engines can keep the
    # aggregate buffers row-partitioned across the model axis instead of
    # replicating them after the cross-shard psum.  None = replicated.
    pspec: Any = None


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    treedef: Any
    groups: Tuple[GroupSpec, ...]
    # the jax.sharding.Mesh the group pspecs refer to (set with them by
    # with_pspecs); None = single-device buffers
    mesh: Any = None

    @property
    def num_leaves(self) -> int:
        return sum(len(g.leaves) for g in self.groups)


def make_flat_spec(tree: PyTree) -> FlatSpec:
    """Build the static layout for ``tree`` (works on arrays or
    ShapeDtypeStructs).  Groups are keyed by original leaf dtype in first-
    appearance order; offsets follow tree-flatten order within a group."""
    leaves, treedef = jax.tree.flatten(tree)
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        dt = jnp.dtype(leaf.dtype).name
        by_dtype.setdefault(dt, []).append((i, leaf))
    groups = []
    for dt, members in by_dtype.items():
        specs, off = [], 0
        for i, leaf in members:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            specs.append(LeafSpec(index=i, shape=tuple(leaf.shape), dtype=dt,
                                  offset=off, size=size))
            off += size
        rows = -(-off // LANES)                      # ceil
        rows = -(-rows // ROW_ALIGN) * ROW_ALIGN     # pad to the row tile
        groups.append(GroupSpec(dtype=dt, leaves=tuple(specs), size=off,
                                rows=rows))
    return FlatSpec(treedef=treedef, groups=tuple(groups))


def _pack(parts: Sequence[jax.Array], size: int, rows: int,
          lead: Tuple[int, ...] = ()) -> jax.Array:
    buf = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
    pad = rows * LANES - size
    if pad:
        buf = jnp.pad(buf, [(0, 0)] * len(lead) + [(0, pad)])
    return buf.reshape(lead + (rows, LANES))


def flatten_tree(spec: FlatSpec, tree: PyTree) -> List[jax.Array]:
    """tree -> one (rows, LANES) fp32 buffer per dtype group.

    Also the *per-client streaming* flatten of the scan cohort strategy:
    called once per client inside the cohort scan, so only ONE client's
    gradient is ever in flat form — the (cohort, rows, LANES) stack of
    :func:`flatten_stacked` never materializes."""
    leaves = jax.tree.leaves(tree)
    out = []
    for g in spec.groups:
        parts = [leaves[l.index].astype(jnp.float32).reshape(l.size)
                 for l in g.leaves]
        out.append(_pack(parts, g.size, g.rows))
    return out


def flatten_stacked(spec: FlatSpec, tree: PyTree) -> List[jax.Array]:
    """tree with a leading cohort axis on every leaf -> one
    (cohort, rows, LANES) fp32 buffer per dtype group."""
    leaves = jax.tree.leaves(tree)
    cohort = leaves[0].shape[0]
    out = []
    for g in spec.groups:
        parts = [leaves[l.index].astype(jnp.float32).reshape(cohort, l.size)
                 for l in g.leaves]
        out.append(_pack(parts, g.size, g.rows, lead=(cohort,)))
    return out


def unflatten_tree(spec: FlatSpec, bufs: Sequence[jax.Array],
                   dtype=None) -> PyTree:
    """Inverse of :func:`flatten_tree` — original structure/shapes/dtypes.

    ``dtype`` overrides the cast-back target for every leaf: the chunked
    executor's tree handle aggregates in fp32 flat buffers but must hand
    the engine a tree in ``grad_agg_dtype`` (one cast, not a lossy
    fp32 -> leaf-dtype -> agg-dtype double hop)."""
    leaves: List[Any] = [None] * spec.num_leaves
    for g, buf in zip(spec.groups, bufs):
        flat = buf.reshape(g.rows * LANES)
        for l in g.leaves:
            x = jax.lax.slice(flat, (l.offset,), (l.offset + l.size,))
            leaves[l.index] = x.reshape(l.shape).astype(
                jnp.dtype(l.dtype) if dtype is None else jnp.dtype(dtype))
    return jax.tree.unflatten(spec.treedef, leaves)


def unflatten_stacked(spec: FlatSpec, bufs: Sequence[jax.Array]) -> PyTree:
    """Inverse of :func:`flatten_stacked` — buffers with a leading cohort
    axis ``(cohort, rows, LANES)`` back to the original structure with the
    cohort axis on every leaf.  Completes the round-trip API for stacked
    buffers; nothing on the hot path calls it (the custom-VJP boundary
    sits at buffer level), but it is the tool for offline inspection of
    per-client cotangents in model coordinates."""
    leaves: List[Any] = [None] * spec.num_leaves
    for g, buf in zip(spec.groups, bufs):
        cohort = buf.shape[0]
        flat = buf.reshape(cohort, g.rows * LANES)
        for l in g.leaves:
            x = jax.lax.slice(flat, (0, l.offset), (cohort, l.offset + l.size))
            leaves[l.index] = x.reshape((cohort,) + l.shape).astype(
                jnp.dtype(l.dtype))
    return jax.tree.unflatten(spec.treedef, leaves)


def with_pspecs(spec: FlatSpec, pspecs: Sequence[Any], mesh) -> FlatSpec:
    """Attach one ``PartitionSpec`` per dtype group (see
    :func:`repro.sharding.specs.flat_group_pspecs`) and the mesh they
    name.  The spec stays a static trace-time constant — the pspec rides
    along exactly like ``rows`` so every consumer of the group buffers
    (engines, codecs, checkpointing) can recover the intended placement,
    and the server update can run its kernels per device over it."""
    assert len(pspecs) == len(spec.groups), (len(pspecs), len(spec.groups))
    return FlatSpec(treedef=spec.treedef, mesh=mesh, groups=tuple(
        dataclasses.replace(g, pspec=p)
        for g, p in zip(spec.groups, pspecs)))


def constrain_groups(spec: FlatSpec,
                     bufs: Sequence[jax.Array]) -> List[jax.Array]:
    """Apply each group's ``pspec`` as a ``with_sharding_constraint`` so
    GSPMD keeps the aggregate buffers partitioned (a no-op for groups
    without a pspec, or when the spec names no mesh)."""
    if spec.mesh is None:
        return list(bufs)
    from jax.sharding import NamedSharding
    out = []
    for g, b in zip(spec.groups, bufs):
        if g.pspec is not None:
            b = jax.lax.with_sharding_constraint(
                b, NamedSharding(spec.mesh, g.pspec))
        out.append(b)
    return out


def zeros_flat(spec: FlatSpec) -> List[jax.Array]:
    """Zero fp32 buffers in the spec's layout (optimizer state slots and
    the scan strategy's streaming accumulator carry)."""
    return [jnp.zeros((g.rows, LANES), jnp.float32) for g in spec.groups]


def flat_sq_norm(bufs: Sequence[jax.Array]) -> jax.Array:
    """||tree||^2 over flat group buffers.  The zero pad contributes
    nothing, so this equals the per-leaf sum of squares exactly."""
    ssq = jnp.float32(0.0)
    for b in bufs:
        ssq = ssq + jnp.sum(b * b)
    return ssq
