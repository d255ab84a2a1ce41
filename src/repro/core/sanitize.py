"""Runtime sanitizer: checkify-instrumented federated rounds.

fedlint (``repro.analysis.fedlint``) proves *static* discipline — rng tags,
kernel contracts, capability declarations.  This module is its runtime
counterpart: ``--sanitize`` (``repro.launch.train``) turns on
``jax_debug_nans``, re-jits the round program under
:mod:`jax.experimental.checkify`, and plants :func:`check_flat_groups`
probes on the flat aggregate buffers — so a NaN/Inf payload (a garbled uplink, an exploding
local step, a bad codec decode) is caught the round it happens, with an
error that names the offending flat dtype group instead of surfacing rounds
later as a silently-poisoned parameter tree.

The sanitizer is strictly additive: with ``sanitize=False`` (the default)
no checkify transform runs and the jitted round program is bit-identical to
the unsanitized build.
"""
from __future__ import annotations

from jax import numpy as jnp
from jax.experimental import checkify

__all__ = ["sanitize_errors", "check_flat_groups", "checkify_round",
           "throw_if_error"]

# The error set --sanitize runs under: the explicit check_flat_groups
# probes below (user checks).  Two checkify error classes are deliberately
# NOT in the set:
#   * float_checks — checkify reports the FIRST failed check, so a
#     per-primitive NaN-genesis check would shadow the flat-group probe,
#     and it is the probe whose message names the aggregation buffer and
#     the recovery path; --sanitize turns on jax_debug_nans to localize
#     genesis instead;
#   * index_checks — checkify's rule for scatter (the transpose of gather
#     under autodiff, produced by every take_along_axis-style loss) raises
#     `IndexError: tuple index out of range` at trace time, still on jax
#     0.9.0 (checkify of jax.grad over a take_along_axis loss reproduces
#     it); re-add `checkify.index_checks` here once that rule is fixed.
sanitize_errors = checkify.user_checks


def check_flat_groups(spec, bufs, where: str) -> None:
    """Probe every flat dtype-group buffer for non-finite values.

    ``spec`` is the :class:`repro.core.flat.FlatSpec` describing ``bufs``
    (one fp32 ``(rows, 128)`` buffer per dtype group, or with leading batch
    axes).  Must run inside a function transformed by
    :func:`checkify_round`; outside it the checks are silently discarded by
    design (checkify's functionalization), which is what keeps the default
    path untransformed.  The error message names the flat group and the
    probe site so the failure is actionable without a device debugger."""
    for i, (g, buf) in enumerate(zip(spec.groups, bufs)):
        bad = jnp.size(buf) - jnp.sum(jnp.isfinite(buf).astype(jnp.int32))
        checkify.check(
            bad == 0,
            f"sanitize: {{n}} non-finite element(s) in flat group {i} "
            f"(dtype {g.dtype}, {g.rows}x128 fp32 buffer) at {where}; "
            "map elements back to parameter leaves with "
            "repro.core.flat.unflatten_tree",
            n=bad)


def checkify_round(fn):
    """Transform a round_fn for jit under the sanitizer's error set.  The
    result returns ``(err, (state, metrics))``; raise host-side with
    :func:`throw_if_error` after the call."""
    return checkify.checkify(fn, errors=sanitize_errors)


# host-side raise of a checkified error value (no-op when no check fired)
throw_if_error = checkify.check_error
