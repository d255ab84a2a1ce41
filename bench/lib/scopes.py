"""Device time of the ops under one of the round program's layer scopes.

The program traces each layer under a ``jax.named_scope`` (``repro.client``,
``repro.attention``, ``repro.flatten``, ``repro.codec``, ``repro.meta``,
``repro.server``).  The scope reaches each op's ``op_name`` in the
compiled program, but a device op in a v5e trace is named by its HLO
instruction alone and carries no ``op_name``.  The program's profiler
keeps the ``op_name`` of every instruction of the round programs its
latest capture ran (``repro.obs.profiler.captured_op_names``); an op's
text here is its trace text with that ``op_name`` added.

Under autodiff a scope appears wrapped (``transpose(jvp(repro.client))``)
and scopes nest (``repro.meta/.../repro.attention``), so a scope is matched
as a whole token anywhere in the text: not preceded by a letter, digit,
``_`` or ``.``, nor followed by one (a source path such as
``src/repro/core/flat.py`` or a file ``repro.flatten.py`` is not the
scope).

Only ops that are neither Mosaic calls nor collectives count, as in
``xla_ops_ms``: the kernels keep their own roofline metrics.  A program
without the scopes, or without the profiler's record, gives nothing.
"""
from __future__ import annotations

import re
from typing import Dict


def holds(scope: str, text: str) -> bool:
    return re.search(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])",
                     text) is not None


def op_names() -> Dict[str, str]:
    """{HLO instruction name: op_name} from the program's profiler, or
    nothing where the program keeps no such record."""
    try:
        from repro.obs.profiler import captured_op_names
    except ImportError:
        return {}
    return captured_op_names()


def scope_ms(ctx, scope: str):
    """Device self time per round, in ms, of the non-kernel ops whose text
    holds ``scope``; None where no op does."""
    t = ctx.trace
    names = op_names()
    ns = sum(v for n, v in t.op_ns.items()
             if n not in t.custom_calls and not t.is_collective(n)
             and holds(scope, t.op_text.get(n, n) + " " + names.get(n, "")))
    if ns <= 0:
        return None
    return ns / 1e6 / ctx.rounds
