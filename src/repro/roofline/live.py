"""Live roofline attribution — the cost model wired onto a real run.

Until PR 10 the roofline machinery only ran inside the multi-pod dry-run
(``launch/dryrun.py``).  This module factors the compiled-program
analysis out of it so the trainer can run the same model on the round
program it is actually dispatching:

  * :func:`compiled_cost_summary` — everything one ``compiled`` object
    yields: XLA's ``cost_analysis`` FLOPs/bytes, the trip-count-aware
    HLO walk (``roofline.hlo_cost`` — XLA counts while bodies once, so
    scan-structured rounds undercount by ~trip-count without it), the
    collective schedule, and ``memory_analysis`` sizes;
  * :func:`round_roofline_event` — one ``roofline`` tracker-event
    payload per compiled round program: per-round FLOPs/bytes/collective
    bytes and the predicted compute/memory/collective seconds + rounds/s
    under the device's row of ``roofline.analysis.PEAKS`` (a TPU kind
    without a row raises).  The trainer appends the *measured* rounds/s
    from its dispatch + device-sync spans before emitting, so prediction
    and measurement sit in the same ``metrics.jsonl`` line.  Off the TPU
    (CI runs on CPU) the prediction is a what-if under the
    ``WHATIF_KIND`` row, and the event names both that kind and the
    device it ran on.

Event keys are pinned by ``repro.obs.schema.ROOFLINE_EVENT_KEYS``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax

from repro.roofline.analysis import (parse_collectives, peaks_for,
                                     roofline_terms)
from repro.roofline.hlo_cost import analyze as hlo_analyze

__all__ = ["compiled_cost_summary", "round_roofline_event"]

_MEM_ATTRS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes")


def compiled_cost_summary(compiled) -> Dict[str, Any]:
    """Cost-model summary of one ``jax.stages.Compiled`` program.

    ``bytes_est`` is the memory-term input: raw ``cost_analysis`` bytes
    are fusion-aware but count loop bodies once, so they are scaled by
    the FLOPs correction ratio (same loop structure), keeping
    fusion-level granularity — the convention dryrun.py established."""
    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    c = hlo_analyze(hlo)
    loop_ratio = c.flops / max(xla_flops, 1.0)
    memory: Dict[str, int] = {}
    try:
        mem = compiled.memory_analysis()
    except Exception:                        # noqa: BLE001 — backend-optional
        mem = None
    if mem is not None:
        for attr in _MEM_ATTRS:
            v = getattr(mem, attr, None)
            if v is not None:
                memory[attr] = int(v)
    return {
        "xla_flops": xla_flops,
        "xla_bytes_accessed": xla_bytes,
        "hlo_flops": c.flops,
        "hlo_bytes_written": c.bytes_written,
        "collective_bytes": c.collective_bytes,
        "per_collective": dict(c.per_collective),
        "collectives": parse_collectives(hlo),
        "loop_ratio": loop_ratio,
        "bytes_est": xla_bytes * max(loop_ratio, 1.0),
        "memory": memory,
        "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
    }


def round_roofline_event(jitted_fn, args, *, rounds_per_call: int = 1
                         ) -> Optional[Dict[str, Any]]:
    """AOT-compile ``jitted_fn(*args)`` (args may be ShapeDtypeStructs)
    and derive the per-round ``roofline`` event payload.  Returns None
    for callables without ``.lower`` — the sanitize path wraps the round
    in a plain checkify closure that cannot be AOT-lowered."""
    lower = getattr(jitted_fn, "lower", None)
    if lower is None:
        return None
    dev = jax.devices()[0]
    peaks_kind, peaks = peaks_for(dev.platform, dev.device_kind)
    t0 = time.perf_counter()
    compiled = lower(*args).compile()
    compile_s = time.perf_counter() - t0
    s = compiled_cost_summary(compiled)
    rl = roofline_terms(s["hlo_flops"], s["bytes_est"],
                        s["collective_bytes"], peaks=peaks)
    k = max(int(rounds_per_call), 1)
    t_round = max(rl.compute_s, rl.memory_s, rl.collective_s) / k
    return {
        "rounds_per_call": k,
        "flops_per_round": s["hlo_flops"] / k,
        "bytes_per_round": s["bytes_est"] / k,
        "collective_bytes_per_round": s["collective_bytes"] / k,
        "per_collective": s["per_collective"],
        "compute_s_per_round": rl.compute_s / k,
        "memory_s_per_round": rl.memory_s / k,
        "collective_s_per_round": rl.collective_s / k,
        "bottleneck": rl.bottleneck,
        "predicted_rounds_per_s": (1.0 / t_round) if t_round > 0 else 0.0,
        "loop_ratio": s["loop_ratio"],
        "xla_flops": s["xla_flops"],
        "memory": s["memory"],
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "peaks_kind": peaks_kind,
        "tpu_custom_calls": s["tpu_custom_calls"],
        "compile_s": compile_s,
        "analysis_s": round(time.perf_counter() - t0, 4),
    }
