"""Roofline-term derivation from compiled dry-run artifacts.

The SPMD-partitioned HLO module is a *per-device* program, so
``compiled.cost_analysis()`` FLOPs/bytes and the collective operand sizes
parsed from ``compiled.as_text()`` are per-chip quantities:

    compute term    = flops_per_chip / peak_flops_chip
    memory term     = bytes_per_chip / hbm_bw_chip
    collective term = collective_bytes_per_chip / link_bw

(equivalent to the global formulation HLO_FLOPs / (chips * peak) since
global = per_chip * chips for an SPMD program).

The peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float             # dense bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    link_bw: float           # bytes/s per chip-to-chip link
    source: str


# One row per device kind; a TPU kind missing here is an error, never a
# default (see peaks_for).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect over 4 links (50 GB/s each)"),
}
# the kind whose peaks a run off the TPU borrows, as a what-if
WHATIF_KIND = "TPU v5 lite"


def peaks_for(platform: str, device_kind: str) -> Tuple[str, Peaks]:
    """(kind whose peaks apply, its peaks) for a device.  A TPU kind with
    no row in :data:`PEAKS` raises; any other platform gets the
    :data:`WHATIF_KIND` row, and the caller records that it did."""
    if platform != "tpu":
        return WHATIF_KIND, PEAKS[WHATIF_KIND]
    if device_kind not in PEAKS:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r}; add its "
            f"published row to repro.roofline.analysis.PEAKS (known: "
            f"{sorted(PEAKS)})")
    return device_kind, PEAKS[device_kind]

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Sum byte sizes of every dtype[dims] occurrence in a shape string
    (handles tuple results)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective op kind from (post-SPMD)
    optimized HLO text.  Result-shape bytes approximate the per-device
    payload that crosses links (all-gather result = full gathered tensor;
    all-reduce payload ~ 2x(n-1)/n of the tensor — we record raw result
    bytes and keep the convention consistent across iterations)."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        shape_str, op = m.groups()
        # normalize fused variants like all-gather-start / all-reduce-done
        base = None
        for k in COLLECTIVE_OPS:
            if op == k or op.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue  # the -start op already carries the shape
        out[base] += shape_bytes(shape_str)
        counts[base] += 1
    out = {k: v for k, v in out.items() if v}
    out["_counts"] = {k: v for k, v in counts.items() if v}
    return out


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    bottleneck: str
    model_flops: Optional[float] = None
    flops_ratio: Optional[float] = None   # MODEL_FLOPS / (HLO_FLOPs*chips)

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   coll_bytes_per_chip: float,
                   model_flops_global: Optional[float] = None,
                   chips: int = 256,
                   peaks: Peaks = PEAKS[WHATIF_KIND]) -> Roofline:
    c = flops_per_chip / peaks.flops
    m = bytes_per_chip / peaks.hbm_bw
    n = coll_bytes_per_chip / peaks.link_bw
    terms = {"compute": c, "memory": m, "collective": n}
    bottleneck = max(terms, key=terms.get)
    ratio = None
    if model_flops_global is not None and flops_per_chip > 0:
        ratio = model_flops_global / (flops_per_chip * chips)
    return Roofline(compute_s=c, memory_s=m, collective_s=n,
                    flops_per_chip=flops_per_chip,
                    bytes_per_chip=bytes_per_chip,
                    coll_bytes_per_chip=coll_bytes_per_chip,
                    bottleneck=bottleneck,
                    model_flops=model_flops_global, flops_ratio=ratio)


def model_flops_per_round(arch, shape, fed=None) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) per token-processing
    pass; D = tokens processed.  For the federated train step the tokens are
    processed (local_steps-1) keep-trace fwd+bwd passes + 1 evaluation
    fwd+bwd + (second-order correction ~ another fwd+bwd over the trajectory)
    — we count the *algorithmic* 6*N*D per optimization pass, with
    pass-count = local_steps for UGA and local_steps for FedAvg, + 1 meta
    pass; the dry-run compute term exposes the rest (remat, second order) as
    compiled/useful ratio."""
    n_active = arch.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        passes = (fed.local_steps if fed is not None else 2)
        meta = 1 if (fed is None or fed.meta) else 0
        # + meta batch tokens (64 sequences)
        meta_tokens = 64 * shape.seq_len * meta
        return 6.0 * n_active * (tokens * passes + meta_tokens)
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
