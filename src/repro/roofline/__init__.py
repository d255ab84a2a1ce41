"""Roofline cost modelling — trip-count-aware HLO analysis plus the
per-device peaks table.

``analysis`` holds the peaks (keyed by ``device_kind``) and term derivation,
``hlo_cost`` the trip-count-aware HLO walker, ``live`` the wiring onto a
compiled round program (the trainer's ``roofline=True`` / ``train.py
--roofline`` hook), and ``report`` the ``python -m repro.roofline.report
<run_dir>`` CLI over an emitted ``metrics.jsonl``.
"""
from repro.roofline.analysis import (COLLECTIVE_OPS, PEAKS, WHATIF_KIND,
                                     Peaks, Roofline,
                                     model_flops_per_round,
                                     parse_collectives, peaks_for,
                                     roofline_terms, shape_bytes)
from repro.roofline.hlo_cost import Cost, analyze
from repro.roofline.live import compiled_cost_summary, round_roofline_event

__all__ = ["PEAKS", "WHATIF_KIND", "Peaks", "peaks_for", "COLLECTIVE_OPS",
           "Roofline",
           "roofline_terms", "parse_collectives", "shape_bytes",
           "model_flops_per_round", "Cost", "analyze",
           "compiled_cost_summary", "round_roofline_event"]
