"""Observability subsystem — the fifth plugin registry, plus analysis.

``repro.obs`` is where runs report what happened: pluggable
:class:`MetricsTracker` sinks for per-round metrics and events
(``noop`` / ``console`` / ``jsonl`` / ``csv`` / ``composite`` built in,
``tensorboard`` behind an optional-dependency gate,
:func:`register_tracker` for plugins), host-side phase :func:`span`
timing (each span also a ``repro.phase.<name>`` trace annotation), the
:class:`RoundProfiler` capturing a JAX trace for a round window, the
process-wide :func:`compile_counter` behind the trainer's ``compile``
events, and the documented round-metrics schema
(:func:`round_metric_keys`).

On top of that substrate sits the analysis layer (PR 10): trace
analytics (:mod:`repro.obs.trace_analysis` — per-op self time, busy/gap,
phase attribution, streamed as ``profile_summary`` events), the live
roofline hook (``roofline`` events via :mod:`repro.roofline.live`), and
the cross-run regression watch (:mod:`repro.obs.regress`, CLI
``python -m repro.obs.compare`` / ``python -m repro.obs report``).
Wired through ``FederatedTrainer(tracker=..., run_dir=...,
trace_summary=..., roofline=...)`` and ``train.py --tracker/--run-dir/
--profile/--trace-summary/--roofline``.
"""
from repro.obs.compiles import CompileCounter, compile_counter
from repro.obs.profiler import RoundProfiler
from repro.obs.regress import (Tolerances, compare_bench_files,
                               compare_run_dirs, summarize_run)
from repro.obs.schema import (PROFILE_SUMMARY_EVENT_KEYS,
                              ROOFLINE_EVENT_KEYS, VECTOR_METRICS,
                              round_metric_keys)
from repro.obs.trace_analysis import (emit_profile_summary, find_trace_file,
                                      summarize_trace)
from repro.obs.trackers import (CompositeTracker, ConsoleTracker,
                                CsvTracker, JsonlTracker, MetricsTracker,
                                NoopTracker, TensorBoardTracker,
                                available_trackers, get_tracker,
                                register_tracker, resolve_tracker, span)

__all__ = ["MetricsTracker", "NoopTracker", "ConsoleTracker",
           "JsonlTracker", "CsvTracker", "CompositeTracker",
           "TensorBoardTracker", "register_tracker", "get_tracker",
           "available_trackers", "resolve_tracker", "span",
           "RoundProfiler", "CompileCounter", "compile_counter",
           "round_metric_keys", "VECTOR_METRICS",
           "ROOFLINE_EVENT_KEYS", "PROFILE_SUMMARY_EVENT_KEYS",
           "summarize_trace", "find_trace_file", "emit_profile_summary",
           "summarize_run", "compare_run_dirs", "compare_bench_files",
           "Tolerances"]
