"""FederatedTrainer — the one driver loop every entry point shares.

Before this facade, ``launch/train.py``, ``benchmarks/common.py`` and the
examples each re-implemented the same loop: a :class:`~repro.core.round.
RoundFnCache` of jitted round programs, per-chunk host sampling,
``stack_round_inputs`` for ``rounds_per_call`` chunking, checkpoint/resume
of the full server state, and per-round history assembly — with separate
``k == 1`` / ``k > 1`` branches in each copy.  The trainer owns all of it
once:

    trainer = FederatedTrainer(model, fed, rounds_per_call=4, seed=0,
                               tracker="jsonl", run_dir="runs/exp0")
    trainer.restore(path)                      # optional resume
    history = trainer.run(data, rounds=100, cohort=8, batch=32)
    trainer.save(path)
    trainer.finish()

``run`` samples each chunk from a :class:`~repro.data.pipeline.
FederatedData`, dispatches one donated program per chunk (metrics sync to
host once per chunk), and returns one record per round
(``{"round": r, **metrics}``).  Hooks:

  * ``sample_meta(data, round_idx, meta_batch, sample)`` — override D_meta
    sampling (default: ``data.sample_meta`` when ``fed.meta``, else None so
    no meta batch is ever shipped);
  * ``on_records(recs, trainer)`` — called after every chunk with that
    chunk's records (eval scheduling, early stopping, custom logging).

Observability (``repro.obs``): every record is fed to the trainer's
:class:`~repro.obs.MetricsTracker` (``tracker=`` — a registry name,
instance, or comma list; default ``noop``), each chunk's host phases
(``sample_stack`` / ``dispatch`` / ``device_sync`` / ``checkpoint``) are
emitted as ``phase`` events, and ``profile=N`` captures a JAX trace for
rounds ``[profile_start, profile_start+N)`` into ``run_dir/profile``.
The analysis layer rides on top: ``trace_summary=True`` parses the
closed capture into a ``profile_summary`` event (top ops by self time,
busy/gap, per-phase attribution — ``repro.obs.trace_analysis``) and
``roofline=True`` emits a ``roofline`` event per compiled chunk program
(trip-count-aware predicted cost vs the measured dispatch + device-sync
throughput — ``repro.roofline.live``).
The legacy ``log_every``/``log_fn`` arguments still work: they compose a
``console`` tracker into the run's sink.

Managed checkpointing: ``checkpoint_every=N`` (with a ``run_dir``) saves
the full server state — and the run history, so a resumed run carries its
curve — every N rounds plus once at run end, through a background
:class:`~repro.checkpoint.CheckpointManager` with ``keep_last`` /
``keep_every`` retention; ``resume_latest()`` picks up the newest blob.

Plugin selection (``algorithm`` / ``executor`` / ``engine`` registry names)
passes through to :func:`repro.core.round.make_federated_round`.
"""
from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import CheckpointManager
from repro.checkpoint import restore as ckpt_restore
from repro.checkpoint import save as ckpt_save
from repro.configs.base import FedConfig
from repro.core.rngtags import round_key
from repro.core.round import (RoundFnCache, init_server_state,
                              stack_round_inputs)
from repro.data.pipeline import FederatedData
from repro.models.model import Model
from repro.sim.faults import client_failed_mask, fault_streams, resolve_faults

# NOTE: repro.obs imports live inside methods: obs's tracker registry is
# built on repro.core.registry, and importing it at module scope from here
# (repro.core's own __init__ imports the trainer) would be circular.

PyTree = Any

__all__ = ["FederatedTrainer"]


class FederatedTrainer:
    """Owns server state + jitted round programs + the chunked host loop."""

    def __init__(self, model: Model, fed: FedConfig, *,
                 rounds_per_call: int = 1, donate: bool = True,
                 seed: int = 0, key: Optional[jax.Array] = None,
                 engine: Optional[str] = None, sanitize: bool = False,
                 tracker=None, run_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 keep_last: int = 3, keep_every: int = 0,
                 profile: int = 0, profile_start: int = 0,
                 trace_summary: bool = False, trace_top_k: int = 15,
                 roofline: bool = False,
                 **round_kwargs):
        self.model = model
        self.fed = fed
        self.rounds_per_call = max(int(rounds_per_call), 1)
        if engine is not None:
            round_kwargs["engine"] = engine
        self._cache = RoundFnCache(model, fed, donate=donate,
                                   sanitize=sanitize, **round_kwargs)
        self.key = key if key is not None else jax.random.PRNGKey(seed)
        self.state = init_server_state(model, fed, self.key, engine=engine)
        self.history: List[Dict[str, float]] = []
        # retry-with-backoff bookkeeping (fed.retry_backoff > 0): failed
        # client id -> attempts so far, and due round -> ids to re-enqueue
        self._retry_attempts: Dict[int, int] = {}
        self._retry_due: Dict[int, List[int]] = {}
        # ---- observability ------------------------------------------------
        from repro.obs.profiler import RoundProfiler
        from repro.obs.trackers import resolve_tracker
        self.run_dir = run_dir
        self.tracker = resolve_tracker(tracker, run_dir=run_dir)
        self.profiler = RoundProfiler(run_dir, start=profile_start,
                                      rounds=profile, tracker=self.tracker)
        # ---- analysis layer (PR 10) ---------------------------------------
        # trace_summary: when the --profile window closes, parse the trace
        # into a profile_summary tracker event (obs/trace_analysis);
        # roofline: AOT-compile each distinct chunk program once, run the
        # trip-count-aware cost model, and emit a roofline event with
        # predicted vs measured rounds/s (roofline/live)
        if trace_summary and profile <= 0:
            raise ValueError(
                "trace_summary summarizes the profiler's capture and needs "
                "an open window; pass profile=N (train.py --profile N) "
                "alongside trace_summary")
        self._trace_summary = bool(trace_summary)
        self._trace_top_k = int(trace_top_k)
        self._roofline = bool(roofline)
        self._roofline_events: Dict[int, Optional[dict]] = {}
        self._ckpt_every = checkpoint_every
        self.manager: Optional[CheckpointManager] = None
        if checkpoint_every is not None:
            if run_dir is None:
                raise ValueError(
                    "managed checkpointing (checkpoint_every=N) writes "
                    "under the run directory; pass run_dir= as well, or "
                    "use save(path) for one-shot checkpoints")
            self.manager = CheckpointManager(
                os.path.join(run_dir, "checkpoints"),
                keep_last=keep_last, keep_every=keep_every)
        self._last_managed_step: Optional[int] = None

    # ---- state management -------------------------------------------------
    @property
    def round(self) -> int:
        """Host-side round counter (syncs the device scalar)."""
        return int(self.state["round"])

    def save(self, path: str, extra: Optional[dict] = None) -> None:
        """Full server state — params, optimizer state (incl. the fused
        engine's tuple-structured flat buffers), the controllable-weights
        slot when present, and the round counter — plus the run history,
        so :meth:`restore` continues mid-run without losing FedOpt
        momentum, meta-learned weights, or the metrics curve."""
        ckpt_save(path, self.state,
                  extra={**(extra or {}), "history": self.history})

    def restore(self, path: str) -> dict:
        """Resume from a checkpoint written by :meth:`save`; restores the
        run history alongside the server state and returns the
        checkpoint's ``extra`` metadata (minus the internal history
        slot)."""
        self.state, extra = ckpt_restore(path, self.state)
        self.history = list(extra.pop("history", self.history))
        return extra

    def resume_latest(self) -> Optional[int]:
        """Restore the newest managed checkpoint (``--resume auto``);
        returns its step, or None when the store is empty/absent."""
        if self.manager is None:
            return None
        hit = self.manager.restore_latest(self.state)
        if hit is None:
            return None
        self.state, extra, step = hit
        self.history = list(extra.pop("history", self.history))
        self._last_managed_step = step
        return step

    def finish(self) -> None:
        """Flush + close the tracker, profiler, and checkpoint manager
        (idempotent).  Drivers that own the run call this once at exit;
        callers that passed a shared tracker instance should close it
        themselves instead."""
        was_active = self.profiler.active
        self.profiler.close()
        if was_active:
            # the run ended inside the capture window; the aborted trace
            # is still on disk, so the summary still lands
            self._emit_trace_summary(self.tracker)
        if self.manager is not None:
            self.manager.close()
        self.tracker.finish()

    # ---- the driver loop --------------------------------------------------
    def run(self, data: FederatedData, *, rounds: int, cohort: int,
            batch: int, meta_batch: int = 32, share: Optional[bool] = None,
            sample_meta: Optional[Callable] = None,
            on_records: Optional[Callable] = None, log_every: int = 0,
            log_fn: Callable = print,
            tracker=None) -> List[Dict[str, float]]:
        """Train from the current round counter up to ``rounds`` total.
        Returns this call's per-round records (also appended to
        ``self.history`` and fed to the tracker).  ``tracker=`` overrides
        the trainer's sink for this call; ``log_every`` composes the
        classic console line in."""
        from repro.obs.trackers import (CompositeTracker, ConsoleTracker,
                                        resolve_tracker)
        share = self.fed.share if share is None else share
        # trackers THIS call constructs (registry-resolved overrides, the
        # log_every console) are finished before returning so their buffers
        # flush; self.tracker and caller-passed instances outlive the call
        owned: List[Any] = []
        trk = self.tracker if tracker is None \
            else resolve_tracker(tracker, run_dir=self.run_dir, owned=owned)
        if log_every:
            console = ConsoleTracker(every=log_every, log_fn=log_fn)
            owned.append(console)
            trk = CompositeTracker([trk, console])
        try:
            return self._run_tracked(
                data, trk, rounds=rounds, cohort=cohort, batch=batch,
                meta_batch=meta_batch, share=share, sample_meta=sample_meta,
                on_records=on_records)
        finally:
            for t in owned:
                t.finish()

    def _run_tracked(self, data: FederatedData, trk, *, rounds: int,
                     cohort: int, batch: int, meta_batch: int, share: bool,
                     sample_meta: Optional[Callable],
                     on_records: Optional[Callable]
                     ) -> List[Dict[str, float]]:
        from repro.obs.trackers import span
        run_history: List[Dict[str, float]] = []
        r = self.round
        trk.log_event("run_start", {
            "start_round": r, "rounds": rounds, "final_round": rounds - 1,
            "cohort": cohort, "batch": batch,
            "rounds_per_call": self.rounds_per_call})
        faults = resolve_faults(self.fed)
        # degradation policy: with faults on and retry_backoff > 0, clients
        # whose report was lost (crash / drop / past the round deadline) are
        # re-enqueued retry_backoff * 2^attempt rounds later, retry_max
        # consecutive failures per client
        retry_on = (self.fed.retry_backoff > 0 and faults.active
                    and (faults.crash > 0 or faults.drop > 0
                         or faults.deadline > 0))
        loop_s, rounds_measured = 0.0, 0
        while r < rounds:
            k = min(self.rounds_per_call, rounds - r)
            with span(trk, "sample_stack", round=r, k=k):
                due = [self._retry_due.pop(r + j, None) if retry_on
                       else None for j in range(k)]
                samples = [data.sample_round(r + j, cohort=cohort,
                                             batch=batch, share=share,
                                             include=due[j])
                           for j in range(k)]
                metas = [self._sample_meta(sample_meta, data, r + j,
                                           meta_batch, samples[j])
                         for j in range(k)]
                rngs = [round_key(self.key, r + j) for j in range(k)]
                staged = self._stage_inputs(samples, metas, rngs)
            if self._roofline and k not in self._roofline_events:
                # before dispatch: staged buffers may be donated by the
                # round program; the abstract shapes must be read first
                self._prepare_roofline(k, staged)
            self.profiler.maybe_start(r, k)
            with span(trk, "dispatch", round=r, k=k) as sp_d, \
                    self._phase_annotation("dispatch"):
                metrics = self._dispatch(k, staged)
            with span(trk, "device_sync", round=r, k=k) as sp_s, \
                    self._phase_annotation("device_sync"):
                # the dispatch span above measures enqueue time only (jax
                # dispatch is async); this one is the actual device work
                # left to drain — together they expose the overlap
                metrics = jax.block_until_ready(metrics)
            was_profiling = self.profiler.active
            self.profiler.maybe_stop(r + k)
            if was_profiling and not self.profiler.active:
                self._emit_trace_summary(trk)
            loop_s += sp_d["dur_s"] + sp_s["dur_s"]
            rounds_measured += k

            # THE record assembly — every driver shares this one.  Vector
            # metrics (e.g. the async runtime's staleness_hist) become
            # plain lists so records stay JSON-serializable.
            recs = [{name: (float(v[j]) if jnp.ndim(v[j]) == 0
                            else np.asarray(v[j], dtype=float).tolist())
                     for name, v in metrics.items()}
                    for j in range(k)]
            if retry_on:
                self._schedule_retries(samples, rngs, recs, due, r, k,
                                       faults)
            for j, rec in enumerate(recs):
                rec["round"] = r + j
                run_history.append(rec)
                self.history.append(rec)
                trk.log_metrics(r + j, rec)
            if on_records is not None:
                on_records(recs, self)
            r += k
            if self.manager is not None and self._ckpt_every \
                    and (r // self._ckpt_every) > ((r - k)
                                                   // self._ckpt_every):
                with span(trk, "checkpoint", round=r - 1):
                    self._save_managed(r)
        if self.manager is not None and self._last_managed_step != r:
            with span(trk, "checkpoint", round=r - 1):
                self._save_managed(r)
        if self._roofline:
            self._emit_roofline(trk, loop_s, rounds_measured)
        trk.log_event("run_finish", {"final_round": rounds - 1,
                                     "rounds_completed": len(run_history)})
        return run_history

    # ---- analysis-layer hooks (PR 10) -------------------------------------
    def _phase_annotation(self, name: str):
        """The trace twin of the ``span()`` event: while the profiler is
        capturing, wrap the phase in a ``repro.phase.<name>``
        TraceAnnotation so ``obs/trace_analysis`` can attribute device
        op self-time to phases.  A no-op context outside the window."""
        if self.profiler.active:
            return jax.profiler.TraceAnnotation(f"repro.phase.{name}")
        return contextlib.nullcontext()

    def _emit_trace_summary(self, trk) -> None:
        if not self._trace_summary:
            return
        from repro.obs.trace_analysis import emit_profile_summary
        emit_profile_summary(trk, self.profiler.trace_dir,
                             top_k=self._trace_top_k)

    def _prepare_roofline(self, k: int, staged) -> None:
        """AOT lower + compile the chunk program for ``k`` on abstract
        stand-ins of the real staged inputs and cache its cost-model
        event payload.  Runs once per distinct k, outside the profiler
        window and the phase spans (analysis time is recorded in the
        event, not smeared into the measured phases)."""
        from repro.roofline.live import round_roofline_event
        def abstract(x):
            # a mesh placement (the sharded executor's state) is part of
            # the program; a default-device one is left unspecified, as the
            # dispatch leaves it, so both compile the same program once
            sh = getattr(x, "sharding", None)
            return jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x),
                sharding=sh if isinstance(sh, NamedSharding) else None)

        absargs = jax.tree.map(abstract, (self.state, *staged))
        # sanitize-mode rounds are checkify closures without .lower —
        # round_roofline_event returns None and the event is skipped
        self._roofline_events[k] = round_roofline_event(
            self._cache(k), absargs, rounds_per_call=k)

    def _emit_roofline(self, trk, loop_s: float, rounds_measured: int
                       ) -> None:
        """One ``roofline`` event per compiled chunk program, with this
        run's measured dispatch + device-sync throughput attached so
        prediction and measurement share a metrics.jsonl line."""
        for k in sorted(self._roofline_events):
            ev = self._roofline_events[k]
            if ev is None:
                continue
            payload = dict(ev)
            payload["rounds_measured"] = rounds_measured
            payload["measured_s_per_round"] = \
                (loop_s / rounds_measured) if rounds_measured else 0.0
            payload["measured_rounds_per_s"] = \
                (rounds_measured / loop_s) if loop_s > 0 else 0.0
            trk.log_event("roofline", payload)

    def _save_managed(self, step: int) -> None:
        self.manager.save(step, self.state,
                          extra={"history": self.history})
        self._last_managed_step = step

    def _schedule_retries(self, samples, rngs, recs, due, r, k, faults):
        """Host-side mirror of the jitted round's fault draws: the fold in
        :func:`repro.sim.faults.fault_streams` is deterministic in the
        round rng, so recomputing the streams here agrees bit-for-bit with
        what the device masked out.  Failed clients are re-enqueued with
        exponential backoff, deferred past the current chunk (the chunk's
        cohorts were already sampled)."""
        cohort = len(samples[0]["clients"])
        for j in range(k):
            fs = fault_streams(rngs[j], cohort, faults)
            failed = np.asarray(client_failed_mask(fs, faults))
            clients = np.asarray(samples[j]["clients"])
            recs[j]["retried"] = float(len(set(due[j] or [])
                                          & set(clients.tolist())))
            for cid in clients[~failed]:
                self._retry_attempts.pop(int(cid), None)
            for cid in clients[failed]:
                cid = int(cid)
                a = self._retry_attempts.get(cid, 0)
                if a >= self.fed.retry_max:
                    continue
                self._retry_attempts[cid] = a + 1
                due_round = max(r + j + self.fed.retry_backoff * (2 ** a),
                                r + k)
                self._retry_due.setdefault(due_round, []).append(cid)

    def _sample_meta(self, sample_meta, data, round_idx, meta_batch, sample):
        if sample_meta is not None:
            return sample_meta(data, round_idx, meta_batch, sample)
        # No FedMeta step -> no D_meta sampling: the round_fn never touches
        # meta_batch when fed.meta is False, so ship None (an empty pytree
        # threads through stack_round_inputs and jit untouched)
        return data.sample_meta(round_idx, meta_batch) if self.fed.meta \
            else None

    def _stage_inputs(self, samples, metas, rngs):
        """Host-side staging (device transfer for k == 1, the
        ``stack_round_inputs`` chunk stack for k > 1) — split from
        dispatch so the ``sample_stack`` phase span covers it."""
        k = len(samples)
        if k == 1:
            return (jax.tree.map(jnp.asarray, samples[0]["cohort_batch"]),
                    jax.tree.map(jnp.asarray, metas[0]),
                    jnp.asarray(samples[0]["client_weights"]), rngs[0])
        return stack_round_inputs(
            [s["cohort_batch"] for s in samples], metas,
            [s["client_weights"] for s in samples], rngs)

    def _dispatch(self, k: int, staged) -> Dict[str, jax.Array]:
        """One donated program for the chunk; metrics come back with a
        leading K axis for k == 1 too, so record assembly exists once."""
        self.state, metrics = self._cache(k)(self.state, *staged)
        if k == 1:
            return {name: v[None] for name, v in metrics.items()}
        return metrics
