"""Round-window JAX profiler — the device-side half of ``--profile N``.

:class:`RoundProfiler` captures a ``jax.profiler`` trace for the round
window ``[start, start + rounds)`` into ``<run_dir>/profile/``.  The
trainer calls :meth:`maybe_start` before dispatching a chunk and
:meth:`maybe_stop` after the chunk's device sync; because a chunk spans
``rounds_per_call`` rounds, the window is widened to chunk boundaries
(you get at least the rounds you asked for, never fewer).  Start/stop are
emitted as ``profile_start`` / ``profile_stop`` tracker events so the
trace window is locatable in the metrics stream.

The capture is the standard XLA profile (``plugins/profile/<ts>/
*.xplane.pb`` + ``*.trace.json.gz``) viewable in TensorBoard's profile
plugin or ``chrome://tracing`` / Perfetto after gunzip.  Host-side phase
timings (sample/stack, dispatch, device-sync, checkpoint) come from the
tracker ``phase`` events instead — :func:`repro.obs.span` — so the two
views line up by round index.
"""
from __future__ import annotations

import os
import re
from typing import Callable, Dict, Optional

import jax

from repro.obs.trackers import MetricsTracker, NoopTracker

__all__ = ["RoundProfiler", "captured_op_names", "op_names_of"]

_INSTR = re.compile(r'\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?op_name="([^"]*)"')
# {HLO instruction name: op_name} of the programs the latest capture in
# this process ran (see RoundProfiler.maybe_start)
_CAPTURED: Dict[str, str] = {}


def op_names_of(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled program's HLO text.  The
    op_name is the JAX name stack the op was traced under, with the
    layer scopes in it (``.../repro.client/...``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def captured_op_names() -> Dict[str, str]:
    """{HLO instruction name: op_name} of the round programs that ran in
    the latest capture of this process.  A device op in the trace is
    named by its instruction and carries no op_name, so this is how its
    time is attributed to the layer scopes."""
    return dict(_CAPTURED)


class RoundProfiler:
    """One capture window per run.  Inert when ``rounds <= 0``."""

    def __init__(self, run_dir: Optional[str], *, start: int = 0,
                 rounds: int = 0,
                 tracker: Optional[MetricsTracker] = None):
        if rounds > 0 and run_dir is None:
            raise ValueError(
                "profiling writes a trace directory and needs a run "
                "directory; pass one (FederatedTrainer's run_dir argument "
                "/ train.py --run-dir)")
        self.start = int(start)
        self.rounds = int(rounds)
        self.trace_dir = (os.path.join(run_dir, "profile")
                          if run_dir is not None else None)
        self._tracker = tracker if tracker is not None else NoopTracker()
        self._active = False
        self._done = rounds <= 0
        self._programs: set = set()

    @property
    def active(self) -> bool:
        return self._active

    def maybe_start(self, round_idx: int, k: int = 1,
                    program: Optional[Callable] = None) -> bool:
        """Open the capture if the chunk ``[round_idx, round_idx + k)``
        overlaps the window — ``k`` is the chunk length, so a window
        starting mid-chunk still opens on the chunk that contains it
        (the widening the class docstring promises).  Returns True iff
        the trace is running.

        ``program`` (called with no arguments, it returns the chunk
        program's ``jax.stages.Lowered``) is read once per chunk length
        in the window, before the capture opens where it opens here: the
        op_name of each of its compiled instructions goes to
        :func:`captured_op_names`.  The program has compiled, or is about
        to, for the dispatch: compiling the same ``Lowered`` finds that
        executable."""
        starting = (not self._done and not self._active
                    and round_idx + k > self.start)
        if starting:
            _CAPTURED.clear()
        if (starting or self._active) and program is not None \
                and k not in self._programs:
            self._programs.add(k)
            _CAPTURED.update(op_names_of(program().compile().as_text()))
        if starting:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._active = True
            self._tracker.log_event("profile_start",
                                    {"round": round_idx,
                                     "trace_dir": self.trace_dir})
        return self._active

    def maybe_stop(self, next_round: int) -> None:
        """Close the capture once the window is fully covered
        (``next_round`` = first round of the NEXT chunk).  Call after the
        chunk's device sync so the captured ops actually executed."""
        if self._active and next_round >= self.start + self.rounds:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            self._tracker.log_event("profile_stop",
                                    {"round": next_round - 1,
                                     "trace_dir": self.trace_dir})

    def close(self) -> None:
        """Abort an open capture (run ended inside the window)."""
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            self._tracker.log_event("profile_stop",
                                    {"round": -1,
                                     "trace_dir": self.trace_dir})
