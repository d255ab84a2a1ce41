"""Process-wide compile counter — which round compiled, and for how long.

JAX reports each jaxpr trace, MLIR lowering and backend compile (a load
from the persistent compile cache included) to its ``jax.monitoring``
duration listeners.  :func:`compile_counter` registers one listener per
process, on first use, that counts backend compiles and sums the three
durations.  The trainer reads it around each chunk's dispatch and emits a
``compile`` tracker event ``{round, k, n, dur_s}`` when the dispatch moved
it, so a jsonl run shows which round traced or compiled a program.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

import jax

__all__ = ["CompileCounter", "compile_counter", "COMPILE_EVENTS"]

JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
MLIR_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (JAXPR_TRACE, MLIR_LOWERING, BACKEND_COMPILE)


@dataclasses.dataclass
class CompileCounter:
    """Backend compiles counted and compile-path seconds summed since the
    listener was registered."""
    compiles: int = 0
    seconds: float = 0.0

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            if event == BACKEND_COMPILE:
                self.compiles += 1

    def reading(self) -> Tuple[int, float]:
        return self.compiles, self.seconds


_COUNTER: Optional[CompileCounter] = None
_LOCK = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's counter; its listener is registered on the first
    call and never again."""
    global _COUNTER
    with _LOCK:
        if _COUNTER is None:
            _COUNTER = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                _COUNTER._on)
        return _COUNTER
