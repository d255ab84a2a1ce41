"""Reduction of a profiler capture (``*.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` only.  Device planes are
the ones named ``/device:TPU:<n>``; on each, the op events of its
``XLA Ops`` line are the device's work.  The window is the span of the
trainer's phase annotations on the host (``repro.phase.dispatch`` and
``repro.phase.device_sync``), so it covers the traced rounds and nothing
before or after them.

* busy: the union of the op intervals inside the window, per device, then
  the mean over devices;
* per-op time: each op's summed self time inside the window (its duration
  less that of the ops nested in it: a ``while`` or a ``call`` holds its
  body's ops on the same line), mean over devices.  On the TPU an op
  event's name is its whole HLO instruction; ops are keyed by the
  instruction's name (``while.1197``) and the whole text is kept in
  ``op_text``;
* idle gaps: the stretches inside the window where no op runs on device 0,
  each labelled by the host phase that covers most of it (``dispatch``,
  ``device_sync``, or ``host between rounds`` where neither does).

A Mosaic kernel's op in the trace carries the name of its HLO instruction
(``closed_call.119``, ``custom_call_target="tpu_custom_call"`` in its
text), not the kernel's.  ``kernels_from_hlo`` reads the compiled
program's text, where each ``tpu_custom_call`` instruction holds its
kernel's serialized module, and ``Reduced.name_kernels`` adds the names
found there (``_accumulate_kernel``, ``accumulate_pass``) to the op's text.
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
PHASE_PREFIX = "repro.phase."
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]                  # ns, host clock of the trace
    devices: int
    busy_ns: float                           # mean over devices
    op_ns: Dict[str, float]                  # mean over devices
    op_count: Dict[str, float]               # mean over devices
    gaps: List[Tuple[str, float]]            # (label, ns), longest first
    custom_calls: frozenset = frozenset()    # op names that are Mosaic calls
    op_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def time_ns(self, match) -> float:
        """Summed device time of the ops whose name, or whose text (the
        name with the event's stats, such as the JAX op path), satisfies
        ``match``."""
        return sum(t for n, t in self.op_ns.items()
                   if match(self.op_text.get(n, n)))

    def name_kernels(self, kernels: Dict[str, str]) -> None:
        """Mark the ops that ``kernels`` (from ``kernels_from_hlo``) names
        as Mosaic calls and add the kernel's names to their text."""
        for op, names in kernels.items():
            if op in self.op_ns:
                self.custom_calls = self.custom_calls | {op}
                self.op_text[op] = self.op_text.get(op, op) + " " + names

    def is_collective(self, name: str) -> bool:
        low = name.lower()
        return any(w in low for w in COLLECTIVE_WORDS)


_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_WORD = re.compile(rb"[A-Za-z_][A-Za-z0-9_]{3,}")


def kernels_from_hlo(hlo_text: str) -> Dict[str, str]:
    """{instruction name: the words of its kernel's module} for each
    ``tpu_custom_call`` in a compiled program's HLO text.  The words hold
    the kernel function's name and the Python function that called it."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, body = _INSTR.match(line), _BODY.search(line)
        if name is None or body is None:
            continue
        words = _WORD.findall(base64.b64decode(body.group(1)))
        out[name.group(1)] = " ".join(sorted({w.decode() for w in words}))
    return out


def find_xplane(root: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the binding cannot decode
        return {}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def reduce_profile(profile) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    phases: List[Tuple[int, int, str]] = []
    device_ops: List[List[Tuple[int, int, str, dict]]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((s, s + int(ev.duration_ns), ev.name,
                                _stats(ev)))
            device_ops.append(evs)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PHASE_PREFIX):
                        s = int(ev.start_ns)
                        phases.append((s, s + int(ev.duration_ns),
                                       ev.name[len(PHASE_PREFIX):]))
    if not device_ops:
        raise ValueError("the trace holds no device plane named "
                         f"{DEVICE_PREFIX}<n>")
    if not phases:
        raise ValueError("the trace holds no phase annotation "
                         f"{PHASE_PREFIX}*: it does not cover a round")
    lo = min(p[0] for p in phases)
    hi = max(p[1] for p in phases)

    n = len(device_ops)
    busy = 0.0
    op_ns: Dict[str, float] = {}
    op_count: Dict[str, float] = {}
    custom = set()
    text: Dict[str, str] = {}
    union0: List[Tuple[int, int]] = []
    for d, evs in enumerate(device_ops):
        inside = []
        for s, e, full, st in evs:
            iv = _clip((s, e), lo, hi)
            if iv is None:
                continue
            name = op_name(full)
            inside.append((iv[0], iv[1], name))
            op_count[name] = op_count.get(name, 0.0) + 1.0 / n
            if name not in text:
                text[name] = " ".join([full] + [str(v) for v in st.values()])
                if _is_custom_call(text[name]):
                    custom.add(name)
        for name, ns in _self_times(inside):
            op_ns[name] = op_ns.get(name, 0.0) + ns / n
        u = _union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in u) / n
        if d == 0:
            union0 = u

    gaps = []
    prev = lo
    for s, e in union0 + [(hi, hi)]:
        if s > prev:
            gaps.append((_label(prev, s, phases), float(s - prev)))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window=(lo, hi), devices=n, busy_ns=busy, op_ns=op_ns,
                   op_count=op_count, gaps=gaps,
                   custom_calls=frozenset(custom), op_text=text)


def op_name(full: str) -> str:
    """``while.1197`` for an event named ``%while.1197 = (...) while(...)``;
    a name that is not an HLO instruction stays as it is."""
    m = _INSTR.match(full)
    return m.group(1) if m else full


def _self_times(intervals: List[Tuple[int, int, str]]
                ) -> List[Tuple[str, int]]:
    """(name, self time) of each of one line's nested op intervals: its
    duration less its direct children's."""
    out: List[Tuple[str, int]] = []
    stack: List[list] = []                   # [end, name, self time]
    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            out.append((n, t))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((n, t) for _, n, t in stack)
    return out


def _is_custom_call(text: str) -> bool:
    """A Mosaic kernel's call (XLA's own custom calls, such as
    ``ConcatBitcast``, are not)."""
    low = text.lower()
    return "tpu_custom_call" in low or "mosaic" in low


def _label(s: int, e: int, phases) -> str:
    cover: Dict[str, int] = {}
    for ps, pe, name in phases:
        o = min(e, pe) - max(s, ps)
        if o > 0:
            cover[name] = cover.get(name, 0) + o
    if not cover:
        return "host between rounds"
    name, ns = max(cover.items(), key=lambda kv: kv[1])
    return name if ns * 2 >= (e - s) else "host between rounds"


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
