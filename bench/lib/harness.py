"""One run of one benchmark cell.

Set-up builds one trainer (``repro.core.FederatedTrainer``, the program's
normal path) with weights and client data made from the seed, and drives
it through its first ``CHECK_ROUNDS`` rounds through the same ``run`` call
and data feed the window uses.  Those rounds' losses, the first aggregate
the server optimizer received, and the parameters' change after them are
kept.  The window then continues the same trainer for about ``--seconds``
seconds, and the peak device memory is read.  That is the program stage.
The reference stage, in a process of its own on the chip, replays the
check rounds with the plain reference from the same weights and inputs;
the gaps between the two decide ``correct`` (``judge``).

The files a cell needs are found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic, ``bench/workloads/<traffic>.json`` holds
the traffic, ``bench/configs/<config>.json`` the configuration with its
builder ``<config>.py`` and plain reference ``<config>_ref.py`` beside it,
and ``bench/metrics/<metric>.py`` each per-layer metric's reader.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from functools import partial
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CHECK_ROUNDS = 3
TRACE_SECONDS = 5.0       # the traced window's length, at least 3 rounds
CHECKS = ("loss_gap", "grad_gap", "change_gap")


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding the cell's files by name
# ---------------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: dict
    cfg: dict
    builder: object
    ref: object
    per_layer: List[dict]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in bm["workloads"] if w["name"] == name]
    if not wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{[w['name'] for w in bm['workloads']]})")
    wl = wl[0]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return load_cell(name, wl["config"], wl["traffic"], int(wl["chips"]),
                     [m for m in bm["per_layer"] if mine(m)], root)


def cell_traffic(name: str, root: str = ROOT) -> dict:
    """The traffic file of a cell named in ``BENCHMARK.json``, read without
    loading the cell's modules."""
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = [w for w in bm["workloads"] if w["name"] == name]
    if not wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return load_json(os.path.join(root, "bench", "workloads",
                                  wl[0]["traffic"] + ".json"))


def load_cell(name: str, config: str, traffic: str, chips: int = 1,
              per_layer: Optional[List[dict]] = None,
              root: str = ROOT) -> Cell:
    """A cell from its files alone: ``bench/workloads/<traffic>.json`` and
    ``bench/configs/<config>.json`` with ``<config>.py`` and
    ``<config>_ref.py`` beside it."""
    bench = os.path.join(root, "bench")
    base = os.path.join(bench, "configs", config)
    return Cell(
        name=name, chips=chips,
        traffic=load_json(os.path.join(bench, "workloads",
                                       traffic + ".json")),
        cfg=load_json(base + ".json"),
        builder=load_module(base + ".py", "bench_cfg_" + config),
        ref=load_module(base + "_ref.py", "bench_ref_" + config),
        per_layer=list(per_layer or []))


def metric_reader(name: str, root: str = ROOT) -> Callable:
    mod = load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform} "
                       f"({devs[0].device_kind}); the benchmark times only "
                       "on a TPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)} "
                       f"({devs[0].device_kind})")
    return devs[:chips]


def enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


class CompileCounter:
    """Counts backend compiles (and loads from the persistent cache)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def fed_config(traffic: dict):
    from repro.configs.base import FedConfig
    return FedConfig(
        algorithm="uga", meta=bool(traffic["meta"]), meta_mode="post",
        cohort=int(traffic["cohort"]),
        local_steps=int(traffic["local_steps"]),
        local_epochs=int(traffic.get("local_epochs", 1)),
        client_lr=float(traffic["client_lr"]),
        server_lr=float(traffic["server_lr"]),
        meta_lr=float(traffic["meta_lr"]),
        lr_decay=float(traffic["lr_decay"]),
        server_opt=traffic["server_opt"], codec=traffic["codec"],
        error_feedback=bool(traffic.get("error_feedback", False)),
        cohort_chunk=int(traffic["cohort_chunk"]), fused_update=True)


def make_recording_data(arrays, parts, meta, seed: int, keep: int):
    """The program's ``FederatedData`` over the benchmark's arrays; it
    keeps a copy of what it feeds the first ``keep`` rounds."""
    from repro.data.pipeline import FederatedData

    class Recording(FederatedData):
        def __post_init__(self):
            super().__post_init__()
            self.fed_rounds: Dict[int, dict] = {}

        def sample_round(self, round_idx, **kw):
            s = super().sample_round(round_idx, **kw)
            if round_idx < keep:
                self.fed_rounds.setdefault(round_idx, {}).update(
                    cohort_batch={k: v.copy()
                                  for k, v in s["cohort_batch"].items()},
                    client_weights=s["client_weights"].copy())
            return s

        def sample_meta(self, round_idx, batch):
            m = super().sample_meta(round_idx, batch)
            if round_idx < keep:
                self.fed_rounds.setdefault(round_idx, {})["meta_batch"] = {
                    k: v.copy() for k, v in m.items()}
            return m

    return Recording(arrays=arrays, client_indices=parts, meta_indices=meta,
                     seed=seed)


class Recorder:
    """The trainer's tracker: keeps its phase spans and round records."""

    def __init__(self):
        from repro.obs.trackers import MetricsTracker

        class _T(MetricsTracker):
            name = "bench"

            def __init__(s):
                s.phases: List[dict] = []

            def log_metrics(s, round_idx, metrics):
                pass

            def log_event(s, name, data=None):
                if name == "phase":
                    s.phases.append(dict(data or {}))

        self.tracker = _T()

    @property
    def phases(self) -> List[dict]:
        return self.tracker.phases

    def round_seconds(self, lo: int, hi: int) -> float:
        """Host time of rounds [lo, hi): sample, dispatch and device sync."""
        return sum(p["dur_s"] for p in self.phases
                   if lo <= p["round"] < hi
                   and p["phase"] in ("sample_stack", "dispatch",
                                      "device_sync"))


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def leaf_gap(prog: List[float], ref: List[float],
             skip: Optional[List[bool]] = None) -> float:
    """Worst leaf's |prog - ref|, over the larger of the reference's norm of
    that leaf and the median leaf's."""
    med = statistics.median(ref)
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for i, (p, r) in enumerate(zip(prog, ref))
            if not (skip and skip[i])]
    return max(gaps) if gaps else 0.0


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers ``correct`` holds to their limits.  ``loss_gap`` is
    the first round's client loss, from the same weights on both sides:
    the later rounds' losses carry their predecessors' rounding through
    Adam's normalised steps, and on some seeds part from the reference by
    a sixth in the program and in the reference computed at the program's
    own precision alike, so they cannot be held to a limit."""
    r0, p0 = ref["client_loss"][0], prog["client_loss"][0]
    loss_gap = abs(p0 - r0) / max(abs(r0), 1e-30)
    if any(not math.isfinite(x)
           for x in prog["client_loss"] + prog["meta_loss"]):
        loss_gap = math.inf
    g_ref = ref["first"]
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone; they are left out of the change
    med = statistics.median(g_ref)
    skip = [g < 1e-3 * med for g in g_ref]
    return {"loss_gap": loss_gap,
            "grad_gap": leaf_gap(prog["first"], g_ref),
            "change_gap": leaf_gap(prog["change"], ref["change"], skip)}


def reference_readings(cell: Cell, p0, rounds: List[dict], *,
                       dtype=None, precision: str = "highest",
                       keep_half: bool = False) -> dict:
    import jax.numpy as jnp
    from lib import fedref
    key = (cell.name, precision)
    if key not in _REFERENCES:
        _REFERENCES[key] = fedref.Reference(
            partial(cell.ref.loss, cell.cfg), dict(cell.traffic), precision)
    out = _REFERENCES[key].run(p0, rounds, dtype=dtype or jnp.float32,
                               keep_half=keep_half)
    first = (_norms(out["G0"]) if cell.traffic["server_opt"] == "adam"
             else _norms(out["params1"], p0))
    return {"client_loss": out["client_loss"], "meta_loss": out["meta_loss"],
            "first": first, "change": _norms(out["params"], p0)}


_REFERENCES: Dict[tuple, object] = {}


def _norms(tree, minus=None) -> List[float]:
    """Per-leaf L2 norms of ``tree`` (or of ``tree - minus``), in fp32."""
    import jax
    import jax.numpy as jnp
    global _NORMS
    if _NORMS is None:
        def norms(tree, minus):
            diff = [x.astype(jnp.float32) for x in jax.tree.leaves(tree)]
            if minus is not None:
                diff = [d - y.astype(jnp.float32)
                        for d, y in zip(diff, jax.tree.leaves(minus))]
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(d)))
                              for d in diff])
        _NORMS = jax.jit(norms)
    return [float(x) for x in _NORMS(tree, minus)]


_NORMS = None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Plant:
    """A fault the benchmark's own tests plant under the timed path."""
    wrap_round: Callable                     # (round_fn) -> round_fn


def build_program(cell: Cell, seed: int, plant: Optional[Plant] = None):
    import jax
    from repro.core import FederatedTrainer
    model = cell.builder.build_model(cell.cfg)
    fed = fed_config(cell.traffic)
    key = jax.random.PRNGKey(seed % (1 << 32))
    abstract = jax.eval_shape(model.init, key)
    init = jax.jit(partial(cell.builder.init_params, cell.cfg, abstract))
    rec = Recorder()
    trainer = FederatedTrainer(model, fed,
                               rounds_per_call=int(
                                   cell.traffic["rounds_per_call"]),
                               key=key, tracker=rec.tracker)
    if plant is not None:
        inner = trainer._cache
        trainer._cache = lambda k: plant.wrap_round(inner(k))
    trainer.state["params"] = init(key)
    return trainer, rec, init, key, abstract


def capture_programs(trainer) -> dict:
    """Lower each round program the trainer runs, from the state and the
    staged inputs of its second round, as they are staged and before they
    are dispatched: {k: Lowered}.  The trainer's own call of the program
    is left as it is, so the program is compiled under the same cache key
    as in an untraced run; and since the first round has compiled and
    loaded it by then, compiling the ``Lowered`` finds that executable in
    memory: no second compile and no second copy on the device."""
    programs: dict = {}
    rounds = [0]
    stage = trainer._stage_inputs

    def stage_and_lower(samples, metas, rngs):
        staged = stage(samples, metas, rngs)
        rounds[0] += 1
        k = len(samples)
        if rounds[0] == 2 and k not in programs:
            programs[k] = trainer._cache(k).lower(trainer.state, *staged)
        return staged

    trainer._stage_inputs = stage_and_lower
    return programs


def program_text(programs: dict, log=None) -> str:
    """The compiled HLO text of the captured round programs.  Empty, and
    the kernels' metrics left out, where it cannot be had."""
    t0, texts = time.monotonic(), []
    try:
        for lowered in programs.values():
            texts.append(lowered.compile().as_text())
    except Exception as e:  # noqa: BLE001 - the run goes on without names
        (log or _log)(f"round program's HLO not read: {e}")
        return ""
    (log or _log)(f"round programs' HLO read in "
                  f"{time.monotonic() - t0:.2f} s")
    return "\n".join(texts)


def check_rounds(cell: Cell, trainer, data, init, key, abstract) -> dict:
    """Drive the trainer through the first CHECK_ROUNDS rounds and read
    the losses, the first aggregate (Adam) or first change (SGD), and the
    change after the last check round."""
    import jax
    from repro.core.flat import make_flat_spec, unflatten_tree
    t = cell.traffic
    run = partial(trainer.run, data, cohort=int(t["cohort"]),
                  batch=int(t["client_batch"]),
                  meta_batch=int(t["meta_batch"]))
    hist = run(rounds=1)
    p0 = init(key)
    if t["server_opt"] == "adam":
        spec = make_flat_spec(abstract)
        m = unflatten_tree(spec, list(trainer.state["opt"]["m"]))
        first = [x / (1 - 0.9) for x in _norms(m)]
        del m
    else:
        first = _norms(trainer.state["params"], p0)
    hist = hist + run(rounds=CHECK_ROUNDS)
    change = _norms(trainer.state["params"], p0)
    del p0
    jax.block_until_ready(trainer.state)
    return {"client_loss": [float(h["client_loss"]) for h in hist],
            "meta_loss": [float(h["meta_loss"]) for h in hist
                          if "meta_loss" in h],
            "first": first, "change": change}


def program_stage(cell: Cell, seed: int, seconds: float, trace: bool, *,
                  t_start: float, require_tpu: bool = True,
                  plant: Optional[Plant] = None, log=None,
                  keep_trace: Optional[str] = None) -> dict:
    """Set-up, the check rounds and the window: the program's side of a
    run.  ``t_start`` is the run's start on ``time.monotonic``'s clock.
    Returns {"result": the result line without its verdict, "prog": the
    check rounds' readings, "rounds": what was fed to them}."""
    log = log or _log
    import repro.core  # noqa: F401 - the package before its submodules
    devs = devices(cell.chips, require_tpu)
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_cache()}")
    counter = CompileCounter()

    arrays, parts, meta = cell.builder.make_data(cell.cfg, cell.traffic, seed)
    data = make_recording_data(arrays, parts, meta, seed, CHECK_ROUNDS)
    trainer, rec, init, key, abstract = build_program(cell, seed, plant)
    programs = capture_programs(trainer) if trace else {}
    prog = check_rounds(cell, trainer, data, init, key, abstract)
    t = cell.traffic
    warm = rec.round_seconds(1, CHECK_ROUNDS) / (CHECK_ROUNDS - 1)
    n = max(2, math.ceil(seconds / max(warm, 1e-6)))
    tdir = None
    if trace:
        from repro.obs.profiler import RoundProfiler
        n = max(3, min(n, math.ceil(TRACE_SECONDS / max(warm, 1e-6))))
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        trainer.profiler = RoundProfiler(tdir, start=CHECK_ROUNDS, rounds=n,
                                         tracker=rec.tracker)
    log(f"set-up done: {counter.n} compiles ({counter.seconds:.1f} s); "
        f"warm round {warm:.4f} s; window of {n} rounds")
    compiles0 = counter.n
    t0 = time.monotonic()
    hist = trainer.run(data, rounds=CHECK_ROUNDS + n,
                       cohort=int(t["cohort"]), batch=int(t["client_batch"]),
                       meta_batch=int(t["meta_batch"]))
    t1 = time.monotonic()
    in_window = counter.n - compiles0
    log(f"window: {n} rounds in {t1 - t0:.4f} s; compiles inside the "
        f"window: {in_window}")
    failed = sum(1 for h in hist if not math.isfinite(h["client_loss"]))
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devs)
    phases = [p for p in rec.phases if p["round"] >= CHECK_ROUNDS]
    rounds_fed = [data.fed_rounds[r] for r in range(CHECK_ROUNDS)]
    trainer.finish()
    trainer.state = None
    del trainer, data, arrays

    result = {"correct": False, "attempted": n, "failed": failed,
              "metrics": {}, "device": {
                  "platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}}
    if trace:
        hlo = program_text(programs, log)
        del programs
        tm = traced_metrics(cell, tdir, n, phases, abstract, devs, log, hlo)
        if keep_trace:
            from lib import trace as trace_lib
            shutil.copy(trace_lib.find_xplane(tdir), keep_trace)
        shutil.rmtree(tdir, ignore_errors=True)
        result["metrics"] = tm["metrics"]
        result["device"].update(tm["device"])
        result["breakdown"] = tm["breakdown"]
        result["kernels"] = tm["kernels"]
    else:
        result["metrics"] = {
            "round_s": {"value": (t1 - t0) / n, "unit": "s/round"},
            "setup_s": {"value": t0 - t_start, "unit": "s"}}
    return {"result": result, "prog": prog, "rounds": rounds_fed}


def reference_stage(cell: Cell, seed: int, rounds: List[dict], *,
                    require_tpu: bool = True, dtype=None,
                    precision: str = "highest", keep_half: bool = False,
                    log=None) -> dict:
    """The plain reference over the check rounds' inputs, from the same
    weights the program started from (the benchmark's ``init_params`` from
    the seed).  Run in a process of its own on the chip: a process that has
    loaded the round program keeps its scratch memory reserved, and the
    reference needs it."""
    log = log or _log
    import jax
    import repro.core  # noqa: F401 - the package before its submodules
    devices(cell.chips, require_tpu)
    enable_cache()
    model = cell.builder.build_model(cell.cfg)
    key = jax.random.PRNGKey(seed % (1 << 32))
    abstract = jax.eval_shape(model.init, key)
    init = jax.jit(partial(cell.builder.init_params, cell.cfg, abstract))
    p0 = jax.device_get(init(key))
    t_ref = time.monotonic()
    ref = reference_readings(cell, p0, rounds, dtype=dtype,
                             precision=precision, keep_half=keep_half)
    log(f"reference ({precision}, {dtype or 'float32'}"
        f"{', half the cohort' if keep_half else ''}): "
        f"{time.monotonic() - t_ref:.1f} s")
    return ref


def judge(limits: dict, result: dict, prog: dict, ref: dict,
          log=None) -> dict:
    """Set ``correct`` and ``checks`` (last) on the result line, against
    the cell's ``limits``."""
    log = log or _log
    numbers = compare(prog, ref)
    checks = {k: {"value": numbers[k], "limit": limits.get(k)}
              for k in CHECKS}
    # a number with no limit set has nothing to hold it, so it fails
    result["correct"] = bool(result["failed"] == 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))
    log(f"program losses {prog['client_loss']} meta {prog['meta_loss']}; "
        f"reference {ref['client_loss']} meta {ref['meta_loss']}")
    result.pop("checks", None)
    result["checks"] = checks
    return result


def save_stage(path: str, stage: dict) -> None:
    """Write a program stage's output: JSON beside an ``.npz`` of the
    inputs fed to the check rounds."""
    import numpy as np
    arrays = {}
    for r, rin in enumerate(stage["rounds"]):
        for part in ("cohort_batch", "meta_batch"):
            for k, v in rin[part].items():
                arrays[f"{r}/{part}/{k}"] = v
        arrays[f"{r}/client_weights"] = rin["client_weights"]
    np.savez(path + ".npz", **arrays)
    with open(path + ".json", "w") as f:
        json.dump({"result": stage["result"], "prog": stage["prog"],
                   "rounds": len(stage["rounds"])}, f)


def load_stage(path: str) -> dict:
    import numpy as np
    stage = load_json(path + ".json")
    rounds: List[dict] = [{"cohort_batch": {}, "meta_batch": {}}
                          for _ in range(stage["rounds"])]
    with np.load(path + ".npz") as z:
        for name in z.files:
            r, rest = name.split("/", 1)
            if rest == "client_weights":
                rounds[int(r)]["client_weights"] = z[name]
            else:
                part, k = rest.split("/", 1)
                rounds[int(r)][part][k] = z[name]
    stage["rounds"] = rounds
    return stage


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             plant: Optional[Plant] = None, log=None,
             keep_trace: Optional[str] = None) -> dict:
    """A whole run in this process, through the stages' own hand-over
    files: for the CPU, where no program holds the chip's memory.  On the
    chip ``bench/run.py`` runs each stage in a process of its own."""
    stage = program_stage(cell, seed, seconds, trace, t_start=t_start,
                          require_tpu=require_tpu, plant=plant, log=log,
                          keep_trace=keep_trace)
    with tempfile.TemporaryDirectory(prefix="bench_stage_") as d:
        save_stage(os.path.join(d, "program"), stage)
        stage = load_stage(os.path.join(d, "program"))
    ref = reference_stage(cell, seed, stage["rounds"],
                          require_tpu=require_tpu, log=log)
    return judge(cell.traffic["limits"], stage["result"], stage["prog"], ref,
                 log)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def traced_metrics(cell: Cell, tdir: str, n: int, phases: List[dict],
                   abstract, devs, log, hlo: str = "") -> dict:
    import types
    from lib import trace, work
    from lib.peaks import peaks_for
    path = trace.find_xplane(tdir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    red = trace.reduce_file(path)
    red.name_kernels(trace.kernels_from_hlo(hlo))
    ctx = types.SimpleNamespace(
        trace=red, rounds=n, chips=cell.chips, phases=phases, peaks=peaks_for(devs[0].device_kind),
        round_flops=cell.builder.round_flops(cell.cfg, cell.traffic),
        work=work.round_work(abstract, cell.traffic),
        traffic=cell.traffic, cfg=cell.cfg, matched={})
    metrics = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    kernels = {" ".join(k): v for k, v in ctx.matched.items()}
    log(f"kernels matched by name: {kernels}")
    top = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:10]
    log("top device ops (s in window): " + ", ".join(
        f"{k}={v / 1e9:.6f}" for k, v in top))
    return {"metrics": metrics, "kernels": kernels,
            "breakdown": {
                "device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": [[k, v / 1e9] for k, v in red.gaps[:10]]},
            "device": {"busy_s": red.busy_ns / 1e9,
                       "window_s": red.window_ns / 1e9}}
