#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, the
control and a planted fault on a few, all at the cell's own size.  The
benchmark's runs do not call this.

    python3 bench/control.py --workload smollm360m.silo \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3

For each of ``--seeds`` the program runs the cell's three check rounds and
is compared with the plain reference, as in a benchmark run.  For each of
``--control-seeds`` two stand-ins take the program's place and are
compared the same way: the control (the reference computed in bfloat16, the
precision below the configuration's float32) and the fault "half of the
round's clients left out, the mean taken over the rest" (the reference with
that fault planted).  A step that returns its state unchanged reads 1 on
``change_gap`` by construction and needs no run.  For each of
``--witness-seeds`` the reference computed as the program computes (fp32
weights, the default matmul precision) is compared the same way: a second
witness of how far rounding alone carries the numbers.  Each program and
witness line also gives every loss's relative gap (``loss_gaps``: the
rounds' client losses, then their meta losses).  Prints one JSON line per
reading, and a summary of the largest program reading and the smallest
control and fault readings per number.

As in a run, the program and each kind of reference run in child processes
of their own, one after the other: the program's round program keeps its
scratch memory reserved on the chip for as long as its process lives.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

# the stand-ins read on --control-seeds: (dtype, matmul precision, keep_half)
KINDS = {"reference": (None, "highest", False),
         "control_bf16": ("bfloat16", "default", False),
         "fault_half_batch": (None, "highest", True),
         "witness_default": (None, "default", False)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--witness-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--stage", choices=("program", *KINDS),
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from lib import harness
    if args.stage == "program":
        program_readings(harness.find_cell(args.workload, ROOT), args.seeds,
                         args.out)
        return 0
    if args.stage:
        reference_readings(harness.find_cell(args.workload, ROOT),
                           args.seeds, args.out, args.stage)
        return 0

    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | set(args.witness_seeds))
    ctl = sorted(set(args.control_seeds))
    wit = sorted(set(args.witness_seeds))
    with tempfile.TemporaryDirectory(prefix="bench_control_") as d:
        def stage(name, which):
            cmd = [sys.executable, os.path.abspath(__file__), "--workload",
                   args.workload, "--seeds", *map(str, which),
                   "--stage", name, "--out", d]
            rc = subprocess.run(cmd).returncode
            if rc:
                raise SystemExit(rc)

        stage("program", seeds)
        stage("reference", seeds)
        if ctl:
            stage("control_bf16", ctl)
            stage("fault_half_batch", ctl)
        if wit:
            stage("witness_default", wit)
        readings = []
        for seed in seeds:
            base = os.path.join(d, f"seed_{seed}")
            prog = harness.load_json(base + ".json")["prog"]
            ref = harness.load_json(base + ".reference.json")
            if seed in args.seeds:
                readings.append({"seed": seed, "who": "program",
                                 **harness.compare(prog, ref),
                                 "loss_gaps": loss_gaps(prog, ref)})
            whos = ((["control_bf16", "fault_half_batch"] if seed in ctl
                     else []) + (["witness_default"] if seed in wit else []))
            for who in whos:
                other = harness.load_json(f"{base}.{who}.json")
                readings.append({"seed": seed, "who": who,
                                 **harness.compare(other, ref),
                                 "loss_gaps": loss_gaps(other, ref)})
    for r in readings:
        print(json.dumps(r), flush=True)
    print(json.dumps(summary(readings)), flush=True)
    return 0


def program_readings(cell, seeds, out, *, require_tpu: bool = True,
                     log=None):
    """The program's check rounds on each seed, one trainer for all of
    them; each seed's readings and fed inputs go to ``out/seed_<n>``."""
    import jax
    import repro.core  # noqa: F401 - the package before its submodules
    from lib import harness
    from repro.core.round import init_server_state
    log = log or (lambda m: print(f"[control] {m}", file=sys.stderr,
                                  flush=True))
    harness.devices(cell.chips, require_tpu)
    harness.enable_cache()
    trainer = None
    for seed in seeds:
        t0 = time.monotonic()
        arrays, parts, meta = cell.builder.make_data(cell.cfg, cell.traffic,
                                                     seed)
        data = harness.make_recording_data(arrays, parts, meta, seed,
                                           harness.CHECK_ROUNDS)
        if trainer is None:
            trainer, rec, init, key, abstract = harness.build_program(
                cell, seed)
        key = jax.random.PRNGKey(seed % (1 << 32))
        trainer.key = key
        trainer.history = []
        state = init_server_state(trainer.model, trainer.fed, key)
        state["params"] = init(key)
        trainer.state = state
        prog = harness.check_rounds(cell, trainer, data, init, key, abstract)
        trainer.state = None
        harness.save_stage(os.path.join(out, f"seed_{seed}"), {
            "result": {}, "prog": prog,
            "rounds": [data.fed_rounds[r]
                       for r in range(harness.CHECK_ROUNDS)]})
        log(f"program, seed {seed}: {time.monotonic() - t0:.1f} s")


def reference_readings(cell, seeds, out, kind, *, require_tpu: bool = True):
    """One kind of reference (``KINDS``) over each seed's fed inputs."""
    import jax.numpy as jnp
    from lib import harness
    dtype, precision, keep_half = KINDS[kind]
    for seed in seeds:
        base = os.path.join(out, f"seed_{seed}")
        rounds = harness.load_stage(base)["rounds"]
        ref = harness.reference_stage(
            cell, seed, rounds, require_tpu=require_tpu,
            dtype=getattr(jnp, dtype) if dtype else None,
            precision=precision, keep_half=keep_half,
            log=lambda m, s=seed: print(f"[control] seed {s}: {m}",
                                        file=sys.stderr, flush=True))
        with open(f"{base}.{kind}.json", "w") as f:
            json.dump(ref, f)


def loss_gaps(prog, ref):
    return [abs(p - r) / abs(r) for p, r in
            zip(prog["client_loss"] + prog["meta_loss"],
                ref["client_loss"] + ref["meta_loss"])]


def summary(readings):
    s = {"who": "summary"}
    for k in ("loss_gap", "grad_gap", "change_gap"):
        prog = [r[k] for r in readings if r["who"] == "program"]
        s[k] = {"program_max": max(prog) if prog else None}
        for who in ("control_bf16", "fault_half_batch", "witness_default"):
            v = [r[k] for r in readings if r["who"] == who]
            s[k][who + "_min"] = min(v) if v else None
    return s


if __name__ == "__main__":
    sys.exit(main())
