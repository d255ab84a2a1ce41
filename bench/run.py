#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it finds.

    python3 bench/run.py --workload smollm360m.silo --seed 7 --seconds 30 \
        --trace 0

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics
(``round_s``, ``setup_s``); with ``--trace 1`` a short window is traced and
the metrics are the cell's per-layer metrics.  Either way the run checks
its first rounds against the plain reference and prints each number
compared beside its limit: as the last lines of standard error, and under
``checks`` in the result, which is the last line of standard output.

This process never touches JAX.  It runs the program stage (set-up, check
rounds, window) and then the reference stage, each in a child process of
its own that holds the chip while it runs, and judges their readings.

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the program's sources are not beside the
benchmark.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


def child(cmd) -> int:
    """Run ``cmd`` and wait for it; it is stopped if this process is."""
    p = subprocess.Popen(cmd)
    try:
        return p.wait()
    finally:
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1, copy the profiler's .xplane.pb "
                         "to FILE")
    ap.add_argument("--stage", choices=("program", "reference"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--t-start", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from lib import harness
    if args.stage == "program":
        cell = harness.find_cell(args.workload, ROOT)
        try:
            stage = harness.program_stage(
                cell, args.seed, args.seconds, bool(args.trace),
                t_start=args.t_start, keep_trace=args.keep_trace)
        except harness.NoDevice as e:
            print(f"[bench] {e}", file=sys.stderr)
            return 3
        harness.save_stage(args.out, stage)
        return 0
    if args.stage == "reference":
        cell = harness.find_cell(args.workload, ROOT)
        stage = harness.load_stage(args.out)
        ref = harness.reference_stage(cell, args.seed, stage["rounds"])
        with open(args.out + ".ref.json", "w") as f:
            json.dump(ref, f)
        return 0

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    limits = harness.cell_traffic(args.workload, ROOT)["limits"]
    with tempfile.TemporaryDirectory(prefix="bench_run_") as d:
        out = os.path.join(d, "program")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--workload", args.workload, "--seed", str(args.seed),
               "--seconds", repr(args.seconds), "--trace", str(args.trace),
               "--out", out]
        keep = ["--keep-trace", os.path.abspath(args.keep_trace)] \
            if args.keep_trace else []
        rc = child(cmd + ["--stage", "program", "--t-start", repr(T_START)]
                   + keep)
        if rc:
            return rc
        rc = child(cmd + ["--stage", "reference"])
        if rc:
            return rc
        stage = harness.load_json(out + ".json")
        ref = harness.load_json(out + ".ref.json")
    result = harness.judge(limits, stage["result"], stage["prog"], ref)
    for name, c in result["checks"].items():
        lim = "unset (fails)" if c["limit"] is None else c["limit"]
        print(f"check {name}: {c['value']!r} limit {lim}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
