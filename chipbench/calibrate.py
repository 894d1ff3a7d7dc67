"""Readings that the correctness limits are set from (not run by the
benchmark's own runs).

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --seconds 6 [--first-seed N] > readings.jsonl

Each seed is one run of the cell through the harness's own path
(``run.execute``: the driver's set-up, a short window of ``--seconds`` at
the cell's own load, its sample and its reference), on the chip at the
cell's own size. For the first ``--control-seeds`` seeds the same run also
puts each control of the driver in the program's place (the reference in
fp8; for a training cell also the planted faults) and checks it against
the committed limits with the same ``bench.Check``.

One line per seed: the program's compared numbers (the lower readings),
each control's (the upper readings) and whether each came out correct.
The exit code is 1 if a sound run came out not correct or a control came
out correct: then a limit does not separate the two.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import bench, run  # noqa: E402

CONTROLS = {"serve_closed_loop": ["fp8"],
            "train_steps": ["fp8", "fault_half_batch", "fault_token",
                            "fault_state_unchanged"]}


def readings(ctx, bench_json, devices, controls):
    """One run of the cell with ``controls`` read beside the program."""
    ctx.controls = list(controls)
    line, res = run.execute(ctx, bench_json, devices)
    out = {"seed": ctx.seed, "correct": line["correct"],
           "program": {c.name: c.value for c in res.checks},
           "limits": {c.name: c.limit for c in res.checks},
           "notes": ctx.notes}
    for name, checks in res.controls.items():
        out[name] = {c.name: c.value for c in checks}
        out[name]["correct"] = bench.correct(checks)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    bench_json = bench.benchmark()
    cell = bench.workload(bench_json, args.workload)
    devices, peaks = run.find_chips(cell["chips"])
    sat_dir = run.setup_caches()
    kind = bench.traffic(cell["traffic"])["driver"]
    sound = True
    for k in range(args.seeds):
        ns = argparse.Namespace(workload=args.workload,
                                seed=args.first_seed + k,
                                seconds=args.seconds, trace=0)
        ctx = run.make_context(ns, bench_json, peaks)
        ctx.sat_cache_dir = sat_dir
        controls = CONTROLS[kind] if k < args.control_seeds else []
        got = readings(ctx, bench_json, devices, controls)
        sound &= got["correct"] and not any(
            got[c]["correct"] for c in controls)
        print(json.dumps(got, default=str), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
