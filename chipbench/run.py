"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and limits are found by name
(``chipbench/bench.py``). With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a few steady seconds inside the window. The
last line of standard output is one JSON object; the compared numbers
and their limits close standard error and the result line.

A machine without a TPU, a chip not in ``chipbench/peaks.json``, fewer
chips than the cell asks for, or a kernel path that fell back to ``jnp``
or degraded ends the run with a non-zero exit and no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import bench  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n_needed: int):
    """The accelerator, or BenchError: never a CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise bench.BenchError(
            f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < n_needed:
        raise bench.BenchError(
            f"the cell asks for {n_needed} chips, JAX sees {len(devs)}")
    return devs, bench.peaks(devs[0].device_kind)


def setup_caches():
    """JAX's compile cache and the saturation cache, at fixed paths in
    the checkout, so that only a cell's first run there compiles."""
    import jax
    jax_dir = bench.CACHE_DIR / "jax"
    jax_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(jax_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(bench.CACHE_DIR / "saturation")


def make_context(args, bench_json, peaks=None, smoke=False,
                 overrides=None) -> bench.RunContext:
    cell = bench.workload(bench_json, args.workload)
    overrides = overrides or {}
    cfg = overrides.get("config") or bench.config(cell["config"])
    traffic = overrides.get("traffic") or bench.traffic(cell["traffic"])
    limits = overrides.get("limits") or bench.limits(cell["name"])
    return bench.RunContext(
        workload=cell, config=cfg, traffic=traffic, limits=limits,
        peaks=peaks, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, smoke=smoke)


def execute(ctx: bench.RunContext, bench_json, devices):
    """Drive the cell and build its result line."""
    cell = ctx.workload["name"]
    if ctx.trace:
        ctx.trace_dir = str(bench.CACHE_DIR / "trace" / cell)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    drv = bench.driver(ctx.traffic["driver"])
    res = drv.run(ctx)
    for k, v in sorted(ctx.notes.items()):
        bench.say(f"{k}: {v}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    metrics = {}
    units = {m["name"]: m["unit"] for m in
             bench_json["end_to_end"] + bench_json["per_layer"]}
    breakdown = None
    if ctx.trace:
        from chipbench import trace as T
        tr = T.load_xplane(ctx.trace_dir)
        view = bench.View(tr, ctx.config["model"], ctx.peaks,
                          ctx.sat_build_s)
        values = bench.read_per_layer(bench_json, cell, view)
        device["busy_s"] = T.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = T.breakdown(tr)
    else:
        values = dict(res.end_to_end, setup_s=ctx.setup_s)
        values = {m["name"]: values[m["name"]] for m in
                  bench.metrics_for(bench_json, "end_to_end", cell)}
    for name, v in values.items():
        metrics[name] = {"value": v, "unit": units[name]}

    line = {"correct": bench.correct(res.checks) and res.failed == 0,
            "attempted": res.attempted, "failed": res.failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    return line, res


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench_json = bench.benchmark()
        cell = bench.workload(bench_json, args.workload)
        devices, peaks = find_chips(cell["chips"])
        sat_dir = setup_caches()
        src = os.path.join(ROOT, "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        ctx = make_context(args, bench_json, peaks)
        ctx.sat_cache_dir = sat_dir
        line, res = execute(ctx, bench_json, devices)
    except bench.BenchError as e:
        bench.say(f"error: {e}")
        return 2
    for text in bench.checks_text(res.checks):
        bench.say(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
