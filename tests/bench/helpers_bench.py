"""Runs a benchmark cell on the CPU at the program's smoke sizes: the
harness's look for a chip is skipped, the rest of a run is driven."""
import argparse
import pathlib

import jax

from chipbench import bench, run

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SMALL = {"serve_closed_loop": dict(clients=2, prompt_len=16, max_new=6,
                                   check_requests=2, trace_lead_s=0.2,
                                   trace_span_s=0.3),
         "train_steps": dict(batch=4, seq=32, trace_lead_s=0.2,
                             trace_span_s=0.3)}


def smoke_context(workload: str, seed: int = 2 ** 31 + 17,
                  seconds: float = 0.5, trace: int = 0):
    bench_json = bench.benchmark()
    cell = bench.workload(bench_json, workload)
    cfg = bench.load_json(FIXTURES / f"{cell['config']}-smoke.json")
    traffic = bench.traffic(cell["traffic"])
    traffic = dict(traffic, **SMALL[traffic["driver"]])
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=trace)
    ctx = run.make_context(ns, bench_json, PEAKS, smoke=True,
                           overrides={"config": cfg, "traffic": traffic})
    return ctx, bench_json


def smoke_run(workload: str, **kw):
    ctx, bench_json = smoke_context(workload, **kw)
    line, _ = run.execute(ctx, bench_json, jax.devices())
    return line
