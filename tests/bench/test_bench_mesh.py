"""The four-chip cell's driver (``serve_closed_loop_mesh``) at the
program's smoke sizes on four virtual CPU devices, in a child process so
this session keeps its single device. The smoke fixture's mesh is
``(1, 2)``: its two KV heads split one per device, as the eight do over
four chips at full size. A sound run is correct; one whose decode step
returns its cache unchanged is not."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent

_CHILD = """
import argparse, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, here, fault = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [root, os.path.join(root, "src"), here]
import jax
from chipbench import bench, run
import helpers_bench

if fault == "cache_unchanged":
    from repro.models.lm import LM
    orig = LM.decode_step

    def decode_step(self, params, cache, token):
        logits, _ = orig(self, params, cache, token)
        return logits, cache
    LM.decode_step = decode_step

cell = "mistral-nemo-12b.decode4"
bench_json = bench.benchmark()
cfg = bench.load_json(helpers_bench.FIXTURES / "mistral-nemo-12b-smoke.json")
# the serve loop's small sizes, under the cell's own driver
traffic = dict(bench.traffic("decode4"),
               **helpers_bench.SMALL["serve_closed_loop"])
ns = argparse.Namespace(workload=cell, seed=2 ** 31 + 17, seconds=0.5,
                        trace=0)
ctx = run.make_context(ns, bench_json, helpers_bench.PEAKS, smoke=True,
                       overrides={"config": cfg, "traffic": traffic})
line, _ = run.execute(ctx, bench_json, jax.devices())
line["notes"] = ctx.notes
print("RESULT " + json.dumps(line, default=str))
"""


def _run(fault: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _CHILD, str(ROOT), str(HERE),
                          fault], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sharded_smoke_run_is_correct():
    line = _run("none")
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["notes"]["mesh"] == {"data": 1, "model": 2}
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tok_s", "setup_s"}


@pytest.mark.parametrize("fault", ["cache_unchanged"])
def test_sharded_serve_fault_is_caught(fault):
    line = _run(fault)
    assert line["correct"] is False
    check = line["checks"]["served_logit_gap"]
    assert check["value"] > check["limit"]
