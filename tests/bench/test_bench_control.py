"""The control (the plain reference computed in fp8, one step below the
bf16 the configurations state, put in the program's place) and, for
training, the planted faults, read through the harness's own run
(``calibrate.readings``: the driver's set-up, window, sample and
reference) at sizes a test run can hold.

On the chip the same readings, at the cells' own sizes, set the upper
end of each limit (``chipbench/calibrate.py``; PERF.md gives them).
"""
import jax
import numpy as np
import pytest

import helpers_bench
from chipbench import calibrate

SEEDS = (2 ** 31 + 101, 2 ** 31 + 102, 2 ** 31 + 103)


def _readings(workload, seed):
    ctx, bench_json = helpers_bench.smoke_context(workload, seed=seed)
    kind = ctx.traffic["driver"]
    return calibrate.readings(ctx, bench_json, jax.devices(),
                              calibrate.CONTROLS[kind])


def test_serve_control_reads_above_program():
    runs = [_readings("minitron-4b.decode", seed) for seed in SEEDS]
    assert all(r["correct"] for r in runs)
    prog = [r["program"]["served_logit_gap"] for r in runs]
    ctrl = [r["fp8"]["served_logit_gap"] for r in runs]
    assert min(ctrl) > 3 * max(prog)


@pytest.fixture(scope="module")
def train_readings():
    return [_readings("whisper-small.train", seed) for seed in SEEDS[:2]]


@pytest.mark.parametrize("name", ["fp8", "fault_half_batch", "fault_token",
                                  "fault_state_unchanged"])
def test_train_control_and_faults_read_above_program(train_readings, name):
    assert all(r["correct"] for r in train_readings)
    keys = sorted(train_readings[0]["program"])
    prog = np.array([[r["program"][k] for k in keys] for r in train_readings])
    other = np.array([[r[name][k] for k in keys] for r in train_readings])
    # some compared number separates the two on every seed, and the
    # committed limits, checked by the harness's own ``bench.Check``, fail
    # the control and each fault
    assert (other.min(0) > 3 * prog.max(0)).any()
    assert not any(r[name]["correct"] for r in train_readings)
