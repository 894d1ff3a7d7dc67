"""A kernel path that fell back to jnp or degraded fails the run: a
silent switch would read as a change of speed."""
import pytest

import helpers_bench
from chipbench import bench


@pytest.fixture
def clean_telemetry():
    from repro.core.telemetry import reset_telemetry
    reset_telemetry()
    yield
    reset_telemetry()


def test_clean_path_passes(clean_telemetry):
    bench.check_guard("test")


@pytest.mark.parametrize("record", [
    lambda t: t.record_runtime_fallback("rmsnorm", "RuntimeError"),
    lambda t: t.record_ladder("swiglu", "cheap"),
])
def test_fallback_or_degradation_fails(clean_telemetry, record):
    from repro.core.telemetry import telemetry
    record(telemetry())
    with pytest.raises(bench.BenchError):
        bench.check_guard("test")


def test_run_with_a_fallback_reports_nothing(clean_telemetry, monkeypatch):
    from repro.kernels import ops
    orig = ops._tile

    def failing(name, *arrays, **scalars):
        if name == "swiglu":
            return ops._guarded(name, _raise, lambda: orig(
                name, *arrays, **scalars))
        return orig(name, *arrays, **scalars)
    monkeypatch.setattr(ops, "_tile", failing)
    with pytest.raises(bench.BenchError):
        helpers_bench.smoke_run("minitron-4b.decode")


def _raise():
    raise RuntimeError("planted kernel failure")
