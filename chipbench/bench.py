"""Harness core: finds a cell's files by name and holds what a run shares.

Everything that belongs to one configuration, traffic mix, driver kind,
per-layer metric or kernel lives in a file of its own, found here by the
name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   sizes as run, source, departures
* ``traffic/<traffic>.json``  a driver kind and its parameters
* ``drivers/<kind>.py``       ``run(ctx) -> DriverResult``
* ``metrics/<name>.py``       ``read(view) -> float | None``; a metric
  ``a.b`` falls back to ``metrics/a.py`` when ``metrics/a.b.py`` is absent
* ``kernels/<kernel>.py``     ``cost(operands, results) -> (flops, bytes)``
* ``limits/<workload>.json``  the correctness limits of one cell
* ``peaks.json``              the chip's peaks, keyed by ``device_kind``
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".cache"      # ignored by chipbench/.gitignore


class BenchError(RuntimeError):
    """A run that must end with a non-zero exit and no result line."""


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(workload_name: str) -> Dict[str, float]:
    return load_json(BENCH_DIR / "limits" / f"{workload_name}.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device_kind {device_kind!r} is not in "
                         f"chipbench/peaks.json ({sorted(table)})")
    return table[device_kind]


_MODULES: Dict[pathlib.Path, Any] = {}


def _load(path: pathlib.Path):
    if path not in _MODULES:
        mod_name = "chipbench_" + "_".join(path.relative_to(BENCH_DIR)
                                           .with_suffix("").parts)
        spec = importlib.util.spec_from_file_location(
            mod_name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def driver(kind: str):
    return _load(BENCH_DIR / "drivers" / f"{kind}.py")


def metric_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    return _load(path)


def kernel_cost(kernel: str):
    """The cost module of a kernel named in the trace, or None."""
    path = BENCH_DIR / "kernels" / f"{kernel}.py"
    return _load(path) if path.exists() else None


def metrics_for(bench: Dict[str, Any], section: str,
                cell: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


# -- what a cell's loop gets and gives ------------------------------------------------
@dataclasses.dataclass
class Check:
    """One compared number, beside its limit; passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class DriverResult:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    # calibration only: the checks with each control in the program's place
    controls: Dict[str, List[Check]] = dataclasses.field(default_factory=dict)


def correct(checks: List[Check]) -> bool:
    return all(c.ok for c in checks)


@dataclasses.dataclass
class RunContext:
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    peaks: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # process start on perf_counter
    smoke: bool = False               # tests: the program's smoke sizes
    sat_cache_dir: Optional[str] = None
    trace_dir: Optional[str] = None
    setup_s: Optional[float] = None
    sat_build_s: Optional[float] = None
    # calibration only: controls to read beside the program (a driver's
    # docstring names those it knows); the benchmark's runs read none
    controls: List[str] = dataclasses.field(default_factory=list)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def mark_setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start


def say(msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def memory_peak() -> Optional[int]:
    """Peak bytes in use on the first chip, as the backend reports it."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- guard --------------------------------------------------------------------------
CLEAN_LEVELS = ("hit", "warm", "cold")


def guard_state() -> Dict[str, Any]:
    from repro.core.telemetry import telemetry
    return telemetry().snapshot()


def check_guard(phase: str):
    """Nothing on the timed path may have fallen back to jnp or degraded:
    a silent switch would read as a change of speed."""
    g = guard_state()["guard"]
    low = {k: v for k, v in g["ladder_levels"].items()
           if k not in CLEAN_LEVELS}
    if g["runtime_fallbacks"] or g["degradations"] or low:
        raise BenchError(
            f"{phase}: the kernel path fell back or degraded: "
            f"runtime_fallbacks={g['runtime_fallbacks']} "
            f"degradations={g['degradations']} "
            f"ladder_levels={g['ladder_levels']}")


def sat_build_s() -> float:
    s = guard_state()
    return s["cold_wall_s"] + s["warm_wall_s"] + s["hit_wall_s"]


# -- compile counting -----------------------------------------------------------------
class CompileCounter:
    """Counts XLA backend compiles and persistent compile-cache hits (an
    executable fetched instead of compiled) from its creation on."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# -- the traced window ------------------------------------------------------------------
class Tracer:
    """Starts the profiler ``lead_s`` into the window and stops it
    ``span_s`` later, from hooks the cell's loop calls between operations.
    Host spans (``span``) are written into the same trace; a span open
    when tracing starts is lost, so the profiler starts only where the
    loop says no span is open (``may_start``)."""

    def __init__(self, enabled: bool, trace_dir: Optional[str],
                 lead_s: float, span_s: float):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.lead_s = lead_s
        self.span_s = span_s
        self.t_window = None
        self.state = "idle"            # idle -> on -> done
        self._window_span = None

    def start_window(self, t0: float):
        self.t_window = t0

    def poll(self, may_start: bool = False):
        if not self.enabled or self.t_window is None:
            return
        import jax
        now = time.perf_counter()
        if (self.state == "idle" and may_start
                and now >= self.t_window + self.lead_s):
            # host spans, no Python function tracing: it slows the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation(
                "chipbench.traced")
            self._window_span.__enter__()
            self._t_on = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= self._t_on + self.span_s:
            self.stop()

    def stop(self):
        if self.state != "on":
            return
        import jax
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def span(self, name: str, **stats):
        if self.state != "on":
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **stats)


def checks_text(checks: List[Check]) -> List[str]:
    return [f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}" for c in checks]


@dataclasses.dataclass
class View:
    """What a per-layer metric reader may read: the traced window, the
    harness's own spans in it, the sizes and the peaks."""
    trace: Any                        # chipbench.trace.Trace
    model: Dict[str, Any]             # the configuration's ``model`` block
    peaks: Dict[str, Any]
    sat_build_s: Optional[float]


def read_per_layer(bench: Dict[str, Any], cell: str,
                   view: View) -> Dict[str, float]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in metrics_for(bench, "per_layer", cell):
        value = metric_reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = value
    return out
