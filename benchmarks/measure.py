"""Timing harness: measured per-instance kernel latencies → calibration.

Closes the predicted-vs-measured loop the cost model was missing: every
kernel the saturator prices analytically is *run* and timed, and
``--fit`` feeds the timings to :mod:`repro.analysis.calibrate` to fit
the roofline latency model's free parameters (per-op-class VPU pass
coefficients, HBM efficiency, per-bound overlap slack, launch overhead),
persisting them as versioned device profiles under
``experiments/device_profiles/``.

Three measurement lanes, each flagged with an explicit ``measured_kind``
(profiles are fitted per kind — the units are not comparable):

* **model tile programs** (``repro.kernels.tile_programs``) run through
  their *generated Pallas kernels* on one (8, 128) tile — compiled on
  TPU/GPU (``pallas_compiled``), interpret mode on CPU
  (``pallas_interpret``: the kernel body executes op-by-op in Python, so
  absolute times are dispatch-dominated; the fitted coefficients and the
  rank ordering are what carry signal).
* **compiled lane** (PR 8, first-class): the same tile kernels timed
  under one ``jax.jit`` per schedule (``pallas_compiled``). Each row
  records ``compile_path`` — ``"native"`` when the Pallas primitives
  lower to the accelerator, ``"xla_interpret"`` on CPU where the
  interpret-mode kernel is traced and compiled by XLA (dispatch
  overhead gone, op costs remain; the honest label keeps the two from
  being conflated).
* **NPB/SPEC suite kernels** (``benchmarks.kernel_suite`` — indexed
  loads/loops, not Pallas-tilable) run their saturated JAX thread body
  sequentially over the grid under one jit (``jax_<backend>_grid``);
  measured per-instance time is wall / n_threads. Their features carry
  the PR-8 trip-count profile (``cg_like``'s ``nnz`` loop).

Warmup iterations are discarded, the median of ``--reps`` repeats is
kept, and inputs are seeded deterministically; the process re-execs with
``PYTHONHASHSEED=0`` (shared ``hashseed`` machinery) so the *extraction
choice* being timed is the exact one the committed tables predict.

Usage:
    python -m benchmarks.measure              # measure, write JSON
    python benchmarks/measure.py --fit        # measure + fit + save profiles
    python benchmarks/measure.py --smoke      # 2-kernel CI smoke check
    python benchmarks/measure.py --kernels rmsnorm,swiglu --reps 3
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

if __package__ in (None, ""):        # direct script invocation
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from benchmarks.bootstrap import OUT_ROOT, ROOT, die_with_import_help
from benchmarks.hashseed import reexec_with_fixed_hashseed

reexec_with_fixed_hashseed()

try:
    import numpy as np
    import jax
except ImportError as e:
    die_with_import_help(e)

MEASUREMENTS_SCHEMA_VERSION = 3   # 1 = PR-4; 2 = +schedule (PR 5);
                                  # 3 = +emitter/compile_path (PR 8)
PROFILE_DIR = ROOT / "experiments" / "device_profiles"
DEFAULT_OUT = OUT_ROOT / "measurements.json"

# Tile programs measured for calibration; a couple of e-graphs
# (e.g. adamw) exceed the straight-line Pallas checks' comfort zone on
# row-block autosizing, so the set is explicit and ordered.
TILE_KERNELS = ("rmsnorm", "rmsnorm_gated", "layernorm", "swiglu", "gelu",
                "rotary", "residual_scale", "softmax", "adamw",
                "sgd_momentum", "ssd_gate", "moe_router", "l2_clip")
SMOKE_KERNELS = ("swiglu", "rmsnorm")
# every tile kernel is timed under each statement order (PR 5): same
# extracted term, different emission schedule
SCHEDULES = ("source", "bulk", "cost")
# the cost-driven schedule is priced with the committed PR-4 interpret
# profile when present, so the measured order is the calibrated
# objective's pick, not the analytic guess
SCHED_PROFILE = "cpu_pallas_interpret"


def _backend() -> str:
    return jax.default_backend()


# ---------------------------------------------------------------------------
# Tile programs through their generated Pallas kernels
# ---------------------------------------------------------------------------
def tile_inputs_for(prog, seed: int = 0):
    """Deterministic (arrays, scalars) for a tile program from its
    declared shapes ((8, 128) when undeclared); values in [0.1, 1.0) so
    log/rsqrt/recip domains stay safe."""
    from repro.analysis import TILE_SHAPE
    rng = np.random.default_rng(seed)
    arrays = []
    for spec in prog.arrays.values():
        if spec.role not in ("in", "inout"):
            continue
        shape = getattr(spec, "shape", None) or TILE_SHAPE
        shape = tuple(TILE_SHAPE[i] if d is None else int(d)
                      for i, d in enumerate(shape))
        arrays.append(rng.uniform(0.1, 1.0, size=shape).astype(np.float32))
    scalars = {s: 0.5 for s in prog.scalars}
    return arrays, scalars


def _sched_profile_name():
    """The committed calibrated profile driving the cost-schedule
    search, if present (fresh checkouts without profiles fall back to
    the analytic model)."""
    return (SCHED_PROFILE
            if (PROFILE_DIR / f"{SCHED_PROFILE}.json").exists() else None)


def _tile_op_for(name: str, schedule: str):
    from repro.kernels.tile_programs import get_tile_op
    return get_tile_op(name, schedule=schedule,
                       device_profile=(_sched_profile_name()
                                       if schedule == "cost" else None))


def _tile_features(op, schedule: str) -> dict:
    """Schedule features of the order actually emitted: the Pallas
    generator's own ScheduleResult for "cost", a recomputed named order
    otherwise (deterministic either way)."""
    from repro.analysis import kernel_features
    from repro.core import compute_schedule
    sr = op.pk.schedule
    if sr is None:
        sr = compute_schedule(op.sk.ssa, dict(op.sk.extraction.choice),
                              mode=schedule)
    return kernel_features(op.sk, schedule=sr).to_dict()


def measure_tile_schedules(name: str, reps: int, warmup: int = 3,
                           schedules=SCHEDULES) -> list:
    """Median per-call wall time of one tile program's Pallas kernel on
    a single (8, 128) tile (grid of one → per-call == per-instance),
    under every statement ``schedule``.

    The schedules are timed **interleaved round-robin** (rep 1 of every
    schedule, then rep 2, ...): all orders run the same number of ops,
    so sequential blocks would hand whichever schedule ran first any
    machine-load drift; interleaving gives every schedule the same
    drift profile and the medians compare cleanly. The within-cycle
    order additionally *rotates* every rep — a fixed order hands the
    later slots the earlier calls' GC/allocator debt, which showed up
    as a systematic per-position bias — and collection runs between
    cycles, outside the timed region.
    """
    import gc
    ops = {s: _tile_op_for(name, s) for s in schedules}
    arrays, scalars = tile_inputs_for(next(iter(ops.values())).sk.ssa.prog)
    args = [jax.numpy.asarray(a) for a in arrays]

    def call(op):
        return jax.block_until_ready(op.apply(*args, **scalars))

    for _ in range(warmup):
        for op in ops.values():
            call(op)
    times = {s: [] for s in schedules}
    order = list(schedules)
    gc_was_enabled = gc.isenabled()
    try:
        for rep in range(reps):
            gc.collect()
            gc.disable()
            rot = rep % len(order)
            for s in order[rot:] + order[:rot]:
                t0 = time.perf_counter()
                call(ops[s])
                times[s].append(time.perf_counter() - t0)
            if gc_was_enabled:
                gc.enable()
    finally:
        if gc_was_enabled:
            gc.enable()
    kind = ("pallas_interpret" if _backend() == "cpu"
            else "pallas_compiled")
    rows = []
    for s in schedules:
        row = {"kernel": name, "group": "tile", "measured_kind": kind,
               "schedule": s, "emitter": ops[s].pk.emitter,
               "measured_ns": statistics.median(times[s]) * 1e9,
               "reps": reps, "warmup": warmup,
               "features": _tile_features(ops[s], s)}
        if s != "bulk" and "bulk" in times:
            # paired per-rep delta vs the bulk order measured in the
            # same interleaved cycle: correlated machine-load noise
            # cancels, so this is the statistic the measured gate uses
            row["paired_vs_bulk_pct"] = statistics.median(
                100.0 * (c - b) / b
                for c, b in zip(times[s], times["bulk"]))
        rows.append(row)
    return rows


def measure_tile_kernel(name: str, reps: int, warmup: int = 3,
                        schedule: str = "bulk") -> dict:
    """Single-schedule measurement (the PR-4 entry point, kept for the
    smoke path and ad-hoc use)."""
    return measure_tile_schedules(name, reps, warmup,
                                  schedules=(schedule,))[0]


def measure_tile_compiled(name: str, reps: int, warmup: int = 3,
                          schedules=SCHEDULES) -> list:
    """The compiled lane (PR 8): the same tile kernels, each schedule
    jitted once and timed hot — ``measured_kind: "pallas_compiled"``.

    On CPU the Pallas call still runs in interpret mode, but *traced
    under jit*: XLA compiles the interpreted op graph, so the Python
    dispatch overhead that dominates the eager interpret lane is gone
    while the op costs remain. Rows record which it was in
    ``compile_path`` (``"xla_interpret"`` vs ``"native"``) so a fitted
    ``*_pallas_compiled_sched`` profile is never mistaken for real
    accelerator numbers. Interleaving/rotation/gc discipline matches
    :func:`measure_tile_schedules`."""
    import gc
    ops = {s: _tile_op_for(name, s) for s in schedules}
    arrays, scalars = tile_inputs_for(next(iter(ops.values())).sk.ssa.prog)
    args = [jax.numpy.asarray(a) for a in arrays]
    native = _backend() != "cpu"
    fns = {}
    for s, op in ops.items():
        fns[s] = jax.jit(lambda *a, _op=op: _op.apply(*a, **scalars))

    def call(fn):
        return jax.block_until_ready(fn(*args))

    for _ in range(warmup + 1):      # +1: jit compile outside the clock
        for fn in fns.values():
            call(fn)
    times = {s: [] for s in schedules}
    order = list(schedules)
    gc_was_enabled = gc.isenabled()
    try:
        for rep in range(reps):
            gc.collect()
            gc.disable()
            rot = rep % len(order)
            for s in order[rot:] + order[:rot]:
                t0 = time.perf_counter()
                call(fns[s])
                times[s].append(time.perf_counter() - t0)
            if gc_was_enabled:
                gc.enable()
    finally:
        if gc_was_enabled:
            gc.enable()
    rows = []
    for s in schedules:
        row = {"kernel": name, "group": "tile",
               "measured_kind": "pallas_compiled",
               "compile_path": "native" if native else "xla_interpret",
               "schedule": s, "emitter": ops[s].pk.emitter,
               "measured_ns": statistics.median(times[s]) * 1e9,
               "reps": reps, "warmup": warmup,
               "features": _tile_features(ops[s], s)}
        if s != "bulk" and "bulk" in times:
            row["paired_vs_bulk_pct"] = statistics.median(
                100.0 * (c - b) / b
                for c, b in zip(times[s], times["bulk"]))
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# NPB/SPEC suite kernels through the jitted grid runner
# ---------------------------------------------------------------------------
def measure_suite_kernel(name: str, reps: int, n: int = 64 * 64,
                         warmup: int = 1) -> dict:
    from repro.analysis import kernel_features
    from repro.core import SaturatorConfig, saturate_program
    from benchmarks.ablation import build_grid_runner
    from benchmarks.kernel_suite import SUITE, inputs_for
    arrays, gscalar, grid, scalars = inputs_for(name, n=n)
    sk = saturate_program(SUITE[name](), SaturatorConfig())
    fn, init_state, n_threads = build_grid_runner(sk, arrays, gscalar,
                                                  grid, scalars)
    for _ in range(warmup + 1):       # +1: jit compile
        jax.block_until_ready(fn(init_state))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(init_state))
        times.append(time.perf_counter() - t0)
    return {"kernel": name, "group": "suite",
            "measured_kind": f"jax_{_backend()}_grid",
            "measured_ns": statistics.median(times) / n_threads * 1e9,
            "reps": reps, "warmup": warmup, "n_threads": n_threads,
            # scalars resolve runtime-bound trip counts (cg_like's nnz)
            "features": kernel_features(sk, scalars=scalars).to_dict()}


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------
def measure_all(kernels=None, reps: int = 5, n: int = 64 * 64,
                schedules=SCHEDULES, compiled: bool = True) -> dict:
    """Measure every requested kernel; returns the measurements document
    (also the ``measure`` section of ``benchmarks/run.py``). Tile
    kernels are timed once per statement schedule — same extracted
    term, different emission order — and, with ``compiled`` on a CPU
    host, once more per schedule under jit (the compiled lane; on
    accelerators the eager lane already *is* ``pallas_compiled``, so no
    second lane runs)."""
    from benchmarks.kernel_suite import SUITE
    from repro.analysis import DEFAULT_PARAMS, predict_ns, KernelFeatures
    rows = []
    for name in TILE_KERNELS:
        if kernels and name not in kernels:
            continue
        rows.extend(measure_tile_schedules(name, reps, schedules=schedules))
        if compiled and _backend() == "cpu":
            rows.extend(measure_tile_compiled(name, reps,
                                              schedules=schedules))
    for name in SUITE:
        if kernels and name not in kernels:
            continue
        rows.append(measure_suite_kernel(name, reps, n=n))
    for r in rows:
        feat = KernelFeatures.from_dict(r["features"])
        r["predicted_ns"] = predict_ns(feat, DEFAULT_PARAMS)
    return {"schema_version": MEASUREMENTS_SCHEMA_VERSION,
            "backend": _backend(), "emitter": "pallas", "rows": rows}


def fit_profiles(doc: dict, out_dir: pathlib.Path = PROFILE_DIR) -> list:
    """Fit one device profile per measured_kind group.

    Tile-kernel groups are fitted from their **cost-schedule** rows with
    the PR-5 schedule features (per-load overlap windows), yielding a
    schedule-aware ``<backend>_<kind>_sched`` profile; the committed
    PR-4 ``<backend>_<kind>`` profile (bulk schedule, no schedule
    features) is left untouched so the two stay comparable in CI. The
    fitted sched profile additionally embeds every schedule's measured
    medians (``fit["schedule_medians"]``) — the bench-regression gate's
    measured cost-vs-bulk leg reads them without re-timing.

    A fit is *promoted* into ``experiments/device_profiles/`` (and from
    there enforced by the bench-regression CI gate) only when it clears
    the acceptance bar — Spearman >= 0.8 and strictly better MAPE than
    the uncalibrated defaults. Fits that fail land in the gitignored out
    dir with a warning: a profile the model cannot rank faithfully would
    make extraction *worse*, not better (e.g. the jitted-grid suite
    path, where XLA fuses the scalar thread bodies so tile-semantics
    features cannot explain the measured ordering).
    """
    from repro.analysis import SPEARMAN_FLOOR, KernelFeatures, fit_profile
    groups = {}
    for r in doc["rows"]:
        sched = r.get("schedule")
        if r.get("group") == "tile" and sched is not None \
                and sched != "cost":
            continue   # only the cost-schedule rows are fitted
        groups.setdefault(r["measured_kind"], []).append(r)
    medians = {}   # per measured_kind: the lanes must not mix (PR 8)
    for r in doc["rows"]:
        if r.get("group") == "tile" and r.get("schedule") is not None:
            entry = medians.setdefault(r["measured_kind"], {}) \
                .setdefault(r["kernel"], {})
            entry[r["schedule"]] = r["measured_ns"]
            if r["schedule"] == "cost" and "paired_vs_bulk_pct" in r:
                entry["cost_vs_bulk_paired_pct"] = r["paired_vs_bulk_pct"]
    written = []
    for kind, rows in sorted(groups.items()):
        if len(rows) < 2:
            print(f"skip {kind}: need >= 2 kernels to fit, have {len(rows)}")
            continue
        feats = [KernelFeatures.from_dict(r["features"]) for r in rows]
        meas = [r["measured_ns"] for r in rows]
        backend = doc["backend"]
        sched_group = rows[0].get("group") == "tile"
        # profile file stem: <measured device>_<path>, e.g.
        # cpu_pallas_interpret_sched, cpu_jax_grid, tpu_pallas_compiled
        name = (f"{backend}_jax_grid" if kind == f"jax_{backend}_grid"
                else f"{backend}_{kind}")
        if sched_group:
            name += "_sched"
        prof = fit_profile(feats, meas, name=name, chip=backend,
                           measured_kind=kind)
        if sched_group and medians.get(kind):
            prof.fit["schedule_medians"] = medians[kind]
            prof.fit["schedule_mode"] = "cost"
            cp = rows[0].get("compile_path")
            if cp is not None:
                prof.fit["compile_path"] = cp
        f = prof.fit
        ok = (f["spearman"] >= SPEARMAN_FLOOR
              and f["mape_pct"] < f["uncalibrated_mape_pct"])
        path = prof.save((out_dir if ok else OUT_ROOT) / f"{name}.json")
        print(f"fitted {name}: {len(rows)} kernels  "
              f"MAPE {f['mape_pct']:.1f}% (uncal {f['uncalibrated_mape_pct']:.1f}%)  "
              f"Spearman {f['spearman']:.3f} (uncal {f['uncalibrated_spearman']:.3f})")
        if f.get("kernels") and prof.params.overlap_efficiency is not None:
            print(f"  fitted overlap_efficiency "
                  f"{prof.params.overlap_efficiency:.3f}")
        if ok:
            written.append(path)
        else:
            print(f"  NOT promoted (needs Spearman >= {SPEARMAN_FLOOR} and "
                  f"MAPE < uncalibrated): kept at {path}")
    return written


def smoke() -> int:
    """CI calibration smoke: fit 2 tile kernels in interpret mode and
    assert the resulting profile round-trips and scores sanely. Uses
    the cost-driven schedule, so the schedule features (per-load
    overlap windows) flow through fit and persistence end-to-end."""
    from repro.analysis import (DeviceProfile, KernelFeatures, check_profile,
                                fit_profile, load_profile)
    rows = [measure_tile_kernel(k, reps=3, schedule="cost")
            for k in SMOKE_KERNELS]
    for r in rows:
        assert r["features"].get("sched_loads"), \
            "cost-schedule measurement lost its schedule features"
    feats = [KernelFeatures.from_dict(r["features"]) for r in rows]
    meas = [r["measured_ns"] for r in rows]
    prof = fit_profile(feats, meas, name="smoke", chip=_backend(),
                       measured_kind=rows[0]["measured_kind"])
    back = DeviceProfile.from_json(prof.to_json(), name="smoke")
    assert back.params == prof.params, "profile params did not round-trip"
    assert back.fit == prof.fit, "profile fit evidence did not round-trip"
    out = OUT_ROOT / "smoke_profile.json"
    prof.save(out)
    loaded = load_profile(out)
    assert loaded.params == prof.params, "saved profile did not load back"
    lm = loaded.latency_model()
    assert lm.hbm_efficiency == prof.params.hbm_efficiency
    # 2 points / many params → the fit must interpolate near-exactly
    assert prof.fit["mape_pct"] < 5.0, \
        f"2-kernel fit MAPE {prof.fit['mape_pct']:.2f}% unexpectedly large"
    fails = check_profile(loaded, spearman_floor=0.0)
    assert not fails, f"smoke profile failed checks: {fails}"
    print(f"calibration smoke OK: {len(rows)} kernels, "
          f"MAPE {prof.fit['mape_pct']:.2f}%, profile round-trips ({out})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", help="comma-separated subset")
    ap.add_argument("--reps", type=int, default=9,
                    help="median-of-N timing repeats (default 9)")
    ap.add_argument("--n", type=int, default=64 * 64,
                    help="suite grid size (default 4096 threads)")
    ap.add_argument("--schedules", default=",".join(SCHEDULES),
                    help="comma-separated statement schedules to time "
                         f"per tile kernel (default {','.join(SCHEDULES)})")
    ap.add_argument("--no-compiled", action="store_true",
                    help="skip the jitted compiled lane on CPU hosts")
    ap.add_argument("--fit", action="store_true",
                    help="fit device profiles from the measurements and "
                         f"save them under {PROFILE_DIR}")
    ap.add_argument("--smoke", action="store_true",
                    help="2-kernel interpret-mode fit + round-trip check")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT,
                    help="measurements JSON path")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    kernels = set(args.kernels.split(",")) if args.kernels else None
    doc = measure_all(kernels=kernels, reps=args.reps, n=args.n,
                      schedules=tuple(args.schedules.split(",")),
                      compiled=not args.no_compiled)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"wrote {args.out} ({len(doc['rows'])} rows, "
          f"backend={doc['backend']}, emitter={doc['emitter']})")
    for r in doc["rows"]:
        sched = r.get("schedule", "-")
        lane = r["measured_kind"] + (
            f"/{r['compile_path']}" if "compile_path" in r else "")
        print(f"  {r['kernel']:24s} {sched:>6s} {r['measured_ns']:14.1f} ns"
              f"  [{lane}]")
    if args.fit:
        written = fit_profiles(doc)
        for p in written:
            print(f"wrote {p}")
        print("NOTE: refresh the committed predicted-vs-measured table + "
              "baseline with `python benchmarks/bench_regression.py "
              "--update` and commit the diffs.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
