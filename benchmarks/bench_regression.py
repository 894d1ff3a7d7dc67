"""Benchmark-regression gate for CI (PR 3 satellite, PR 4 calibration).

Runs the saturator over the full kernel suite (NPB/SPEC-style kernels +
model tile programs), extracts every kernel with both the beam search and
the PR-2 hill climb, and compares the roofline-predicted latency and
extracted DAG cost against the committed baseline
(``experiments/bench_baseline.json``).

The build fails when any kernel:

* regresses more than ``TOLERANCE_PCT`` (2%) in predicted latency or DAG
  cost vs the baseline, or
* extracts *worse* with the beam than with the hill climb (the beam is
  seeded with the hill climb's restarts, so this indicates a search
  regression, not noise);

or when the committed device profiles (``experiments/device_profiles/``,
the calibrated predicted-vs-measured loop) stop holding their bar:

* no committed profile exists at all,
* a profile's calibrated Spearman rank correlation — recomputed from its
  stored measurements with the *current* model code — falls below the
  0.8 floor or below the value stored at fit time, or
* its calibrated MAPE stops beating the uncalibrated defaults;

or when the PR-5 **scheduling legs** break:

* predicted schedule latency must rank cost <= bulk <= source for every
  kernel (recomputed deterministically in-run from the saturated
  e-graphs),
* the committed schedule-aware profile's embedded measured medians must
  show the cost-driven order within ``TOLERANCE_PCT`` of bulk per
  kernel (paired per-rep deltas — nothing is re-timed in CI), and
* the schedule-aware profile must keep beating the PR-4 profile on
  Spearman or MAPE over the cost-schedule measurements.

The gate also (re)writes the top-level ``BENCH_5.json`` perf
trajectory (per-kernel predicted + measured ns by schedule, profile
id); CI fails if the committed copy drifts.

Predicted metrics are model-computed (chip constants) and every search
pass stops on a deterministic evaluation budget (`beam_expansions`,
`hillclimb_evals`) rather than the wall clock, with generous time
ceilings as pure safety nets (``saturation_stats.GATE_CONFIG``) — so
the gate is exact on any runner regardless of machine speed or load,
unlike wall-clock benchmarks. The calibration checks are equally exact:
they re-score committed measurements, they do not re-time anything. The
hill-climb comparison re-extracts the *same* saturated e-graph, so
beam <= hillclimb holds structurally within one run. The script re-execs
itself with ``PYTHONHASHSEED=0`` — e-node sets iterate in hash order, so
rule-match ordering (and with it plateau tie-breaks in extraction) would
otherwise drift per process. Kernels new since the baseline are reported
but do not fail the gate; refresh the baseline with ``--update`` after
intentional cost-model or extraction changes and commit the diff.

All regenerated artifacts live under gitignored ``experiments/out/``;
only the baseline, the device profiles, and the latency table are
committed. The baseline is schema-versioned: a version mismatch fails
loudly instead of silently comparing incompatible numbers.

Usage:
    python benchmarks/bench_regression.py            # check vs baseline
    python benchmarks/bench_regression.py --update   # rewrite baseline
"""
from __future__ import annotations

import json
import pathlib
import sys

if __package__ in (None, ""):        # direct script invocation
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from benchmarks.bootstrap import OUT_ROOT, ROOT  # noqa: E402
from benchmarks.hashseed import reexec_with_fixed_hashseed  # noqa: E402

reexec_with_fixed_hashseed()

BASELINE = ROOT / "experiments" / "bench_baseline.json"
PROFILE_DIR = ROOT / "experiments" / "device_profiles"
CURRENT = OUT_ROOT / "bench_current.json"
BEAM_STATS = OUT_ROOT / "beam_stats.json"
BENCH5 = ROOT / "BENCH_5.json"
BENCH6 = ROOT / "BENCH_6.json"
BENCH9 = ROOT / "BENCH_9.json"
SCHED_PROFILE = "cpu_pallas_interpret_sched"   # PR-5 schedule-aware fit
BASE_PROFILE = "cpu_pallas_interpret"          # PR-4 bulk-order fit

BASELINE_SCHEMA_VERSION = 3   # 2 = PR 4 (no schedule block); 1 = PR 3
BENCH5_SCHEMA_VERSION = 1
BENCH6_SCHEMA_VERSION = 1
BENCH9_SCHEMA_VERSION = 1
BENCH6_REPLAY_FLOOR = 10.0   # committed cold/replay saturation speedup
TOLERANCE_PCT = 2.0
ABS_EPS = 1e-6          # ignore float dust on tiny costs
BEAM_EPS = 1e-6
SCHED_EPS = 1e-6


def collect():
    from benchmarks.saturation_stats import run_saturation_stats
    res = run_saturation_stats(compare_hillclimb=True)
    metrics = {}
    for r in res["rows"]:
        metrics[r["kernel"]] = {
            "predicted_latency_ns": r["predicted_latency_ns"],
            "dag_cost": r["dag_cost"],
            "hillclimb_latency_ns": r["hillclimb_latency_ns"],
            "hillclimb_dag_cost": r["hillclimb_dag_cost"],
            "beam_vs_hillclimb_pct": r["beam_vs_hillclimb_pct"],
            "oracle_gap": r["oracle_gap"],
            "schedule_predicted": r["schedule_predicted"],
        }
    return res, metrics


def load_baseline() -> dict:
    """Parse the committed baseline, failing loudly on schema drift."""
    try:
        doc = json.loads(BASELINE.read_text())
    except json.JSONDecodeError as e:
        print(f"ERROR: baseline {BASELINE} is not valid JSON: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    ver = doc.get("schema_version") if isinstance(doc, dict) else None
    if ver != BASELINE_SCHEMA_VERSION:
        print(
            f"ERROR: baseline {BASELINE} has schema_version {ver!r}, this "
            f"gate expects {BASELINE_SCHEMA_VERSION}. A silent comparison "
            "of incompatible schemas hides real regressions — regenerate "
            "with `python benchmarks/bench_regression.py --update` and "
            "commit the diff.", file=sys.stderr)
        raise SystemExit(2)
    return doc["kernels"]


def check(metrics, baseline) -> list:
    failures = []
    # losing a kernel is itself a regression (coverage silently shrank)
    missing = sorted(set(baseline) - set(metrics))
    if missing:
        failures.append(
            f"kernel(s) in baseline but absent from this run: {missing} "
            "(remove them with --update if intentional)")
    for kernel, cur in sorted(metrics.items()):
        # structural invariant: beam never worse than hill climb ON THE
        # EXTRACTION OBJECTIVE (dag_cost, store-free). The reported
        # latencies add constant store traffic, and a roofline max does
        # not preserve ordering under a shift on one axis — a genuinely
        # better but more memory-leaning beam pick could legally show a
        # higher store-inclusive latency, so that pair is not gated.
        if cur["dag_cost"] > cur["hillclimb_dag_cost"] + BEAM_EPS:
            failures.append(
                f"{kernel}: beam dag_cost {cur['dag_cost']:.6f} worse "
                f"than hill climb {cur['hillclimb_dag_cost']:.6f}")
        base = baseline.get(kernel)
        if base is None:
            print(f"  NEW    {kernel} (not in baseline; add with --update)")
            continue
        for metric in ("predicted_latency_ns", "dag_cost"):
            b, c = base[metric], cur[metric]
            if c > b + ABS_EPS and (c - b) > abs(b) * TOLERANCE_PCT / 100.0:
                pct = f"+{100.0 * (c - b) / b:.2f}%" if b else "from zero"
                failures.append(
                    f"{kernel}: {metric} regressed "
                    f"{b:.4f} -> {c:.4f} ({pct} > {TOLERANCE_PCT}%)")
    return failures


def check_schedule_predicted(metrics) -> list:
    """Scheduling leg 1 (deterministic, recomputed in-run): for every
    kernel the cost-driven schedule's predicted latency must be <= the
    bulk-load schedule's, which must be <= the source order's — the
    paper's computational-reordering claim, as an invariant."""
    failures = []
    for kernel, cur in sorted(metrics.items()):
        sp = cur.get("schedule_predicted") or {}
        if not sp:
            failures.append(f"{kernel}: no schedule predictions in run")
            continue
        if sp["cost"] > sp["bulk"] + SCHED_EPS:
            failures.append(
                f"{kernel}: cost schedule predicted {sp['cost']:.4f} ns "
                f"worse than bulk {sp['bulk']:.4f} ns")
        if sp["bulk"] > sp["source"] + SCHED_EPS:
            failures.append(
                f"{kernel}: bulk schedule predicted {sp['bulk']:.4f} ns "
                f"worse than source {sp['source']:.4f} ns")
    return failures


def _load_profile_or_none(name):
    from repro.analysis import load_profile
    path = PROFILE_DIR / f"{name}.json"
    if not path.exists():
        return None
    return load_profile(path)


def check_schedule_measured() -> list:
    """Scheduling leg 2 (deterministic — committed medians only): the
    schedule-aware profile's embedded per-schedule measured medians
    must show the cost-driven order no slower than bulk beyond the
    noise tolerance, and the schedule-aware fit must beat the PR-4
    profile on Spearman or MAPE when both are re-scored with the
    current model code."""
    from repro.analysis import evaluate_params
    prof = _load_profile_or_none(SCHED_PROFILE)
    if prof is None:
        return [f"no committed schedule-aware profile "
                f"{SCHED_PROFILE} under {PROFILE_DIR}; fit one with "
                "`python benchmarks/measure.py --fit`"]
    failures = []
    medians = prof.fit.get("schedule_medians", {})
    if not medians:
        failures.append(f"profile {prof.name}: no embedded "
                        "schedule_medians evidence")
    worse = 0
    for kernel, by_sched in sorted(medians.items()):
        bulk, cost = by_sched.get("bulk"), by_sched.get("cost")
        if bulk is None or cost is None:
            failures.append(f"{kernel}: schedule_medians missing "
                            "bulk/cost entries")
            continue
        # the gated statistic is the *paired* per-rep delta (cost and
        # bulk timed in the same interleaved cycle — machine-load noise
        # cancels); the raw medians are evidence, not the gate
        from repro.analysis import schedule_paired_pct
        delta = schedule_paired_pct(by_sched)
        if delta > TOLERANCE_PCT:
            failures.append(
                f"{kernel}: measured cost schedule {delta:+.2f}% vs bulk "
                f"(paired median) beyond the {TOLERANCE_PCT}% tolerance")
        if delta > 0:
            worse += 1
    if medians:
        print(f"  schedule medians: cost <= bulk (paired, within "
              f"{TOLERANCE_PCT}%) on {len(medians)} kernels "
              f"({len(medians) - worse} at-or-better outright)")
    base = _load_profile_or_none(BASE_PROFILE)
    if base is None:
        failures.append(f"committed PR-4 profile {BASE_PROFILE} missing — "
                        "cannot compare the schedule-aware fit against it")
        return failures

    # both parameter sets are re-scored against the SAME measurements —
    # the schedule-aware profile's stored cost-schedule rows (PR-4
    # params see the same features; without a fitted overlap term the
    # schedule fields are inert for them), so the comparison asks one
    # question deterministically: which calibration explains the
    # measured data better under the current model code?
    from repro.analysis.calibrate import chip_by_name
    feats = prof.stored_features()
    meas = prof.stored_measurements()

    def rescore(p):
        return evaluate_params(feats, meas, p.params,
                               chip=chip_by_name(p.model_chip),
                               tile_elems=p.tile_elems)
    s, b = rescore(prof), rescore(base)
    print(f"  on the cost-schedule measurements — sched profile vs PR-4: "
          f"Spearman {s['spearman']:.3f} vs {b['spearman']:.3f}, "
          f"MAPE {s['mape_pct']:.2f}% vs {b['mape_pct']:.2f}%")
    if not (s["spearman"] > b["spearman"] + 1e-12
            or s["mape_pct"] < b["mape_pct"] - 1e-12):
        failures.append(
            f"schedule-aware profile {prof.name} no longer beats "
            f"{base.name} on Spearman ({s['spearman']:.3f} vs "
            f"{b['spearman']:.3f}) or MAPE ({s['mape_pct']:.2f}% vs "
            f"{b['mape_pct']:.2f}%) on the cost-schedule measurements")
    return failures


def write_bench5(metrics) -> None:
    """Top-level machine-readable perf trajectory: per kernel, the
    predicted latency of every statement schedule (this run,
    deterministic) and the measured medians embedded in the committed
    schedule-aware profile. Committed and drift-checked by CI, so the
    trajectory is comparable across PRs."""
    prof = _load_profile_or_none(SCHED_PROFILE)
    medians = prof.fit.get("schedule_medians", {}) if prof else {}
    kernels = {}
    for kernel, cur in sorted(metrics.items()):
        bare = kernel.split(":", 1)[-1]
        row = {
            "schedule": "cost",
            "predicted_ns": {k: round(v, 4) for k, v in
                             (cur.get("schedule_predicted") or {}).items()},
            "extraction_predicted_latency_ns":
                round(cur["predicted_latency_ns"], 4),
        }
        if bare in medians:
            row["measured_ns"] = {k: round(v, 1) for k, v in
                                  sorted(medians[bare].items())}
            row["measured_kind"] = prof.measured_kind
            row["profile"] = prof.name
        kernels[kernel] = row
    doc = {"schema_version": BENCH5_SCHEMA_VERSION,
           "pr": 5,
           "description": "per-kernel predicted + measured median ns by "
                          "statement schedule (see benchmarks/"
                          "bench_regression.py and docs/cost_model.md)",
           "kernels": kernels}
    BENCH5.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    print(f"wrote {BENCH5} ({len(kernels)} kernels)")


def check_bench6() -> list:
    """Drift check for the committed PR-6 serve-decode cache report.

    Wall clocks are machine-dependent, so unlike the BENCH_5 leg this
    does not recompute anything: it validates that the committed report
    parses, matches the expected schema, and that its invariant facts
    hold — a fully-warm second boot (hit rate 1.0, no warm-boot
    misses), positive throughputs, and a cold/replay saturation-time
    speedup at or above the floor the cache exists to deliver."""
    if not BENCH6.exists():
        return [f"missing {BENCH6}; regenerate with `PYTHONPATH=src "
                "python examples/serve_decode.py --out BENCH_6.json` "
                "and commit it"]
    try:
        doc = json.loads(BENCH6.read_text())
    except json.JSONDecodeError as e:
        return [f"{BENCH6.name}: invalid JSON: {e}"]
    ver = doc.get("schema_version")
    if ver != BENCH6_SCHEMA_VERSION:
        return [f"{BENCH6.name}: schema_version {ver!r}, expected "
                f"{BENCH6_SCHEMA_VERSION} — regenerate and commit"]
    failures = []
    for sec in ("saturated", "reference"):
        tps = (doc.get(sec) or {}).get("tokens_per_s", 0)
        if not tps or tps <= 0:
            failures.append(f"{BENCH6.name}: {sec}.tokens_per_s missing "
                            "or non-positive")
    cache = doc.get("cache")
    if not isinstance(cache, dict):
        failures.append(f"{BENCH6.name}: no cache section (was it "
                        "generated with --no-cache?)")
        return failures
    cold, warm = cache.get("cold") or {}, cache.get("warm") or {}
    if cold.get("misses", 0) < 1 or cold.get("stores", 0) < 1:
        failures.append(f"{BENCH6.name}: cold boot recorded no cache "
                        "misses/stores — the cache was never exercised")
    if warm.get("hit_rate") != 1.0 or warm.get("misses", 1) != 0:
        failures.append(
            f"{BENCH6.name}: warm boot not fully served from cache "
            f"(hit_rate={warm.get('hit_rate')!r}, "
            f"misses={warm.get('misses')!r})")
    speedup = cache.get("replay_speedup", 0)
    if not speedup or speedup < BENCH6_REPLAY_FLOOR:
        failures.append(
            f"{BENCH6.name}: committed replay_speedup {speedup!r} below "
            f"the {BENCH6_REPLAY_FLOOR:.0f}x floor")
    if not failures:
        print(f"  BENCH_6 ok: warm hit_rate=1.0, replay "
              f"{speedup:.0f}x, saturated "
              f"{doc['saturated']['tokens_per_s']:.1f} tok/s vs ref "
              f"{doc['reference']['tokens_per_s']:.1f} tok/s")
    return failures


def check_bench9() -> list:
    """Drift check for the committed PR-9 tuning summary (BENCH_9.json).

    Winners are measured, hence machine-dependent — they are validated
    structurally (a legal, sublane-aligned survivor). The *static* half
    is recomputed exactly: candidate/pruned counts, prune reasons, and
    survivor sets come from ``benchmarks.tune.static_prune`` (grid-pass
    legality, VMEM against the scoped limit), so any change to the candidate list,
    the prune rules, or the verifier's legality verdicts shows up as
    drift against the committed document."""
    if not BENCH9.exists():
        return [f"missing {BENCH9}; regenerate with `PYTHONPATH=src "
                "python benchmarks/tune.py --update-bench` and commit it"]
    try:
        doc = json.loads(BENCH9.read_text())
    except json.JSONDecodeError as e:
        return [f"{BENCH9.name}: invalid JSON: {e}"]
    ver = doc.get("schema_version")
    if ver != BENCH9_SCHEMA_VERSION:
        return [f"{BENCH9.name}: schema_version {ver!r}, expected "
                f"{BENCH9_SCHEMA_VERSION} — regenerate and commit"]
    from benchmarks.tune import static_prune
    rows = doc.get("rows")
    kernels = doc.get("kernels") or {}
    failures = []
    if not kernels:
        return [f"{BENCH9.name}: no kernels section"]
    for name, rec in sorted(kernels.items()):
        cur = static_prune(name, rows=rows)
        for key in ("n_candidates", "n_pruned", "pruned_reasons",
                    "survivors", "default_row_block"):
            if rec.get(key) != cur[key]:
                failures.append(
                    f"{BENCH9.name}: {name}.{key} drifted — committed "
                    f"{rec.get(key)!r}, recomputed {cur[key]!r}")
        win = rec.get("winner_row_block")
        if win is not None:
            if win not in cur["survivors"]:
                failures.append(f"{BENCH9.name}: {name} winner {win} is "
                                "not a legal survivor")
            elif win % 8:
                failures.append(f"{BENCH9.name}: {name} winner {win} is "
                                "not sublane-aligned")
    if not failures:
        total = sum(r["n_pruned"] for r in kernels.values())
        avg = total / len(kernels)
        if avg < 1.0:
            failures.append(
                f"{BENCH9.name}: avg {avg:.2f} candidates pruned per "
                "kernel — the static filter prunes nothing")
        else:
            print(f"  BENCH_9 ok: {len(kernels)} kernels, avg {avg:.1f} "
                  f"candidates statically pruned, winners all legal "
                  f"survivors")
    return failures


def check_calibration() -> list:
    """The predicted-vs-measured leg of the gate: every committed device
    profile must still rank kernels faithfully under the current model
    code (Spearman >= floor, >= its committed baseline, MAPE better than
    uncalibrated). Deterministic — re-scores stored measurements only."""
    from repro.analysis import check_profile, load_profile
    paths = sorted(PROFILE_DIR.glob("*.json"))
    if not paths:
        return [f"no committed device profiles under {PROFILE_DIR}; the "
                "calibrated predicted-vs-measured loop is unverified "
                "(fit one with `python benchmarks/measure.py --fit`)"]
    failures = []
    for p in paths:
        try:
            prof = load_profile(p)
        except Exception as e:
            failures.append(f"{p.name}: unloadable profile: {e}")
            continue
        fails = check_profile(prof)
        failures.extend(fails)
        f = prof.fit
        status = "FAIL" if fails else "ok"
        print(f"  profile {prof.name:24s} [{status}] "
              f"spearman {f.get('spearman', float('nan')):.3f} "
              f"(uncal {f.get('uncalibrated_spearman', float('nan')):.3f})  "
              f"MAPE {f.get('mape_pct', float('nan')):.1f}% "
              f"(uncal {f.get('uncalibrated_mape_pct', float('nan')):.1f}%)")
    return failures


def main() -> int:
    update = "--update" in sys.argv
    res, metrics = collect()

    CURRENT.parent.mkdir(parents=True, exist_ok=True)
    CURRENT.write_text(json.dumps(metrics, indent=2, sort_keys=True) + "\n")
    beam_rows = [{k: r[k] for k in
                  ("kernel", "search", "predicted_latency_ns",
                   "hillclimb_latency_ns", "beam_vs_hillclimb_pct",
                   "dag_cost", "hillclimb_dag_cost", "beam_generations",
                   "beam_expanded", "oracle_gap", "extract_s")}
                 for r in res["rows"]]
    BEAM_STATS.write_text(json.dumps(beam_rows, indent=2) + "\n")
    print(f"wrote {CURRENT} and {BEAM_STATS} ({len(metrics)} kernels)")

    # refresh the latency table from the same run (artifact-uploaded by
    # CI) — includes the predicted-vs-measured calibration section
    from benchmarks.roofline_table import kernel_table
    kernel_table(res)
    # machine-readable perf trajectory (committed; CI checks drift)
    write_bench5(metrics)

    if update:
        BASELINE.write_text(json.dumps(
            {"schema_version": BASELINE_SCHEMA_VERSION, "kernels": metrics},
            indent=2, sort_keys=True) + "\n")
        print(f"baseline updated: {BASELINE}")
        return 0

    if not BASELINE.exists():
        print(f"ERROR: no baseline at {BASELINE}; "
              "run with --update and commit it", file=sys.stderr)
        return 2
    baseline = load_baseline()
    failures = check(metrics, baseline)
    for kernel, cur in sorted(metrics.items()):
        base = baseline.get(kernel, {})
        b = base.get("predicted_latency_ns")
        print(f"  {kernel:24s} lat {cur['predicted_latency_ns']:10.2f} ns"
              f" (base {b if b is None else format(b, '10.2f')})"
              f"  beamΔ {cur['beam_vs_hillclimb_pct']:+.2f}%")
    print("schedule leg (predicted cost <= bulk <= source):")
    failures += check_schedule_predicted(metrics)
    print("schedule leg (committed measured medians):")
    failures += check_schedule_measured()
    print("calibrated predicted-vs-measured check:")
    failures += check_calibration()
    print("BENCH_6 serve-decode cache report:")
    failures += check_bench6()
    print("BENCH_9 statically-pruned block tuning:")
    failures += check_bench9()
    if failures:
        print(f"\nFAIL: {len(failures)} regression(s) "
              f"(tolerance {TOLERANCE_PCT}%):", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"\nOK: {len(metrics)} kernels within {TOLERANCE_PCT}% of "
          "baseline; beam never worse than hill climb; schedules ranked "
          "cost <= bulk <= source with measured cost medians inside the "
          "bulk tolerance; calibrated profiles rank >= 0.8 Spearman and "
          "beat uncalibrated MAPE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
