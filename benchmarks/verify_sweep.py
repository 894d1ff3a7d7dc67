"""Exhaustive static-verification sweep (PR 7 acceptance gate).

Certifies every tile kernel under both rule sets and all three
statement orders with ``repro.verify``:

* per (kernel, rule set): one saturation, then **e-graph invariants**;
* per (kernel, rule set, schedule mode): **schedule legality** of the
  explicitly computed order (searchless for source/bulk so the
  certified order is exactly what the emitter/cache replays) and the
  **generated-code AST pass** over both the JAX source and — for
  tilable programs — the Pallas source;
* per rule set: **rule soundness** (random/bf16/adversarial
  differential validation);
* per (kernel, schedule mode): the **grid pass** (PR 9)
  — the emitted kernel's ``plan_tile_call`` launch plan is certified
  coverage-complete, write-disjoint, in-bounds (padded remainder tile
  modeled) and inside the exact VMEM budget, at a geometry that forces
  a ragged remainder tile. The hand-written flash-attention and
  SSD-scan BlockSpec layouts are audited once through the same engine.

Exit status is non-zero on any error-severity finding, so CI's
``verify-smoke`` job (a 3-kernel subset via ``--kernels``) gates on
zero errors. Run the full sweep with::

    PYTHONPATH=src python -m benchmarks.verify_sweep [--json out.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Dict, List

from repro.core import (SaturatorConfig, SearchConfig, compute_schedule,
                        saturate_program)
from repro.core.pallasgen import SyncPallasGenerator
from repro.core.pipeline import _schedule_cm
from repro.core.schedule import SCHEDULE_MODES
from repro.kernels.tile_programs import PROGRAMS
from repro.verify import (VerifyReport, check_egraph, check_generated,
                          check_grid, flash_attention_model, shapes_of,
                          ssd_scan_model, verify_rules, verify_schedule)
from repro.verify.grid_check import check_tile_kernel_grid

RULE_SETS = ("paper", "extended")


def _config(rule_set: str) -> SaturatorConfig:
    return SaturatorConfig(mode="accsat",
                           extended_rules=(rule_set == "extended"),
                           search_cfg=SearchConfig(
                               time_limit_s=120.0,
                               extract_time_limit_s=120.0))


def sweep(kernels: List[str]) -> Dict:
    report = VerifyReport()
    rows: List[Dict] = []
    for rule_set in RULE_SETS:
        cfg = _config(rule_set)
        rres = verify_rules(cfg.rules())
        report.extend(rres.findings)
        report.rules_checked += rres.rules_checked
        for kname in kernels:
            prog = PROGRAMS[kname]()
            sk = saturate_program(prog, cfg)
            kfs = list(check_egraph(sk.ssa.egraph))
            report.egraphs_checked += 1
            certified = 0
            grids = 0
            scheds = {}
            for mode in SCHEDULE_MODES:
                # searchless for source/bulk — certify exactly the order
                # the legacy emitters/cache replay; the cost mode keeps
                # its deterministic search budget
                kw = {} if mode == "cost" else {"move_budget": 0}
                sched = compute_schedule(
                    sk.ssa, dict(sk.extraction.choice), mode=mode,
                    cost_model=_schedule_cm(cfg, prog, sk.ssa.egraph),
                    **kw)
                scheds[mode] = sched
                scr = verify_schedule(sk.ssa, sk.extraction.choice, sched)
                kfs.extend(scr.findings)
                certified += scr.regions_certified
            kfs.extend(check_generated(sk.kernel.source, shapes_of(prog),
                                       subject=f"{kname}:jax"))
            report.sources_checked += 1
            try:
                pk = SyncPallasGenerator(sk.ssa, sk.extraction,
                                         bulk=True).generate_pallas()
            except NotImplementedError:
                pk = None          # not tilable: JAX source only
            if pk is not None:
                kfs.extend(check_generated(pk.source, shapes_of(prog),
                                           subject=f"{kname}:pallas"))
                report.sources_checked += 1
                # grid pass: one emission per schedule mode reuses the
                # saturation above — geometry certification needs only
                # the emitted kernel, not a fresh pipeline run
                for mode in SCHEDULE_MODES:
                    epk = SyncPallasGenerator(
                        sk.ssa, sk.extraction, bulk=True,
                        schedule=scheds[mode]).generate_pallas()
                    gres = check_tile_kernel_grid(epk, prog)
                    kfs.extend(dataclasses.replace(
                        f, subject=f"{mode}/pallas:{f.subject}")
                        for f in gres.findings)
                    grids += gres.grids_checked
            report.extend(kfs)
            report.schedules_certified += certified
            report.grids_checked += grids
            errors = [f for f in kfs if f.severity == "error"]
            rows.append({
                "kernel": kname, "rule_set": rule_set,
                "schedules_certified": certified,
                "grids_checked": grids,
                "findings": len(kfs), "errors": len(errors),
            })
            for f in errors:
                print(f"  {kname}/{rule_set}: {f}", file=sys.stderr)

    # the hand-written Pallas kernels outside the saturator pipeline:
    # their BlockSpec layouts (attention_layout / ssd_layout) feed the
    # same symbolic engine. flash attention is the inert-axis case — the
    # output map ignores the kv step, a legal revisit, not a race.
    handwritten = (
        ("flash_attention", flash_attention_model(2, 4, 2, 512, 128)),
        ("ssd_scan", ssd_scan_model(2, 4, 512, 64, 128)),
    )
    for hname, model in handwritten:
        gres = check_grid(model)
        report.extend(gres.findings)
        report.grids_checked += gres.grids_checked
        errors = [f for f in gres.findings if f.severity == "error"]
        rows.append({
            "kernel": hname, "rule_set": "handwritten",
            "schedules_certified": 0,
            "grids_checked": gres.grids_checked,
            "findings": len(gres.findings), "errors": len(errors),
        })
        for f in errors:
            print(f"  {hname}: {f}", file=sys.stderr)
    out = report.summary()
    out["rows"] = rows
    out["kernels"] = list(kernels)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", nargs="*", default=None,
                    help="subset of tile kernels (default: all "
                         f"{len(PROGRAMS)})")
    ap.add_argument("--json", default=None,
                    help="write the full summary to this path")
    args = ap.parse_args(argv)
    kernels = args.kernels or list(PROGRAMS)
    unknown = [k for k in kernels if k not in PROGRAMS]
    if unknown:
        ap.error(f"unknown kernels {unknown}; available: {list(PROGRAMS)}")
    summary = sweep(kernels)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(summary, fh, indent=2, sort_keys=True)
    sev = summary["by_severity"]
    print(f"verify_sweep: {len(kernels)} kernels x {len(RULE_SETS)} rule "
          f"sets x {len(SCHEDULE_MODES)} schedules")
    print(f"  rules_checked={summary['rules_checked']} "
          f"schedules_certified={summary['schedules_certified']} "
          f"egraphs={summary['egraphs_checked']} "
          f"sources={summary['sources_checked']} "
          f"grids={summary['grids_checked']}")
    print(f"  findings: {sev['error']} error / {sev['warning']} warning "
          f"/ {sev['info']} info")
    if not summary["ok"]:
        print("FAIL: error-severity findings present", file=sys.stderr)
        return 1
    print("OK: zero error-severity findings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
