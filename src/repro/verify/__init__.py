"""repro.verify — static soundness & legality analysis (PR 7).

Four passes over the saturator's artifacts, each reporting
severity-tagged :class:`Finding`\\ s:

1. **rules** (:mod:`.rules_check`) — structural lint + random/bf16/
   adversarial differential validation that every rewrite rule is an
   actual equality;
2. **egraph** (:mod:`.egraph_check`) — union-find, hashcons/congruence
   closure and analysis-consistency invariants
   (= ``EGraph.check_invariants()``);
3. **schedule** (:mod:`.schedule_check`) — an independent re-derivation
   of RAW/WAR/store-store dependences certifying emitted statement
   orders as legal topological orders (an N-version check against
   ``repro.core.schedule``, not a call into it);
4. **codegen** (:mod:`.codegen_check`) — AST analysis of emitted
   JAX/Pallas sources (bounds, aliasing, use-before-def, dead loads,
   overlap-distance lint);
5. **grid** (:mod:`.grid_check`, PR 9) — symbolic certification of the
   launch configuration itself: BlockSpec index maps are evaluated over
   symbolic grid coordinates (:mod:`repro.analysis.access`) and the
   resulting footprints proven coverage-complete, write-disjoint,
   in-bounds (pad region modeled), and inside the exact VMEM budget.

``SaturatorConfig(verify="cheap"|"full")`` runs 2–4 on every pipeline
product (``"full"`` also re-validates the active rule set and certifies
reconstructed orders for legacy emitters); findings are counted in
``repro.core.telemetry`` and surfaced by ``benchmarks/verify_sweep.py``.
"""
from __future__ import annotations

from typing import Optional

from .codegen_check import check_generated, shapes_of
from .egraph_check import check_egraph
from .findings import (PASS_CODEGEN, PASS_EGRAPH, PASS_GRID, PASS_RULES,
                       PASS_SCHEDULE, SEVERITIES, Finding, VerifyReport)
from .grid_check import (GridCheckResult, check_grid, check_tile_op,
                         flash_attention_model, ssd_scan_model,
                         tile_call_model)
from .rules_check import RuleRecord, RulesCheckResult, verify_rules
from .schedule_check import ScheduleCheckResult, verify_schedule

VERIFY_LEVELS = ("off", "cheap", "full")

__all__ = [
    "Finding", "VerifyReport", "SEVERITIES", "VERIFY_LEVELS",
    "PASS_RULES", "PASS_EGRAPH", "PASS_SCHEDULE", "PASS_CODEGEN",
    "PASS_GRID",
    "verify_rules", "RulesCheckResult", "RuleRecord",
    "check_egraph", "verify_schedule", "ScheduleCheckResult",
    "check_generated", "shapes_of",
    "check_grid", "check_tile_op", "tile_call_model", "GridCheckResult",
    "flash_attention_model", "ssd_scan_model",
    "verify_saturated", "verify_pallas_kernel", "verify_tile_op",
]


def verify_saturated(sk, level: Optional[str] = None) -> VerifyReport:
    """Run the static passes over one pipeline product.

    ``level`` defaults to ``sk.config.verify``. ``"cheap"`` checks the
    e-graph, certifies the schedule actually attached to the generated
    kernel, and lints the emitted source; ``"full"`` additionally
    re-validates the active rule set differentially and reconstructs a
    searchless schedule for legacy (source/bulk) emissions so those
    orders are certified too. Findings are recorded in the process
    telemetry; the report is also attached to ``sk.verify_report`` by
    the pipeline."""
    level = sk.config.verify if level is None else level
    if level not in VERIFY_LEVELS:
        raise ValueError(f"verify level must be one of {VERIFY_LEVELS}, "
                         f"got {level!r}")
    rep = VerifyReport()
    if level == "off":
        return rep

    # pass 2: e-graph invariants (post run_rules / post graft)
    rep.extend(check_egraph(sk.ssa.egraph))
    rep.egraphs_checked += 1

    # pass 3: schedule legality (explicit orders always; at "full",
    # legacy implicit emissions get a searchless reconstruction so the
    # certified order is exactly what a cache entry would replay)
    sched = sk.kernel.schedule
    if sched is None and level == "full":
        from repro.core.pipeline import _schedule_cm
        from repro.core.schedule import compute_schedule
        try:
            sched = compute_schedule(
                sk.ssa, dict(sk.extraction.choice),
                mode=sk.config.schedule_mode,
                cost_model=_schedule_cm(sk.config, sk.ssa.prog,
                                        sk.ssa.egraph),
                move_budget=0)
        except ValueError as e:
            rep.add(Finding(
                PASS_SCHEDULE, "error", "unschedulable",
                f"no legal order could be reconstructed: {e}"))
    if sched is not None:
        scr = verify_schedule(sk.ssa, sk.extraction.choice, sched)
        rep.extend(scr.findings)
        rep.schedules_certified += scr.regions_certified

    # pass 4: emitted-source analysis
    rep.extend(check_generated(sk.kernel.source, shapes_of(sk.ssa.prog),
                               subject=sk.kernel.name))
    rep.sources_checked += 1

    # pass 1 (full only — rule sets don't change per kernel, so cheap
    # runs leave this to verify_sweep / the test suite)
    if level == "full":
        rres = verify_rules(sk.config.rules())
        rep.extend(rres.findings)
        rep.rules_checked += rres.rules_checked

    from repro.core.telemetry import telemetry
    telemetry().record_verify(rep)
    return rep


def verify_pallas_kernel(pk, ssa) -> VerifyReport:
    """Certify one emitted :class:`PallasKernel` (PR 8): lint its
    kernel source with the :mod:`.codegen_check` pass."""
    rep = VerifyReport()
    rep.extend(check_generated(pk.source, shapes_of(ssa.prog),
                               subject=pk.name))
    rep.sources_checked += 1
    from repro.core.telemetry import telemetry
    telemetry().record_verify(rep)
    return rep


def verify_tile_op(op, rows: Optional[int] = None,
                   chip=None) -> VerifyReport:
    """Certify one :class:`~repro.core.pallasgen.TileOp`'s launch plan
    (PR 9): the grid pass over exactly the :func:`plan_tile_call` plan
    the op executes — coverage, write disjointness, bounds with the pad
    region modeled, and the exact VMEM fit against the scoped limit.
    Wired into ``make_tile_op`` for every ``verify`` level
    above ``"off"``; findings land in the process telemetry like every
    other pass."""
    kwargs = {} if chip is None else {"chip": chip}
    res = check_tile_op(op, rows=rows, **kwargs)
    rep = VerifyReport()
    rep.extend(res.findings)
    rep.grids_checked += res.grids_checked
    from repro.core.telemetry import telemetry
    telemetry().record_verify(rep)
    return rep
