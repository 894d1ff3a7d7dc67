"""Statically-pruned row-block autotuner for the tile kernels (PR 9).

A tile op sizes its ``row_block`` per call (``pick_row_block``, against
the scoped VMEM limit); this driver searches the block-shape space
around that default — but instead of timing every candidate, it first
runs each through the symbolic grid verifier
(:func:`repro.verify.grid_check.check_tile_kernel_grid`) and **prunes
statically**:

* ``sublane-misaligned`` — ``row_block % 8 != 0`` (the fp32 native tile
  is 8 sublanes; misaligned blocks relayout on every load);
* ``exceeds-rows``       — larger than the tuning geometry's row count
  (``plan_tile_call`` would clamp it to a duplicate of ``rows``);
* any grid-pass **error** (``grid-vmem-overflow`` against the scoped
  VMEM limit, ``grid-oob-read``, ...) — the candidate is illegal, not
  merely slow.

Only the survivors are measured (interleaved round-robin with the
``measure.py`` gc/rotation discipline — every candidate runs the same
op on the same inputs, only the launch grid moves); the winner is the
fastest median. ``--fit`` persists winners into the committed device
profile (``fit["tuned_row_blocks"]``) — ``row_block`` is deliberately
outside the saturation-cache fingerprint (``repro.cache.keys``), so
tuned defaults never invalidate committed cache entries.

The committed ``BENCH_9.json`` records the *invariant* facts only
(candidate/pruned counts, prune reasons, survivor sets, winner shapes —
no wall clocks); ``bench_regression.py`` recomputes the static half and
gates on it. The static report is hash-seed invariant (the plan depends
only on declared program geometry), which ``--static --keep-hashseed``
lets CI check under rotated ``PYTHONHASHSEED``.

Usage:
    python -m benchmarks.tune                  # tune all tile kernels
    python benchmarks/tune.py --smoke          # 2-kernel CI gate
    python benchmarks/tune.py --static         # prune report only, no timing
    python benchmarks/tune.py --update-bench   # refresh BENCH_9.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time

if __package__ in (None, ""):        # direct script invocation
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from benchmarks.bootstrap import OUT_ROOT, ROOT, die_with_import_help
from benchmarks.hashseed import reexec_with_fixed_hashseed

# --keep-hashseed skips the PYTHONHASHSEED=0 pin: the static prune
# report must not depend on hash order (CI runs it under rotated seeds
# and diffs), while timed runs keep the deterministic-extraction pin.
if "--keep-hashseed" not in sys.argv:
    reexec_with_fixed_hashseed()

try:
    import numpy as np
    import jax
except ImportError as e:
    die_with_import_help(e)

from benchmarks.measure import PROFILE_DIR, SMOKE_KERNELS, TILE_KERNELS

TUNE_SCHEMA_VERSION = 1
BENCH9 = ROOT / "BENCH_9.json"
DEFAULT_OUT = OUT_ROOT / "tune.json"

# Geometrically-spaced candidates around the 8..512 autosizing range,
# plus deliberate illegal probes: 4 and 12 are never sublane-aligned,
# 768/1024 overshoot most tuning geometries — the static filter must
# always have something to reject.
CANDIDATE_ROW_BLOCKS = (4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
                        384, 512, 768, 1024)
TUNE_ROWS = 1000     # ragged against every aligned candidate above 8
SMOKE_ROWS = 264     # small CI geometry, still ragged for most blocks


def _op_for(name: str):
    from repro.kernels.tile_programs import get_tile_op
    return get_tile_op(name)


def static_prune(name: str, rows: int = TUNE_ROWS) -> dict:
    """Classify every candidate row block for one kernel — no timing,
    no randomness; the grid verifier is the only legality oracle."""
    from repro.core.pallasgen import (_declared_dtype_bytes,
                                      _declared_feature_dim)
    from repro.verify.grid_check import check_tile_kernel_grid

    if rows % 8:
        raise ValueError(f"tuning rows must be sublane-aligned (multiple "
                         f"of 8), got {rows}")
    op = _op_for(name)
    prog = op.sk.ssa.prog
    default_rb = op.block_rows(_declared_feature_dim(prog) or 256,
                               _declared_dtype_bytes(prog))
    # what the default actually runs at this geometry: plan_tile_call
    # clamps row_block to the row count, so the baseline the winner must
    # beat is the clamped block, not the (possibly larger) autosized one
    eff_default = min(default_rb, rows)
    cands = sorted(set(CANDIDATE_ROW_BLOCKS) | {eff_default})
    entries = []
    for rb in cands:
        entry = {"row_block": rb, "default": rb == eff_default}
        if rb % 8:
            entry.update(status="pruned", reason="sublane-misaligned")
        elif rb > rows:
            entry.update(status="pruned", reason="exceeds-rows")
        else:
            res = check_tile_kernel_grid(op.pk, prog, row_block=rb,
                                         rows=rows)
            errors = [f for f in res.findings if f.severity == "error"]
            if errors:
                entry.update(status="pruned", reason=errors[0].code)
            else:
                entry.update(status="survivor", vmem_bytes=res.vmem_bytes)
        entries.append(entry)
    survivors = [e["row_block"] for e in entries
                 if e["status"] == "survivor"]
    assert eff_default in survivors, \
        f"{name}: autosized default {default_rb} (clamped {eff_default})" \
        f" failed its own legality check — pick_row_block and " \
        f"grid_check disagree"
    reasons: dict = {}
    for e in entries:
        if e["status"] == "pruned":
            reasons[e["reason"]] = reasons.get(e["reason"], 0) + 1
    return {"kernel": name, "rows": rows,
            "d": _declared_feature_dim(prog) or 256,
            "default_row_block": default_rb,
            "effective_default": eff_default,
            "candidates": entries,
            "n_candidates": len(entries),
            "n_pruned": len(entries) - len(survivors),
            "pruned_reasons": dict(sorted(reasons.items())),
            "survivors": survivors}


def _tune_inputs(op, rows: int, d: int):
    """Deterministic operand arrays at the tuning geometry (values in
    [0.1, 1.0) for log/rsqrt/recip domain safety, like measure.py)."""
    from repro.verify.grid_check import tile_input_shapes
    rng = np.random.default_rng(0)
    shapes = tile_input_shapes(op.pk, op.sk.ssa.prog, rows, d)
    args = [jax.numpy.asarray(
        rng.uniform(0.1, 1.0, size=s).astype(np.float32)) for s in shapes]
    scalars = {s: 0.5 for s in op.sk.ssa.prog.scalars}
    return args, scalars


def tune_kernel(name: str, rows: int = TUNE_ROWS, reps: int = 5,
                warmup: int = 2) -> dict:
    """Static prune, then measure the survivors and pick the winner.

    Candidates share one saturated op (``dataclasses.replace`` swaps
    only ``row_block`` — the launch grid, not the kernel body), one
    input set, and the interleaved-rotation/gc timing discipline of
    ``measure.py``, so medians compare cleanly."""
    import gc
    rep = static_prune(name, rows)
    op = _op_for(name)
    args, scalars = _tune_inputs(op, rows, rep["d"])
    ops = {rb: dataclasses.replace(op, row_block=rb)
           for rb in rep["survivors"]}

    def call(o):
        return jax.block_until_ready(o.apply(*args, **scalars))

    for _ in range(warmup):
        for o in ops.values():
            call(o)
    times: dict = {rb: [] for rb in ops}
    order = list(ops)
    gc_was_enabled = gc.isenabled()
    try:
        for r in range(reps):
            gc.collect()
            gc.disable()
            rot = r % len(order)
            for rb in order[rot:] + order[:rot]:
                t0 = time.perf_counter()
                call(ops[rb])
                times[rb].append(time.perf_counter() - t0)
            if gc_was_enabled:
                gc.enable()
    finally:
        if gc_was_enabled:
            gc.enable()
    medians = {rb: statistics.median(ts) * 1e9 for rb, ts in times.items()}
    # fastest median; ties break to the smaller block (deterministic)
    winner = min(medians, key=lambda rb: (medians[rb], rb))
    default = rep["effective_default"]
    rep.update(
        measured_ns={str(rb): medians[rb] for rb in sorted(medians)},
        winner_row_block=winner,
        winner_ns=medians[winner],
        default_ns=medians[default],
        winner_vs_default_pct=(100.0 * (medians[winner]
                                        - medians[default])
                               / medians[default]),
        reps=reps, warmup=warmup)
    return rep


def persist_winners(results, out_dir: pathlib.Path = PROFILE_DIR):
    """Fold the winners into the committed device profile's ``fit``
    section (``tuned_row_blocks``). Safe by construction: ``row_block``
    never enters a cache fingerprint, so default-config cache entries
    keep their keys byte-identical."""
    from repro.analysis import load_profile
    backend = jax.default_backend()
    kind = "pallas_interpret" if backend == "cpu" else "pallas_compiled"
    path = out_dir / f"{backend}_{kind}.json"
    if not path.exists():
        print(f"no device profile at {path}; run "
              "`python benchmarks/measure.py --fit` first — winners "
              "not persisted", file=sys.stderr)
        return None
    prof = load_profile(path)
    tuned = prof.fit.setdefault("tuned_row_blocks", {})
    for r in results:
        tuned[r["kernel"]] = {"row_block": r["winner_row_block"],
                              "rows": r["rows"]}
    prof.save(path)
    return path


def bench9_doc(results) -> dict:
    """The committed, machine-independent view: static facts + winner
    shapes, no wall clocks."""
    kernels = {}
    for r in results:
        kernels[r["kernel"]] = {
            "default_row_block": r["default_row_block"],
            "n_candidates": r["n_candidates"],
            "n_pruned": r["n_pruned"],
            "pruned_reasons": r["pruned_reasons"],
            "survivors": r["survivors"],
            "winner_row_block": r.get("winner_row_block"),
        }
    return {"schema_version": TUNE_SCHEMA_VERSION, "pr": 9,
            "rows": results[0]["rows"] if results else TUNE_ROWS,
            "description": "statically-pruned row-block tuning summary "
                           "(invariants only — see benchmarks/tune.py "
                           "and docs/verification.md)",
            "kernels": kernels}


def smoke() -> int:
    """CI gate: 2 kernels at the small geometry — every kernel must
    prune statically, the winner must be a legal survivor, and the
    winner can never be slower than the default (it is the argmin over
    a set containing the default)."""
    results = []
    for k in SMOKE_KERNELS:
        r = tune_kernel(k, rows=SMOKE_ROWS, reps=3, warmup=1)
        assert r["n_pruned"] >= 1, f"{k}: nothing statically pruned"
        assert r["winner_row_block"] in r["survivors"]
        assert r["winner_row_block"] % 8 == 0
        assert r["winner_ns"] <= r["default_ns"], \
            f"{k}: winner slower than default?!"
        results.append(r)
        print(f"  {k:16s} default {r['effective_default']:4d} -> winner "
              f"{r['winner_row_block']:4d}  ({r['n_pruned']} pruned / "
              f"{r['n_candidates']} candidates, "
              f"{r['winner_vs_default_pct']:+.1f}% vs default)")
    avg = sum(r["n_pruned"] for r in results) / len(results)
    print(f"tune smoke OK: {len(results)} kernels, "
          f"avg {avg:.1f} candidates pruned statically")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", help="comma-separated subset")
    ap.add_argument("--rows", type=int, default=TUNE_ROWS,
                    help=f"tuning row count (default {TUNE_ROWS})")
    ap.add_argument("--reps", type=int, default=5,
                    help="median-of-N timing repeats (default 5)")
    ap.add_argument("--static", action="store_true",
                    help="static prune report only — no timing, no "
                         "randomness; deterministic across hash seeds")
    ap.add_argument("--keep-hashseed", action="store_true",
                    help="don't re-exec with PYTHONHASHSEED=0 (the "
                         "static report must not need the pin)")
    ap.add_argument("--smoke", action="store_true",
                    help="2-kernel CI gate at the small geometry")
    ap.add_argument("--fit", action="store_true",
                    help="persist winners into the committed device "
                         f"profile under {PROFILE_DIR}")
    ap.add_argument("--update-bench", action="store_true",
                    help=f"write the invariant summary to {BENCH9}")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT,
                    help="full tuning report JSON path")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    kernels = (args.kernels.split(",") if args.kernels
               else list(TILE_KERNELS))
    unknown = [k for k in kernels if k not in TILE_KERNELS]
    if unknown:
        ap.error(f"unknown kernels {unknown}; "
                 f"available: {list(TILE_KERNELS)}")
    results = []
    for name in kernels:
        if args.static:
            r = static_prune(name, rows=args.rows)
        else:
            r = tune_kernel(name, rows=args.rows, reps=args.reps)
        results.append(r)
        win = (f" -> winner {r['winner_row_block']:4d} "
               f"({r['winner_vs_default_pct']:+.1f}% vs default)"
               if "winner_row_block" in r else "")
        print(f"  {name:16s} default {r['default_row_block']:4d}  "
              f"{r['n_pruned']}/{r['n_candidates']} pruned "
              f"{r['pruned_reasons']}{win}")
    avg = sum(r["n_pruned"] for r in results) / max(len(results), 1)
    print(f"tune: {len(results)} kernels, avg {avg:.1f} candidates "
          f"pruned statically per kernel"
          + (" (static only — nothing measured)" if args.static else ""))
    if args.static:
        # canonical JSON on stdout-adjacent file for determinism diffs
        doc = {"schema_version": TUNE_SCHEMA_VERSION, "static": True,
               "rows": args.rows, "results": results}
    else:
        doc = {"schema_version": TUNE_SCHEMA_VERSION, "static": False,
               "rows": args.rows, "results": results}
        if args.fit:
            path = persist_winners(results)
            if path is not None:
                print(f"persisted winners into {path}")
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    print(f"wrote {args.out}")
    if args.update_bench:
        BENCH9.write_text(json.dumps(bench9_doc(results), indent=1,
                                     sort_keys=True) + "\n")
        print(f"wrote {BENCH9}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
