"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes full JSON to
experiments/out/bench/ (gitignored — benchmark outputs never get
committed by accident). Tables:
  ablation          — Fig. 2 / Fig. 4 (CSE / CSE+SAT / CSE+BULK / ACCSAT)
  breakdown         — Table IV (per-kernel instruction/load/FMA deltas)
  saturation_stats  — §VII pipeline timing statistics
  rule_ablation     — §V-A validation (restricted vs extended rule sets)
  measure           — measured per-instance kernel times (the calibration
                      harness, benchmarks/measure.py) vs the roofline
                      model's predictions
(The Tables II/III inventory — suite × sizes — is the kernel_suite itself;
the dry-run roofline table lives in experiments/dryrun/.)

Runs as ``python -m benchmarks.run`` or ``python benchmarks/run.py``.
"""
import json
import pathlib
import sys

if __package__ in (None, ""):        # direct script invocation
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from benchmarks.bootstrap import OUT_ROOT, die_with_import_help

OUT = OUT_ROOT / "bench"


def main() -> None:
    OUT.mkdir(parents=True, exist_ok=True)
    try:
        from benchmarks.ablation import run_ablation
        from benchmarks.breakdown import run_breakdown
        from benchmarks.saturation_stats import run_saturation_stats
        from benchmarks.measure import measure_all
    except ImportError as e:
        die_with_import_help(e)

    print("name,us_per_call,derived")

    abl = run_ablation(n=64 * 64)
    (OUT / "ablation.json").write_text(json.dumps(abl, indent=1))
    for kernel, modes in abl.items():
        for mode, r in modes.items():
            print(f"ablation/{kernel}/{mode},{r['us_per_thread']:.4f},"
                  f"speedup={r['speedup_wall']:.3f};cost={r['dag_cost']:.0f};"
                  f"ops={r['n_ops']};loads={r['n_loads']};fma={r['n_fma']}")

    brk = run_breakdown()
    (OUT / "breakdown.json").write_text(json.dumps(brk, indent=1))
    for row in brk:
        print(f"breakdown/{row['kernel']},0,"
              f"ops_delta={row['ops_delta_pct']:.1f}%;"
              f"loads_saved={row['loads_saved_pct']:.1f}%;"
              f"fma={row['fma_formed']};"
              f"tpu_cost_red={row['tpu_cost_reduction_pct']:.1f}%")

    from benchmarks.rule_ablation import run_rule_ablation
    ra = run_rule_ablation()
    (OUT / "rule_ablation.json").write_text(json.dumps(ra, indent=1))
    for row in ra:
        pk, ek = row["paper"], row["extended"]
        print(f"rule_ablation/{row['kernel']},{pk['sat_s']*1e6:.0f},"
              f"paper_nodes={pk['e_nodes']};ext_nodes={ek['e_nodes']};"
              f"paper_cost={pk['dag_cost']:.0f};ext_cost={ek['dag_cost']:.0f};"
              f"ext_sat_slowdown={ek['sat_s']/max(pk['sat_s'],1e-6):.1f}x")

    sat = run_saturation_stats()
    (OUT / "saturation_stats.json").write_text(json.dumps(sat, indent=1))
    print(f"saturation_stats/ssa_codegen,"
          f"{sat['ssa_codegen_ms_mean']*1e3:.1f},"
          f"mean_ms={sat['ssa_codegen_ms_mean']:.2f};"
          f"stdev={sat['ssa_codegen_ms_stdev']:.2f};"
          f"paper_mean_ms=91.8")
    print(f"saturation_stats/saturation,"
          f"{sat['saturation_s_mean']*1e6:.1f},"
          f"mean_s={sat['saturation_s_mean']:.4f};"
          f"stdev={sat['saturation_s_stdev']:.4f};paper_mean_s=0.63")

    mea = measure_all()
    (OUT / "measure.json").write_text(json.dumps(mea, indent=1))
    for row in mea["rows"]:
        print(f"measure/{row['kernel']},{row['measured_ns']/1e3:.3f},"
              f"kind={row['measured_kind']};"
              f"predicted_ns={row['predicted_ns']:.1f}")


if __name__ == '__main__':
    main()
