"""Benchmark harness: one module per paper table/figure + the serving
sweep. Prints ``name,us_per_call,derived`` CSV rows (one per experiment)
and writes JSON artifacts under ``benchmarks/artifacts/``.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # reduced sizes
    REPRO_FULL=1 PYTHONPATH=src python -m benchmarks.run   # paper scale
    PYTHONPATH=src python -m benchmarks.run table2_ws rre  # subset
    PYTHONPATH=src python -m benchmarks.run --quick    # CI smoke scale
    PYTHONPATH=src python -m benchmarks.run --list     # what can run

After a run, ``python -m benchmarks.report`` renders EXPERIMENTS.md from
the artifacts.
"""

from __future__ import annotations

import sys
import traceback

BENCHES = [
    ("table2_ws", "benchmarks.bench_table2_ws"),          # deterministic, fast
    ("table1_sim", "benchmarks.bench_table1_sim"),
    ("table3_noshare", "benchmarks.bench_table3_noshare"),
    ("j2_bounds", "benchmarks.bench_j2_bounds"),
    ("fig2_ripple", "benchmarks.bench_fig2_ripple"),      # also covers Table V
    ("rre", "benchmarks.bench_rre"),
    ("slru", "benchmarks.bench_slru"),
    ("simthroughput", "benchmarks.bench_simthroughput"),  # engine speedup
    ("large_n_smoke", "benchmarks.large_n_smoke"),        # streaming + RSS guard
    ("admission", "benchmarks.bench_admission"),
    ("cluster", "benchmarks.bench_cluster"),              # K x failure-rate sweep
    ("serving", "benchmarks.bench_serving"),      # tenants x overlap x mix
    ("serving_smoke", "benchmarks.serving_smoke"),
]


def list_available() -> None:
    """Enumerate benchmarks and the scenario presets they run on."""
    from repro.scenario import list_presets

    print("benchmarks (python -m benchmarks.run <name> ...):")
    for name, module in BENCHES:
        print(f"  {name:16s} {module}")
    print("\nscenario presets (repro.scenario.get_preset(name)):")
    for name, desc in list_presets().items():
        print(f"  {name:16s} {desc}")


def main() -> None:
    import importlib

    args = sys.argv[1:]
    if "--list" in args:
        list_available()
        return
    if "--quick" in args:
        args = [a for a in args if a != "--quick"]
        from benchmarks import common

        common.QUICK = True
    selected = set(args)
    known = {name for name, _ in BENCHES}
    unknown = selected - known
    if unknown:
        print(f"unknown benchmark(s): {', '.join(sorted(unknown))}")
        print(f"available: {', '.join(sorted(known))}")
        sys.exit(2)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    outcomes = []  # (name, error-or-None), in run order
    for name, module in BENCHES:
        if selected and name not in selected:
            continue
        print(f"\n===== {name} =====")
        try:
            mod = importlib.import_module(module)
            mod.main()
        except Exception as e:  # keep the harness going; report at the end
            outcomes.append((name, e))
            traceback.print_exc()
            print(f"{name},nan,FAILED:{type(e).__name__}")
        else:
            outcomes.append((name, None))
    # Per-bench summary: one PASS/FAIL line each, so a crashed bench is
    # visible in the log tail and the harness exit code (CI greps both).
    print("\n----- summary -----")
    for name, err in outcomes:
        status = "PASS" if err is None else f"FAIL ({type(err).__name__})"
        print(f"{name:16s} {status}")
    failures = [(n, e) for n, e in outcomes if e is not None]
    if failures:
        print(f"\n{len(failures)}/{len(outcomes)} benchmark(s) failed: "
              + ", ".join(n for n, _ in failures))
        sys.exit(1)
    print(f"\nall {len(outcomes)} selected benchmark(s) passed")


if __name__ == "__main__":
    main()
