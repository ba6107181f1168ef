"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  variance        — Fig. 1b  output-norm variance theory vs simulation
  flops_table     — Table 5  sparse vs dense training/inference FLOPs
  condensed_bench — Fig. 4   condensed vs dense/unstructured/structured layer
  ablation_bench  — Fig. 3b  active-neuron fraction, RigL vs SRigL
  serve_paths     — Fig. 6/7 masked vs condensed vs structured decode tok/s
  kernel_autotune — tuned-vs-default kernel blocks + calibrated crossover
  accuracy        — Tables 1-3 proxy: method ordering on a small LM
  gamma_sweep     — Fig. 8   gamma_sal sensitivity

Besides the CSV, the harness writes a combined ``BENCH_summary.json``
(``--out``; empty string disables): ONE row per suite with its status,
row count, headline metric (the first CSV row — each suite leads with its
signature number) and the suite module's own ``SCHEMA_VERSION`` where it
defines one — so the cross-PR perf trajectory is machine-readable from a
single artifact instead of scattered across per-suite files.

Use --quick to cut the training-based benchmarks' budgets; --only <name>.
"""
import argparse
import importlib
import json
import sys
import traceback

SUMMARY_SCHEMA_VERSION = 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_summary.json",
                    help="combined machine-readable summary (one row per "
                         "suite); empty string disables")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    steps = 30 if args.quick else 80
    # (suite name, entry module, runner taking the imported module) — modules
    # import lazily per suite so one broken import SKIPS that suite (with a
    # note) instead of aborting the whole run
    suites = [
        ("variance", "variance",
         lambda m: m.run(n_samples=500 if args.quick else 2000)),
        ("flops_table", "flops_table", lambda m: m.run()),
        ("condensed_bench", "condensed_bench",
         lambda m: m.run(batch=1) + m.run(batch=256)),
        ("serve_paths", "serve_paths",
         lambda m: m.run(batches=(1, 32) if args.quick else (1, 32, 256))),
        ("kernel_autotune", "kernel_autotune", lambda m: m.run(smoke=True)),
        ("ablation_bench", "ablation_bench",
         lambda m: m.run(steps=min(steps, 40))),
        ("accuracy", "accuracy", lambda m: m.run(steps=steps)),
        ("gamma_sweep", "gamma_sweep",
         lambda m: m.run(steps=min(steps, 60))),
    ]

    print("name,us_per_call,derived")
    failures = 0
    skipped = []
    summary_rows = []
    for name, module, fn in suites:
        if args.only and args.only != name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{module}")
        except Exception as e:  # noqa: BLE001 — skip the suite, keep the run
            skipped.append(name)
            print(f"{name},0.0,SKIPPED(import failed: "
                  f"{type(e).__name__}: {str(e)[:120]})")
            summary_rows.append({"suite": name, "status": "skipped",
                                 "n_rows": 0, "schema_version": None,
                                 "headline": None,
                                 "note": f"import failed: {type(e).__name__}"})
            continue
        schema = getattr(mod, "SCHEMA_VERSION", None)
        try:
            rows = list(fn(mod))
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.1f},{derived}")
            head = rows[0] if rows else None
            summary_rows.append({
                "suite": name, "status": "ok", "n_rows": len(rows),
                "schema_version": schema,
                # each suite leads with its signature metric — the headline
                # is that first CSV row, verbatim
                "headline": ({"name": head[0], "us_per_call": round(head[1], 3),
                              "derived": head[2]} if head else None),
            })
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
            summary_rows.append({"suite": name, "status": "failed",
                                 "n_rows": 0, "schema_version": schema,
                                 "headline": None})
    if skipped:
        print(f"# skipped (import failures, not counted as suite failures): "
              f"{', '.join(skipped)}")
    if args.out:
        payload = {"benchmark": "summary",
                   "schema_version": SUMMARY_SCHEMA_VERSION,
                   "quick": bool(args.quick),
                   "only": args.only or None,
                   "suites": summary_rows}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {args.out} ({len(summary_rows)} suite rows)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
