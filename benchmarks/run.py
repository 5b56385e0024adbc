"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) plus
PASS/FAIL rows for each of the paper's qualitative claims. Exits 1 when a
suite raises or a claim FAILs. Every suite
shares the uniform ``run(quick=..., json_path=...)`` signature; pass
``--json-dir`` to write one JSON artifact per suite next to the CSV
stream.

    PYTHONPATH=src python -m benchmarks.run            # paper suite
    PYTHONPATH=src python -m benchmarks.run --quick    # reduced (CI)
    PYTHONPATH=src python -m benchmarks.run --json-dir out/
    PYTHONPATH=src python -m benchmarks.run --roofline # + §Roofline table
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced configs (smoke models, fewer steps)")
    ap.add_argument("--roofline", action="store_true",
                    help="also run the roofline table (slow: spawns dry-runs)")
    ap.add_argument("--json-dir", default=None,
                    help="write <dir>/<suite>.json per suite (uniform "
                         "--json path for every entry)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table2,table3,fig2,fig3,"
                         "fig4,fig5,ablation_split,throughput,"
                         "time_to_accuracy,scaling,async_rounds,serving_load")
    args = ap.parse_args(argv)

    from benchmarks import (ablation_split_point, async_rounds,
                            fig2_lr_tuning, fig3_training_cost,
                            fig4_robustness, fig5_participation, scaling,
                            serving_load, table2_accuracy, table3_new_client,
                            throughput, time_to_accuracy)
    from benchmarks.common import enable_compilation_cache

    # persistent jit cache (JAX_COMPILATION_CACHE_DIR): the suite retraces
    # the same seven algorithms across figures — compile each once
    enable_compilation_cache()

    suites = {
        "fig2": fig2_lr_tuning.run,
        "table2": table2_accuracy.run,
        "table3": table3_new_client.run,
        "fig3": fig3_training_cost.run,
        "fig4": fig4_robustness.run,
        "fig5": fig5_participation.run,
        "ablation_split": ablation_split_point.run,
        "throughput": throughput.run_suite,
        "time_to_accuracy": time_to_accuracy.run,
        "scaling": scaling.run,
        "async_rounds": async_rounds.run,
        "serving_load": serving_load.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        t0 = time.time()
        json_path = (os.path.join(args.json_dir, f"{name}.json")
                     if args.json_dir else None)
        try:
            rows = fn(quick=args.quick, json_path=json_path)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            failures += 1
            continue
        for r in rows:
            print(",".join(str(x) for x in r))
            if isinstance(r[-1], str) and r[-1].startswith("FAIL"):
                failures += 1
        print(f"{name}/wall,{(time.time() - t0) * 1e6:.0f},s={time.time() - t0:.1f}")
        sys.stdout.flush()

    if args.roofline:
        from benchmarks.roofline import roofline_terms
        from repro.launch.dryrun import ASSIGNED

        for arch in ASSIGNED:
            r = roofline_terms(arch, "train_4k", verbose=False)
            if r.get("status") == "OK":
                print(f"roofline/{arch}/train_4k,0,"
                      f"dominant={r['dominant']};compute_ms={r['compute_s']*1e3:.2f};"
                      f"useful={r['useful_flops_ratio']}")

    print(f"claims_failed,{failures},{'OK' if failures == 0 else 'CHECK'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
