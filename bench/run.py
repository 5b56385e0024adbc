"""Run one benchmark cell once, on the chips of the host it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, limits and metrics are found by name
from BENCHMARK.json (see bench/harness.py). Weights and inputs are made
from --seed. Set-up (imports, weights, compilation or compile-cache loads,
the checked first steps) is timed from process start; then the window
measures for --seconds. With --trace 0 the result carries the cell's
end-to-end metrics, with --trace 1 its per-layer metrics, read from a
profile of the window's first seconds. Exits nonzero, with no result,
unless JAX's first device is a TPU and there are as many as the cell needs.

The last line of standard output is the JSON result; the last lines of
standard error are each compared number beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import check  # noqa: E402
import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def drive(cell, args, device, t_start):
    """Everything after the device check: set-up, window, comparison, and
    the result line. Returns the result's `correct`."""
    profile_dir = harness.ROOT / ".bench" / "trace" / cell.name
    if args.trace:
        shutil.rmtree(profile_dir, ignore_errors=True)
    res = harness.runner(cell).run(cell, args, t_start, profile_dir)
    run = res.run
    print(f"bench: {cell.name} seed {args.seed}: {res.log}", file=sys.stderr)
    print(f"bench: compilations inside the window: {run.compiles}",
          file=sys.stderr)
    metrics = harness.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, run)
    device = dict(device, memory_peak_bytes=res.memory)
    breakdown = None
    if args.trace:
        tr = run.trace
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": [[n, s] for n, s in tr["top_ops"]],
                     "idle_gaps": tr["idle_gaps"]}
        print("bench: trace " + json.dumps(
            {k: tr[k] for k in ("busy_s", "window_s", "collective_s",
                                "programs", "ops")}), file=sys.stderr)
    correct, rows = check.verdict(res.numbers, cell.limits)
    harness.emit(correct, res.attempted, res.failed, metrics, device, rows,
                 breakdown)
    return correct


def main(argv=None):
    args = parse(argv)
    cell = harness.Cell(args.workload)
    device = harness.device_info(cell.chips)
    from repro.utils.jit_cache import enable_compilation_cache

    print(f"bench: device {device['kind']} x{device['count']} "
          f"({device['platform']}); compile cache "
          f"{enable_compilation_cache()}", file=sys.stderr)
    drive(cell, args, device, T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
