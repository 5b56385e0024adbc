"""Readings that set a training cell's limits, on the chip.

    python3 bench/calibrate.py program --workload W --seeds 1,2,3 [--seconds S] [--control]
        the cell's compared numbers over many seeds in one process (the
        lower readings); with --control also the control's, the reference
        computed in the configuration's next lower precision put in the
        program's place (the upper readings)
    python3 bench/calibrate.py fault --workload W --seeds 1,2,3
        a training cell's numbers with half of each client's rows left out
        (planted in the reference put in the program's place)

Like bench/run.py it needs a TPU. Each line it prints is one JSON reading.
"""
import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import check  # noqa: E402
import harness  # noqa: E402


def _args(ns, seed, **kw):
    return argparse.Namespace(workload=ns.workload, seed=seed,
                              seconds=ns.seconds, trace=0, **kw)


def program(ns, cell):
    for seed in ns.seeds:
        t0 = time.perf_counter()
        res = harness.runner(cell).run(cell, _args(ns, seed,
                                                   control=ns.control),
                                       t0, None)
        print(json.dumps({"seed": seed, "numbers": res.numbers,
                          "control": res.control, "memory": res.memory,
                          "seconds": time.perf_counter() - t0}), flush=True)
        print(res.log, file=sys.stderr, flush=True)


def fault(ns, cell):
    import jax

    import faults
    import train_cell

    axes = train_cell.layout(cell)[2]
    mesh = train_cell.cell_mesh(cell, jax.devices()[:cell.chips])
    for seed in ns.seeds:
        inputs = []
        it = iter(train_cell.source(cell, seed))
        for _ in range(cell.traffic["check_rounds"]):
            inputs.append(next(it))
        ref = train_cell.reference_checks(cell, seed, inputs, axes,
                                          mesh=mesh)
        bad = train_cell.reference_checks(cell, seed,
                                          faults.half_batch_inputs(inputs),
                                          axes, mesh=mesh)
        print(json.dumps({"seed": seed, "fault": "half_batch",
                          "numbers": check.train_numbers(bad, ref)[0]}),
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("program", "fault"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ns = ap.parse_args(argv)
    cell = harness.Cell(ns.workload)
    harness.device_info(cell.chips)
    from repro.utils.jit_cache import enable_compilation_cache

    enable_compilation_cache()
    {"program": program, "fault": fault}[ns.mode](ns, cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())
