"""Round-throughput benchmark: synchronous vs. async-pipelined train loop.

Measures steady-state wall-clock per round for the SAME seeded workload
driven through `train/loop.train` twice — once fully synchronous
(`prefetch=0`: the host draws the schedule, synthesizes the batch,
transfers it, and materializes metrics while the device idles) and once
pipelined (`prefetch=2`: train/pipeline.py runs the host work two rounds
ahead on a background thread, double-buffers the host->device transfer,
and defers metric materialization). The two runs are trajectory-identical
(pinned by tests/test_pipeline.py) — only the wall-clock differs, which is
the whole point: the schedule subsystem SIMULATES straggler waste inside
the round, and the pipeline removes the host-side waste AROUND the round.

METHOD NOTE (differential timing): a fresh `train()` call pays trace +
compile + init once, which at toy scale dwarfs the per-round cost. Each
cell therefore (1) warms a process-local persistent compilation cache with
an untimed run, so every timed run's compile is a cache hit; (2) times a
SHORT and a LONG run of the identical config and reports
(T_long - T_short) / (rounds_long - rounds_short) — the remaining fixed
costs (trace, init) cancel in the difference; and (3) repeats the pair and
takes the MEDIAN estimate, squeezing out scheduler noise.

The sweep covers the trivial schedule (control) and a straggler-heavy
heterogeneous schedule (the regime the paper's system story cares about),
for the paper's split algorithm (mtsl — one step per round, so host-side
batch synthesis is a large fraction of the round) and a round-based
baseline (fedavg). Batch sizes are chosen so host generation and device
compute are comparable — the regime where overlap pays.

Reported per cell: steady-state ms/round for each mode and the
sync/pipelined speedup. The JSON claim `prefetch_wins` records whether at
least one straggler-heavy cell shows a measurable (>2%) win — asserted by
the benchmark smoke tests rather than hard-failing here, since CI machines
share cores between the generator thread and XLA.

A separate `data_path` section (same differential-timing method) compares
the two things the prefetch thread can be doing at massive M: per-round
host SYNTHESIS (`MultiTaskImageSource`, the historical path) vs. mmap'd
shard READS from a prebuilt client cache (data/shards.py, `--data cached`
on the launcher). At M=256 synthesis is the background thread's critical
path; cached reads take it off, and the `cached_data_wins` claim records
the resulting end-to-end speedup.

    PYTHONPATH=src python -m benchmarks.throughput            # quick cells
    PYTHONPATH=src python -m benchmarks.throughput --json throughput.json
"""
from __future__ import annotations

import argparse
import json
import time

from repro.configs import get_config
from repro.core.schedule import ScheduleConfig, padded_batch_per_client
from repro.data.pipeline import client_batches
from repro.models import build_model
from repro.optim import sgd
from repro.train.loop import TrainConfig, train

from benchmarks.common import make_source


def _timed_train(model, src, M, *, algorithm, rounds, local_steps,
                 batch_per_client, schedule, prefetch, seed=0):
    from repro.core.algorithms import HParams, get_algorithm

    spr = get_algorithm(algorithm).steps_per_round(
        HParams(local_steps=local_steps))
    per_round = padded_batch_per_client(schedule, batch_per_client) * spr
    tcfg = TrainConfig(
        steps=rounds * spr, algorithm=algorithm, lr=0.1,
        local_steps=local_steps, log_every=1, seed=seed,
        schedule=schedule, prefetch=prefetch,
        batch_per_client=batch_per_client)
    batches = client_batches(src, per_round, steps=rounds, seed=seed,
                             as_numpy=True)
    t0 = time.time()
    _, history = train(model, sgd(0.1), batches, tcfg, M, log=lambda s: None)
    return time.time() - t0, history


def _steady_state_per_round(model, src, M, *, rounds_long, rounds_short=8,
                            reps=2, **kw):
    """Median over `reps` of (T_long - T_short) / (rounds_long -
    rounds_short): trace/init costs are paid by both runs and cancel in the
    difference; compile is a cache hit after the caller's warmup."""
    import statistics

    estimates = []
    history = None
    for _ in range(reps):
        t_short, _ = _timed_train(model, src, M, rounds=rounds_short, **kw)
        t_long, history = _timed_train(model, src, M, rounds=rounds_long, **kw)
        estimates.append((t_long - t_short) / (rounds_long - rounds_short))
    return statistics.median(estimates), history


def _data_path_cell(cfg, quick: bool) -> dict:
    """Cached-vs-synthesized data path at massive M (same method: warm
    compile cache, short/long differential, median of reps). Both runs use
    prefetch=2 — the comparison isolates WHAT the background thread does
    (synthesis vs. mmap'd shard reads), not whether it exists. The two
    trajectories differ by design (the cache draws from its own seeded
    stream), so unlike the prefetch cells there is no trajectory assert."""
    import shutil
    import tempfile

    from repro.data.shards import build_cache, load_cache
    from repro.data.synthetic import MultiTaskImageSource

    M = 256
    examples_per_client = 64
    big = cfg.with_updates(num_clients=M)
    model = build_model(big)
    # noise_sigma keeps synthesis realistically expensive (the same choice
    # as the prefetch cells); num_tasks decouples M from the class count
    src = MultiTaskImageSource(
        num_classes=cfg.num_clients, num_tasks=M, image_size=cfg.image_size,
        channels=cfg.image_channels, alpha=0.0, noise_sigma=0.5, seed=0)
    cache_dir = tempfile.mkdtemp(prefix="repro-bench-cache-")
    try:
        build_cache(cache_dir, src, examples_per_client, seed=0)
        dataset = load_cache(cache_dir)
        rounds = 60 if quick else 150
        kw = dict(algorithm="mtsl", local_steps=1, batch_per_client=4,
                  schedule=ScheduleConfig(), prefetch=2)
        for data in (src, dataset):  # warm the compile cache, untimed
            _timed_train(model, data, M, rounds=2, **kw)
        synth_r, _ = _steady_state_per_round(
            model, src, M, rounds_long=rounds, **kw)
        cached_r, _ = _steady_state_per_round(
            model, dataset, M, rounds_long=rounds, **kw)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    cell = {
        "num_clients": M,
        "examples_per_client": examples_per_client,
        "batch_per_client": 4,
        "rounds": rounds,
        "synthesized_ms_per_round": synth_r * 1e3,
        "cached_ms_per_round": cached_r * 1e3,
        "speedup": synth_r / cached_r if cached_r > 0 else float("inf"),
    }
    print(f"throughput/data_path/M{M}: "
          f"synthesized {synth_r * 1e3:.2f}ms/round  "
          f"cached {cached_r * 1e3:.2f}ms/round  "
          f"speedup x{cell['speedup']:.2f}")
    return cell


def run(quick: bool = True, json_path: str | None = None) -> dict:
    from repro.utils.jit_cache import enable_compilation_cache

    # persistent compile cache: the warmup run populates it, every timed
    # run hits it
    enable_compilation_cache()

    cfg = get_config("paper-mlp", smoke=True)
    model = build_model(cfg)
    M = cfg.num_clients
    # noise_sigma makes batch synthesis realistically expensive (one more
    # host-side normal draw per pixel) — the fig4 robustness regime
    src = make_source(cfg, alpha=0.0, noise_sigma=0.5, seed=0)
    rounds = 80 if quick else 200
    straggle = ScheduleConfig(straggler_frac=0.5, seed=7)
    cells = [
        ("mtsl", 1, 512, ScheduleConfig()),
        ("mtsl", 1, 512, straggle),
        ("fedavg", 4, 128, straggle),
    ]
    results = []
    for algorithm, local_steps, batch_per_client, scfg in cells:
        kw = dict(algorithm=algorithm, local_steps=local_steps,
                  batch_per_client=batch_per_client, schedule=scfg)
        for prefetch in (0, 2):  # warm the compile cache, untimed
            _timed_train(model, src, M, rounds=2, prefetch=prefetch, **kw)
        sync_r, h_sync = _steady_state_per_round(
            model, src, M, rounds_long=rounds, prefetch=0, **kw)
        pipe_r, h_pipe = _steady_state_per_round(
            model, src, M, rounds_long=rounds, prefetch=2, **kw)
        # the two modes must agree on WHAT was computed
        assert [e["loss"] for e in h_sync] == [e["loss"] for e in h_pipe], \
            f"{algorithm}: pipelined trajectory diverged from synchronous"
        results.append({
            "algorithm": algorithm,
            "local_steps": local_steps,
            "batch_per_client": batch_per_client,
            "straggler_frac": scfg.straggler_frac,
            "rounds": rounds,
            "sync_ms_per_round": sync_r * 1e3,
            "pipelined_ms_per_round": pipe_r * 1e3,
            "speedup": sync_r / pipe_r if pipe_r > 0 else float("inf"),
        })
        print(f"throughput/{algorithm}/b{batch_per_client}"
              f"/straggle{scfg.straggler_frac}: "
              f"sync {sync_r * 1e3:.2f}ms/round  "
              f"pipelined {pipe_r * 1e3:.2f}ms/round  "
              f"speedup x{results[-1]['speedup']:.2f}")
    data_path = _data_path_cell(cfg, quick)
    out = {
        "benchmark": "throughput",
        "quick": quick,
        "rounds": rounds,
        "results": results,
        "data_path": data_path,
        "claims": {
            # a measurable (>2%) prefetch win on a straggler-heavy schedule
            "prefetch_wins": any(
                r["speedup"] > 1.02 for r in results
                if r["straggler_frac"] > 0),
            # cached shard reads beat per-round synthesis at massive M
            "cached_data_wins": data_path["speedup"] > 1.02,
        },
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {json_path}")
    return out


def run_suite(quick: bool = False, json_path: str | None = None):
    """benchmarks/run.py adapter: the aggregate runner consumes
    (name, us_per_call, derived) rows, so fold the dict-shaped results into
    that shape (one row per cell plus the prefetch-wins claim)."""
    out = run(quick=quick, json_path=json_path)
    rows = []
    for r in out["results"]:
        rows.append((
            f"throughput/{r['algorithm']}/b{r['batch_per_client']}"
            f"/straggle{r['straggler_frac']}",
            r["pipelined_ms_per_round"] * 1e3,
            f"sync_ms={r['sync_ms_per_round']:.2f} "
            f"pipelined_ms={r['pipelined_ms_per_round']:.2f} "
            f"speedup=x{r['speedup']:.2f}",
        ))
    dp = out["data_path"]
    rows.append((
        f"throughput/data_path/M{dp['num_clients']}",
        dp["cached_ms_per_round"] * 1e3,
        f"synthesized_ms={dp['synthesized_ms_per_round']:.2f} "
        f"cached_ms={dp['cached_ms_per_round']:.2f} "
        f"speedup=x{dp['speedup']:.2f}",
    ))
    # recorded, not hard-failed: CI machines share cores between the
    # generator thread and XLA (see the module docstring's method note)
    rows.append(("throughput/prefetch_wins", 0.0,
                 "PASS" if out["claims"]["prefetch_wins"] else "note:no-win"))
    rows.append(("throughput/cached_data_wins", 0.0,
                 "PASS" if out["claims"]["cached_data_wins"]
                 else "note:no-win"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer runs (steadier numbers)")
    ap.add_argument("--json", default="BENCH_throughput.json",
                    help="JSON artifact path (uniform BENCH_* default)")
    args = ap.parse_args(argv)
    # run() configures the compilation cache itself (CI dir or a local one)
    run(quick=not args.full, json_path=args.json)


if __name__ == "__main__":
    main()
