"""Massive-M scaling benchmark: client-axis sharding + scan-over-clients.

Sweeps the client count M on a FORCED 8-device host-CPU mesh (the sweep
runs in a child process with ``--xla_force_host_platform_device_count=8``
so the parent's already-initialized JAX backend cannot pin the device
count) and reports, per M:

  dense    the classic single-device jitted round (core/algorithms.
           jit_round_fn) — trace+compile is paid PER M because the round's
           shapes carry the full [M, ...] client axis;
  scan     the host-driven chunked round (core/scan_round.py) — three
           jitted kernels shaped [chunk, ...], so every M at a fixed chunk
           reuses the same executables and trace+compile stays FLAT;
  sharded  the GSPMD round (core/algorithms.shard_round_fn) on a
           ``data=8`` mesh with the client axis of state/batch/schedule
           sharded over devices.

Each cell reports first-call seconds (trace+compile+run), steady-state
rounds/s, and the process peak RSS high-water mark (monotone across the
sweep — read deltas between consecutive cells, not absolutes).

Claims (JSON ``claims``, asserted by tests/test_benchmarks_smoke.py):

  compile_reuse   after the whole sweep the scan kernels' jit caches hold
                  exactly ONE compiled shape each
                  (core/scan_round.scan_round_compile_counts);
  compile_flat    the scan cell's trace+compile component (first-call
                  minus one steady round) does not grow with M — later Ms
                  stay under max(0.6 x first M, 0.25 s), the floor
                  covering warm persistent-cache runs where even the
                  first M compiles in milliseconds;
  sharded_speedup rounds/s of the ``data=8`` sharded round beats the
                  1-device dense round at the largest M both ran. Only
                  evaluated when ``os.cpu_count() >= 4``: on a
                  single-core host the 8 forced devices share one core,
                  so the comparison measures nothing — recorded as null
                  with a note (CI's multi-device job evaluates it).

    PYTHONPATH=src python -m benchmarks.scaling --quick
    PYTHONPATH=src python -m benchmarks.scaling --json BENCH_scaling.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

CHUNK = 8  # per-device client block; divides every swept M
QUICK_MS = (8, 32, 128)
FULL_MS = (8, 32, 128, 512, 2048, 4096)
# dense/sharded pay whole-[M] compiles and O(M) device memory per program;
# past this the scan round is the only cell worth the wall-clock
DENSE_MAX_M = 512


def _sweep(ms, quick: bool) -> dict:
    """Child-process body: the actual measurements (8 forced devices)."""
    import time

    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.algorithms import (
        HParams,
        get_algorithm,
        jit_round_fn,
        place_algorithm_state,
        shard_round_fn,
    )
    from repro.core.scan_round import (
        build_mtsl_scan_round,
        scan_round_compile_counts,
    )
    from repro.core.schedule import full_schedule
    from repro.data.synthetic import MultiTaskImageSource
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models import build_model
    from repro.utils.jit_cache import enable_compilation_cache
    from repro.utils.sharding import client_sharding

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        enable_compilation_cache()

    # ONE model for the whole sweep: M enters only through state/batch
    # shapes, so the scan kernels' (model, chunk, opt) cache key is stable
    # across M — the compile_reuse claim depends on this.
    cfg = get_config("paper-mlp", smoke=True)
    model = build_model(cfg)
    hp = HParams(lr=0.1, local_steps=1)
    alg = get_algorithm("mtsl")
    b = 8  # per-client batch width (a jit key for the scan kernels)
    steady_rounds = 3 if quick else 6
    mesh = make_mesh_from_spec("data=8")
    cshard = client_sharding(mesh)

    def peak_rss_mb():
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    def make_batch(M):
        # num_tasks decouples the client count from the 10-class head;
        # vectorized=True is the batched across-clients RNG path — one
        # inverse-CDF label draw + one normal draw for ALL M clients
        src = MultiTaskImageSource(
            num_classes=cfg.num_classes, image_size=cfg.image_size,
            channels=cfg.image_channels, alpha=0.0, seed=0, num_tasks=M)
        x, y = src.all_tasks_batch(
            np.random.default_rng(0), b, vectorized=True)
        return {"image": jnp.asarray(x),
                "label": jnp.asarray(y, jnp.int32)}

    def time_cell(round_fn, state, batch, sched):
        t0 = time.perf_counter()
        state, metrics = round_fn(state, batch, sched)
        jax.block_until_ready((state, metrics))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steady_rounds):
            state, metrics = round_fn(state, batch, sched)
        jax.block_until_ready((state, metrics))
        steady_s = (time.perf_counter() - t0) / steady_rounds
        return {"first_call_s": first_s, "steady_s_per_round": steady_s,
                "rounds_per_s": 1.0 / steady_s if steady_s > 0 else None,
                "trace_compile_s": max(0.0, first_s - steady_s),
                "peak_rss_mb": peak_rss_mb()}

    results = []
    for M in ms:
        batch = make_batch(M)
        sched = full_schedule(M, alg.steps_per_round(hp))
        row = {"M": M}
        if M <= DENSE_MAX_M:
            state = alg.init_state(model, jax.random.PRNGKey(0), M, hp)
            row["dense"] = time_cell(
                jit_round_fn(alg, model, M, hp), state, batch, sched)
        state = alg.init_state(model, jax.random.PRNGKey(0), M, hp)
        row["scan"] = time_cell(
            build_mtsl_scan_round(model, M, hp, chunk=CHUNK),
            state, batch, None)
        if M <= DENSE_MAX_M:
            state = place_algorithm_state(
                alg, alg.init_state(model, jax.random.PRNGKey(0), M, hp),
                mesh)
            sbatch = jax.device_put(batch, cshard)
            row["sharded"] = time_cell(
                shard_round_fn(alg, model, M, hp, mesh=mesh),
                state, sbatch, sched)
        results.append(row)
        print(f"scaling: M={M} done "
              f"(scan first={row['scan']['first_call_s']:.2f}s "
              f"steady={row['scan']['steady_s_per_round']*1e3:.1f}ms)",
              file=sys.stderr)

    cache = scan_round_compile_counts(model, CHUNK, lr=hp.lr)
    compile_reuse = all(v == 1 for v in cache.values())
    scan_tc = [r["scan"]["trace_compile_s"] for r in results]
    compile_flat = (len(scan_tc) < 2
                    or max(scan_tc[1:]) <= max(0.6 * scan_tc[0], 0.25))
    speedup = None
    note = None
    if (os.cpu_count() or 1) >= 4:
        both = [r for r in results if "dense" in r and "sharded" in r]
        if both:
            r = both[-1]
            speedup = (r["sharded"]["rounds_per_s"]
                       / r["dense"]["rounds_per_s"])
    else:
        note = ("single-core host: the 8 forced devices share one core, "
                "so sharded-vs-dense throughput measures nothing here; "
                "evaluated on the multi-core CI multidevice job")
    return {
        "benchmark": "scaling",
        "quick": quick,
        "chunk": CHUNK,
        "batch_per_client": b,
        "devices": len(jax.devices()),
        "cpu_count": os.cpu_count(),
        "results": results,
        "kernel_cache": cache,
        "claims": {
            "compile_reuse": compile_reuse,
            "compile_flat": compile_flat,
            "sharded_speedup": speedup,
        },
        "notes": {"sharded_speedup": note} if note else {},
    }


def run(quick: bool = False, json_path: str | None = None):
    """Uniform suite entry point: spawn the 8-device child, collect its
    JSON, emit (name, us_per_call, derived) rows for benchmarks/run.py."""
    from benchmarks.common import dump_rows_json

    ms = QUICK_MS if quick else FULL_MS
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo, "src"), repo,
                    env.get("PYTHONPATH", "")) if p)
    with tempfile.TemporaryDirectory() as td:
        out_file = os.path.join(td, "scaling.json")
        cmd = [sys.executable, "-m", "benchmarks.scaling", "--child",
               "--out", out_file, "--ms", ",".join(map(str, ms))]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, env=env, cwd=repo,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"scaling child failed:\n{proc.stdout}\n{proc.stderr}")
        with open(out_file) as f:
            out = json.load(f)

    rows = []
    for r in out["results"]:
        for cell in ("dense", "scan", "sharded"):
            if cell not in r:
                continue
            c = r[cell]
            rows.append((
                f"scaling/M{r['M']}/{cell}",
                c["steady_s_per_round"] * 1e6,
                f"rps={c['rounds_per_s']:.2f};"
                f"first_s={c['first_call_s']:.3f};"
                f"compile_s={c['trace_compile_s']:.3f};"
                f"rss_mb={c['peak_rss_mb']:.0f}",
            ))
    claims = out["claims"]
    rows.append(("scaling/compile_reuse", 0.0,
                 "PASS" if claims["compile_reuse"]
                 else f"FAIL:cache={out['kernel_cache']}"))
    rows.append(("scaling/compile_flat", 0.0,
                 "PASS" if claims["compile_flat"] else "FAIL"))
    if claims["sharded_speedup"] is None:
        rows.append(("scaling/sharded_speedup", 0.0, "note:cpu<4"))
    else:
        # recorded, not hard-failed below 1.0: like throughput's prefetch
        # claim, shared-core CI machines can flip marginal wins
        rows.append(("scaling/sharded_speedup", 0.0,
                     f"x{claims['sharded_speedup']:.2f}"))
    dump_rows_json(json_path, "scaling", quick, rows,
                   extra={"results": out["results"],
                          "claims": claims,
                          "kernel_cache": out["kernel_cache"],
                          "chunk": out["chunk"],
                          "devices": out["devices"],
                          "cpu_count": out["cpu_count"],
                          "notes": out["notes"]})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced M sweep (8..128)")
    ap.add_argument("--json", default="BENCH_scaling.json",
                    help="JSON artifact path (uniform BENCH_* default)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--ms", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        out = _sweep(tuple(int(m) for m in args.ms.split(",")), args.quick)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        return
    for r in run(quick=args.quick, json_path=args.json):
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
