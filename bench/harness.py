"""What every cell shares: finding its files by name, the device check, the
compile counter, the profiler window, the metric readers and the result line.

A cell `<config>.<traffic>` of BENCHMARK.json names a configuration file
`configs/<config>.json`, a traffic file `traffic/<traffic>.json` (whose
`kind` names the runner module `<kind>_cell.py`), and a limits file
`limits/<cell>.json`. The configuration's `family` names its plug-in
`families/<family>.py` (see `train_cell.py` for what it gives), looked up
beside the cell's files first and then among the benchmark's own. Each
metric of BENCHMARK.json is read by `metrics/<metric>.py`, whose
`read(run)` returns a number or None.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with the files it names."""

    def __init__(self, name, spec=None, files=BENCH):
        spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                             f"{sorted(cells)}")
        w = cells[name]
        self.name, self.chips, self.files = name, w["chips"], files
        self.config = load_json(files / "configs" / f"{w['config']}.json")
        self.traffic = load_json(files / "traffic" / f"{w['traffic']}.json")
        self.limits = load_json(files / "limits" / f"{name}.json")["limits"]

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    @functools.cached_property
    def family(self):
        """The configuration's family plug-in module."""
        return family(self.config["family"], self.files)


def family(name, files=BENCH):
    """The plug-in `families/<name>.py`, from `files` or else the
    benchmark's own."""
    for d in (files, BENCH):
        path = d / "families" / f"{name}.py"
        if path.exists():
            return _load(path, "bench_family_" + name)
    raise SystemExit(f"bench: no plug-in families/{name}.py")


def runner(cell):
    """The module that runs cells of the traffic's kind."""
    return importlib.import_module(f"{cell.traffic['kind']}_cell")


def device_info(need_chips):
    """The devices as JAX reports them. Exits nonzero, before any result,
    unless the first device is a TPU and there are enough of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's first device is on "
                         f"platform {devs[0].platform!r}")
    if len(devs) < need_chips:
        raise SystemExit(f"bench: the cell needs {need_chips} chips, JAX "
                         f"has {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": need_chips}


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileCounter:
    """Counts traces and backend compiles (cache hits excluded) while
    `active`; the window must see none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


class Profile:
    """A few seconds of the window under the profiler, and their reduction."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.t0 = self.t1 = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, chips):
        import tracereduce as trace_mod

        files = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        return trace_mod.reduce_file(files[-1], chips)


def _load(path, name):
    """The module in file `path`, run under `name` (dots and dashes made
    underscores); it is not entered in sys.modules."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric):
    return _load(BENCH / "metrics" / f"{metric}.py",
                 "bench_metric_" + metric).read


def read_metrics(metrics, run):
    out = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def finite(x):
    """A number JSON can carry: infinities and NaN become 1e30."""
    x = float(x)
    return x if math.isfinite(x) else 1e30


def emit(correct, attempted, failed, metrics, device, rows, breakdown=None):
    """Print the checks on stderr, then the one result line on stdout."""
    for name, value, limit in rows:
        print(f"check {name}: {finite(value):.6g} (limit {limit:.6g})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": finite(v), "limit": limit}
                      for name, v, limit in rows}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
