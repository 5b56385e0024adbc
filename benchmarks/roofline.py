"""Roofline analysis harness (deliverable g).

Derives the three roofline terms per (arch x shape) on the single-pod
16x16 mesh (TPU v5e constants) from *compiled* dry-run artifacts:

    compute_s    = HLO_FLOPs / (chips x 197e12)
    memory_s     = HLO_bytes / (chips x 819e9)
    collective_s = collective_bytes / (chips x 50e9)

METHOD NOTE (nested-scan correction): XLA's cost_analysis counts every
while-loop body exactly ONCE (verified empirically — see EXPERIMENTS.md
§Roofline/method), so scanned-layer programs under-report. We therefore
lower each program at two reduced depths d1 = split+u and d2 = split+2u
(u = the server stack's repeating-unit length) with scan_layers=False and
microbatches=1, fit cost(n) = a + b*n, and extrapolate to the full depth —
exact for homogeneous server stacks since the real config is the same tower
plus (N-split)/u more units. Archs with <= 24 layers are lowered at full
depth directly. Memory numbers come from the production (scanned) lowering
in §Dry-run, which is how the model would actually deploy.

Run:  PYTHONPATH=src python -m benchmarks.roofline --arch gemma3-12b --shape train_4k
      PYTHONPATH=src python -m benchmarks.roofline --all --json roofline.json

NOTE: spawns dry-run subprocesses (each needs its own 512-device jax init).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Optional

CHIPS = 256
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# the child runs on host devices only: an attached chip belongs to the
# process that drives it
SNIPPET = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
from repro.launch.dryrun import lower_program
r = lower_program({arch!r}, {shape!r}, multi_pod=False,
                  overrides=json.loads({ov!r}), verbose=False)
print("::REPORT::" + json.dumps(r))
"""


def _lower_subprocess(arch: str, shape: str, overrides: dict, timeout=900) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    code = SNIPPET.format(arch=arch, shape=shape, ov=json.dumps(overrides))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("::REPORT::"):
            return json.loads(line[len("::REPORT::"):])
    raise RuntimeError(
        f"dry-run subprocess failed for {arch}x{shape}: {out.stderr[-2000:]}")


def _unit_and_depths(cfg):
    """Server-stack repeating unit and the two probe depths."""
    from repro.models.stacks import segment_layers

    kinds = cfg.layer_kinds
    split = cfg.split_layers
    segs = segment_layers(kinds[split:])
    u = len(segs[0][0]) if segs else 1
    d1, d2 = split + u, split + 2 * u
    return u, d1, d2


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train / 2·N·D forward (N = active params,
    D = processed tokens). Decode: D = batch (one token each)."""
    n_active = cfg.param_count(active_only=True) if cfg.num_experts else cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: 1 token per request


def roofline_terms(arch: str, shape_name: str, overrides: Optional[dict] = None,
                   verbose: bool = True) -> dict:
    from repro.configs import INPUT_SHAPES, get_config

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_updates(**overrides)
    shape = INPUT_SHAPES[shape_name]
    base_ov = dict(overrides or {})
    base_ov.update({"scan_layers": False, "microbatches": 1})

    u, d1, d2 = _unit_and_depths(cfg)
    N = cfg.num_layers
    if N <= 24:
        r = _lower_subprocess(arch, shape_name, base_ov)
        if r["status"] != "OK":
            return {"arch": arch, "shape": shape_name, **r}
        flops, byts, coll = r["flops"], r["bytes_accessed"], r["collective_bytes"]
        reports = [r]
    else:
        r1 = _lower_subprocess(arch, shape_name, {**base_ov, "num_layers": d1})
        if r1["status"] != "OK":
            return {"arch": arch, "shape": shape_name, **r1}
        r2 = _lower_subprocess(arch, shape_name, {**base_ov, "num_layers": d2})
        n_units = (N - d1) / u

        def extrap(k):
            slope = (r2[k] - r1[k]) / 1.0  # per extra unit
            return r1[k] + slope * n_units

        flops, byts = extrap("flops"), extrap("bytes_accessed")
        coll = extrap("collective_bytes")
        reports = [r1, r2]

    # cost_analysis flops/bytes are per-device; collective bytes are parsed
    # from the (single-program) HLO = per-device traffic.
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mflops = model_flops(cfg, shape)
    out = {
        "arch": arch,
        "shape": shape_name,
        "status": "OK",
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": byts,
        "collective_bytes_per_device": coll,
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops_total": mflops,
        "useful_flops_ratio": round(mflops / (flops * CHIPS), 3) if flops > 0 else None,
        "probe_depths": [d1, d2] if N > 24 else [N],
        "collectives": reports[-1].get("collectives", {}),
    }
    if verbose:
        print(f"{arch:>22s} x {shape_name:<12s} "
              f"compute={compute_s*1e3:8.2f}ms memory={memory_s*1e3:8.2f}ms "
              f"collective={collective_s*1e3:8.2f}ms -> {out['dominant']:<10s} "
              f"useful={out['useful_flops_ratio']}")
    return out


def main():
    from repro.configs import INPUT_SHAPES
    from repro.launch.dryrun import ASSIGNED

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = (v.lower() == "true") if v.lower() in ("true", "false") else (
            int(v) if v.lstrip("-").isdigit() else v)

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    out = []
    for arch in archs:
        for shape in shapes:
            try:
                out.append(roofline_terms(arch, shape, overrides or None))
            except Exception as e:  # noqa: BLE001
                print(f"{arch} x {shape}: ERROR {e}")
                out.append({"arch": arch, "shape": shape, "status": "ERROR",
                            "error": str(e)[-500:]})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
