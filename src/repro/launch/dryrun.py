"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination on placeholder devices; print memory_analysis (proves it
fits) and cost_analysis (roofline terms).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --json out.json
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init, and the production mesh needs 512 placeholder host
# devices. JAX_PLATFORMS=cpu keeps a dry run off any attached accelerator,
# which belongs to the one process that drives it.
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config, list_configs  # noqa: E402
from repro.core.mtsl import TrainState, build_train_step  # noqa: E402
from repro.launch.mesh import make_production_mesh, num_clients_for  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim import adamw, sgd  # noqa: E402
from repro.optim.per_component import ComponentLR  # noqa: E402
from repro.serve.engine import build_decode_step, build_prefill_step  # noqa: E402
from repro.utils import hlo  # noqa: E402
from repro.utils import tree as tu  # noqa: E402
from repro.utils.sharding import tree_shardings  # noqa: E402

ASSIGNED = [
    "gemma3-12b",
    "llama-3.2-vision-11b",
    "deepseek-7b",
    "mamba2-130m",
    "deepseek-moe-16b",
    "qwen3-moe-30b-a3b",
    "whisper-tiny",
    "mistral-large-123b",
    "zamba2-7b",
    "mistral-nemo-12b",
]


def _fsdp_rules(cfg):
    return {"embed": ("data",)} if cfg.fsdp else None


def _sds_bf16(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        if jnp.issubdtype(l.dtype, jnp.floating)
        else l,
        tree,
    )


def lower_program(arch: str, shape_name: str, *, multi_pod: bool = False,
                  algorithm: str = "mtsl", overrides: Optional[dict] = None,
                  verbose: bool = True, top_collectives: int = 0):
    """Lower+compile one (arch, shape, mesh). Returns a report dict."""
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_updates(**overrides)
    if shape.kind == "decode" and shape.seq_len > 131_072 and not specs.long_context_supported(cfg):
        return {"arch": arch, "shape": shape_name, "status": "SKIPPED",
                "reason": "full-attention arch; no sub-quadratic variant (DESIGN.md §6)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    M, b = specs.clients_for(shape, mesh)
    rules = _fsdp_rules(cfg)
    t0 = time.time()

    params_sds, params_axes = specs.abstract_mtsl_params(model, M)
    in_sds, in_axes = specs.input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        opt = adamw(1e-4) if cfg.family not in ("mlp", "resnet") else sgd(0.05)
        step_fn = build_train_step(model, opt, M, algorithm,
                                   microbatches=cfg.microbatches)
        opt_sds, opt_axes = specs.abstract_opt_state(opt, params_sds, params_axes)
        state_sds = TrainState(params_sds, opt_sds,
                               jax.ShapeDtypeStruct((), jnp.int32))
        clr_sds = ComponentLR(
            server=jax.ShapeDtypeStruct((), jnp.float32),
            clients=jax.ShapeDtypeStruct((M,), jnp.float32),
        )
        with mesh:
            state_sh = TrainState(
                tree_shardings(mesh, params_sds, params_axes, rules),
                tree_shardings(mesh, opt_sds, opt_axes, rules),
                NamedSharding(mesh, P()),
            )
            batch_sh = tree_shardings(mesh, in_sds, in_axes, rules)
            clr_sh = ComponentLR(NamedSharding(mesh, P()), NamedSharding(mesh, P()))
            lowered = jax.jit(
                step_fn, in_shardings=(state_sh, batch_sh, clr_sh)
            ).lower(state_sds, in_sds, clr_sds)
    elif shape.kind == "prefill":
        params_sds = _sds_bf16(params_sds)
        prefill = build_prefill_step(model, M, max_len=shape.seq_len)
        with mesh:
            p_sh = tree_shardings(mesh, params_sds, params_axes, rules)
            in_sh = tree_shardings(mesh, in_sds, in_axes, rules)
            lowered = jax.jit(prefill, in_shardings=(p_sh, in_sh)).lower(
                {"towers": params_sds["towers"], "server": params_sds["server"]},
                in_sds,
            )
    else:  # decode
        params_sds = _sds_bf16(params_sds)
        decode = build_decode_step(model, M)
        caches_sds, caches_axes = specs.abstract_caches(model, shape, mesh)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        with mesh:
            p_sh = tree_shardings(mesh, params_sds, params_axes, rules)
            c_sh = tree_shardings(mesh, caches_sds, caches_axes, rules)
            tok_sh = tree_shardings(mesh, in_sds, in_axes, rules)["tokens"]
            lowered = jax.jit(
                decode, in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P()))
            ).lower(params_sds, caches_sds, in_sds["tokens"], pos_sds)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo_text = compiled.as_text()
    stats = hlo.collective_bytes(hlo_text)
    top = hlo.top_collectives(hlo_text, top_collectives) if top_collectives else []

    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "algorithm": algorithm if shape.kind == "train" else "-",
        "status": "OK",
        "num_clients": M,
        "batch_per_client": b,
        "flops": float(cost.get("flops", -1)) if cost else -1.0,
        "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1.0,
        "collective_bytes": stats.total_bytes,
        "collectives": {k: [stats.count_by_kind[k], v] for k, v in stats.bytes_by_kind.items()},
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    if top:
        report["top_collectives"] = top
    if mem is not None:
        for attr in ("output_size_in_bytes", "temp_size_in_bytes",
                     "argument_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                report[attr] = int(v)
    if verbose:
        print(f"== {arch} x {shape_name} ({report['mesh']}) : {report['status']}")
        print(f"   clients={M} b={b} lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"   memory_analysis: {mem}")
        print(f"   cost_analysis: flops={report['flops']:.3e} "
              f"bytes={report['bytes_accessed']:.3e}")
        print("   collectives:")
        print(stats.summary())
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--algorithm", default="mtsl",
                    choices=["mtsl", "splitfed", "fedavg"])
    ap.add_argument("--json", default=None, help="write reports to this file")
    ap.add_argument("--set", nargs="*", default=[],
                    help="config overrides key=value (e.g. fsdp=False)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = json.loads(v.lower()) if v.lower() in ("true", "false") else (
            int(v) if v.isdigit() else v)

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    reports = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    r = lower_program(arch, shape, multi_pod=mp,
                                      algorithm=args.algorithm,
                                      overrides=overrides or None)
                except Exception as e:  # noqa: BLE001 — report and continue
                    traceback.print_exc()
                    r = {"arch": arch, "shape": shape,
                         "mesh": "2x16x16" if mp else "16x16",
                         "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                reports.append(r)
    ok = sum(r["status"] == "OK" for r in reports)
    skip = sum(r["status"] == "SKIPPED" for r in reports)
    fail = sum(r["status"] == "FAILED" for r in reports)
    print(f"\n=== dry-run summary: {ok} OK, {skip} SKIPPED, {fail} FAILED "
          f"of {len(reports)}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(reports, f, indent=1)
        print(f"wrote {args.json}")
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
