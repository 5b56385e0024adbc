"""Family plug-ins, unit axes from the program's template, and the mesh
path, on the CPU at test sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_families.py

The digests were taken from the weights and unit axes that the harness
made before the families moved into plug-ins: the move changes neither.
"""
import argparse
import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import train_cell  # noqa: E402
import weights  # noqa: E402

FIX = BENCH / "tests" / "fixtures"
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
LM, IMAGE = "tiny-mamba2.train-lm", "tiny-resnet.train-image"

WEIGHTS = {
    (LM, 5): "d243cfed54e4639519c311db8ae41702d0fede7b4e6c660f14bf361f448e68c3",
    (LM, 2 ** 33 + 5):
        "0f43c406cb5f6580bbd50fe1ed809397fe8b9a975c4550138321f04b10a36a0b",
    (IMAGE, 5):
        "15871ba52746ed39d2f49a50ff50abfbf7e554a276a59abd022c7a8679813d71",
    (IMAGE, 2 ** 33 + 5):
        "fb1264448cd9b0870411faa320151238d92a2d8fc591563638c97f241ade6131",
}
# (digest of {path: unit axes}, units); the benchmark's cells count the
# 738 and 149 units of PERF.md section 2
AXES = {
    LM: ("c016e1cfaa31d22834f0446765ba3a81af02d5f50dd07b651a0a4f59d27c82aa",
         88),
    IMAGE: ("b29a56994e445a3929083d7d61b991a9d899985f9e5ba7c8c8ca712d84ede126",
            14),
    "mamba2-130m.train.m8-s512":
        ("c016e1cfaa31d22834f0446765ba3a81af02d5f50dd07b651a0a4f59d27c82aa",
         738),
    "cifar-resnet20.train.m10":
        ("3a4164216a386b3d27c816e95c2ae79fad626dc936473e8645fc628d6749f53a",
         149),
}


def _fixture_cell(name):
    spec = json.loads((FIX / "benchmark.json").read_text())
    return harness.Cell(name, spec=spec, files=FIX)


def _cell(name):
    if any(w["name"] == name for w in SPEC["workloads"]):
        return harness.Cell(name, SPEC)
    return _fixture_cell(name)


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _units(axes, shapes):
    return sum(int(np.prod(s.shape[:a])) for s, a in
               zip(jax.tree.leaves(shapes), jax.tree.leaves(axes)))


@pytest.mark.parametrize("name,seed", sorted(WEIGHTS))
def test_weights_are_the_parents(name, seed):
    cell = _fixture_cell(name)
    fam = cell.family
    params = weights.make_params(fam, seed, fam.ref_cfg(cell.config),
                                 cell.traffic["clients"])
    assert _digest(params) == WEIGHTS[name, seed]


@pytest.mark.parametrize("name", sorted(AXES))
def test_unit_axes_are_the_parents(name):
    cell = _cell(name)
    _, tmpl, axes = train_cell.layout(cell)
    by_path = {jax.tree_util.keystr(k): v for k, v in
               jax.tree_util.tree_flatten_with_path(axes)[0]}
    digest = hashlib.sha256(json.dumps(by_path, sort_keys=True).encode())
    from repro.utils.sharding import strip

    assert (digest.hexdigest(), _units(axes, strip(tmpl))) == AXES[name]


def _split_stack():
    """A hybrid stack that models/stacks.py splits: the tower's one mamba
    layer is an unrolled seg0; the server's 12 mamba layers are a stacked
    seg0, its shared-attention layer an unrolled seg1."""
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.models.stacks import segment_layers

    cfg = get_config("zamba2-7b", smoke=True).with_updates(
        num_layers=14, shared_attn_every=14, split_layers=1,
        scan_layers=True)
    kinds = cfg.layer_kinds
    assert segment_layers(kinds[:1]) == [(("mamba",), 1)]
    assert segment_layers(kinds[1:]) == [(("mamba",), 12),
                                         (("shared_attn",), 1)]
    return build_model(cfg)


def _by_path(axes):
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(axes)[0]}


def test_unit_axes_on_a_split_stack():
    """The name rule gave the tower's unrolled seg0 a layer axis and the
    server's unrolled seg1 none; the template gives each leaf the axes it
    has: the client axis in the tower, the layer axis in the server's
    stacked seg0 and nowhere else."""
    from repro.utils.sharding import Annotated

    tmpl = weights.template(_split_stack(), 3)
    axes = _by_path(weights.unit_axes(tmpl))
    names = {jax.tree_util.keystr(k): a.axes for k, a in
             jax.tree_util.tree_flatten_with_path(
                 tmpl, is_leaf=lambda x: isinstance(x, Annotated))[0]}
    tower = {k for k in axes if k.startswith("['towers']")}
    seg0 = {k for k in axes if k.startswith("['server']['blocks']['seg0']")}
    rest = set(axes) - tower - seg0
    assert any("['towers']['blocks']['seg0']" in k for k in tower)
    assert any("seg1" in k for k in rest)
    assert {axes[k] for k in tower} == {1}
    assert {names[k][0] for k in tower} == {"client"}
    assert {axes[k] for k in seg0} == {1}
    assert {names[k][0] for k in seg0} == {"layers"}
    assert {axes[k] for k in rest} == {0}
    assert not any("layers" in names[k] for k in tower | rest)


def test_a_family_may_name_more_unit_axes():
    """A stacked MoE server with the expert axis named a unit axis: each
    expert's slice of a layer's expert weights is a unit; the router, whose
    expert axis is not leading, stays one unit a layer."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("deepseek-moe-16b", smoke=True)
                        .with_updates(num_layers=4, split_layers=1,
                                      scan_layers=True))
    tmpl = weights.template(model, 2)
    plain = _by_path(weights.unit_axes(tmpl))
    experts = _by_path(weights.unit_axes(tmpl, ("experts",)))
    moe = "['server']['blocks']['seg0']['0']['moe']"
    for w in ("wg", "wu", "wd"):
        assert (plain[f"{moe}['{w}']"], experts[f"{moe}['{w}']"]) == (1, 2)
    assert experts[f"{moe}['router']"] == 1
    assert {k for k in plain if plain[k] != experts[k]} == {
        f"{moe}['{w}']" for w in ("wg", "wu", "wd")}


TOY = '''"""Family `mlp` (a test's own): the program's paper-mlp, a stack of
dense layers, the first `split_layers` of them in each client's tower."""
import math

import jax
import jax.numpy as jnp

from weights import F32, normal


def ref_cfg(config):
    return {"dims": config["mlp_dims"], "split_layers": config["split_layers"]}


def program_want(config):
    return {"mlp_dims": tuple(config["mlp_dims"]),
            "image_size": config["image_size"],
            "image_channels": config["image_channels"],
            "num_classes": config["num_classes"]}


def make_params(key, cfg, M):
    dims, split = cfg["dims"], cfg["split_layers"]
    ks = jax.random.split(key, len(dims) - 1)

    def fc(i, lead):
        return {"w": normal(ks[i], lead + (dims[i], dims[i + 1]),
                            1.0 / math.sqrt(dims[i])),
                "b": jnp.zeros(lead + (dims[i + 1],), F32)}

    return {"towers": {f"fc{i}": fc(i, (M,)) for i in range(split)},
            "server": {f"fc{i - split}": fc(i, ())
                       for i in range(split, len(dims) - 1)}}


def train_round_flops(config, traffic):
    d = config["mlp_dims"]
    return (3 * traffic["clients"] * traffic["batch_per_client"]
            * sum(2 * a * b for a, b in zip(d, d[1:])))


def loss_and_grads(params, batch, cfg, cdt=None):
    dims, split = cfg["dims"], cfg["split_layers"]

    def client(tp, sp, b):
        x = b["image"].reshape(b["image"].shape[0], -1)
        for i in range(len(dims) - 1):
            p = tp[f"fc{i}"] if i < split else sp[f"fc{i - split}"]
            x = x @ p["w"] + p["b"]
            if i < len(dims) - 2:
                x = jax.nn.relu(x)
        gold = jnp.take_along_axis(x, b["label"][:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(x, axis=-1) - gold)

    def total(p):
        return jnp.sum(jax.vmap(lambda tp, b: client(tp, p["server"], b))(
            p["towers"], batch))

    return jax.value_and_grad(total)(params)
'''


def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text if isinstance(text, str) else json.dumps(text))


def _copy(src, dst):
    _write(dst, src.read_text())


def test_a_new_family_needs_only_files(tmp_path):
    """A third family, plugged in by files in a directory of its own: no
    module of the harness is edited or told about it."""
    spec = json.loads((FIX / "benchmark.json").read_text())
    name = "tiny-mlp.train-image"
    spec["workloads"].append({"name": name, "config": "tiny-mlp",
                              "traffic": "train-image", "chips": 1,
                              "why": "test"})
    _write(tmp_path / "families" / "mlp.py", TOY)
    _write(tmp_path / "configs" / "tiny-mlp.json", {
        "registry": "paper-mlp", "smoke": True, "family": "mlp",
        "control_dtype": "bfloat16", "source": "test size",
        "mlp_dims": [64, 32, 32, 16, 10], "image_size": 8,
        "image_channels": 1, "num_classes": 10, "split_layers": 2,
        "dtype": "float32", "param_dtype": "float32"})
    _copy(FIX / "traffic" / "train-image.json",
          tmp_path / "traffic" / "train-image.json")
    _write(tmp_path / "limits" / f"{name}.json",
           {"limits": {"loss": 1e-4, "grad": 1e-3, "update": 1e-3}})
    cell = harness.Cell(name, spec, files=tmp_path)
    assert pathlib.Path(cell.family.__file__).parent == tmp_path / "families"
    args = argparse.Namespace(workload=name, seed=2 ** 33 + 3, seconds=0.5,
                              trace=0)
    res = harness.runner(cell).run(cell, args, time.perf_counter(), None)
    ok, rows = check.verdict(res.numbers, cell.limits)
    assert ok, rows
    assert res.attempted > 0
    assert res.run.round_flops == 3 * 3 * 4 * 2 * (64 * 32 + 32 * 32
                                                    + 32 * 16 + 16 * 10)


MESH_RUN = """
import argparse, json, pathlib, sys, time
bench, work = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2])
sys.path[:0] = [str(bench), str(bench.parent / "src")]
import jax
import harness
assert len(jax.devices()) == 4, jax.devices()
spec = json.loads((work / "benchmark.json").read_text())
out = {}
for w in spec["workloads"]:
    cell = harness.Cell(w["name"], spec, files=work)
    args = argparse.Namespace(workload=w["name"], seed=2 ** 33 + 11,
                              seconds=0.5, trace=0)
    res = harness.runner(cell).run(cell, args, time.perf_counter(), None)
    out[w["name"]] = {"numbers": res.numbers, "losses": res.losses,
                      "limits": cell.limits}
print(json.dumps(out))
"""


@pytest.mark.parametrize("dtype,rtol", [("float32", 1e-6),
                                        ("bfloat16", 2.0 ** -8)])
def test_mesh_from_the_traffic_file(tmp_path, dtype, rtol):
    """The tiny LM cell at M=4, once on one device and once sharded
    data=4 over four virtual CPU devices by the traffic's `mesh`: both are
    correct, and their checked losses agree to the rounding of the
    activations' dtype. (Sharded, the server's sums run in another order;
    in bfloat16 that moves the second and third losses by ~1e-4 of
    themselves, in float32 by ~1e-7.)"""
    spec = json.loads((FIX / "benchmark.json").read_text())
    lm = json.loads((FIX / "traffic" / "train-lm.json").read_text())
    lm.update(clients=4, server_lr_scale=0.25)
    spec["workloads"] = []
    for traffic, chips, mesh in [("m4", 1, {}),
                                 ("m4-data4", 4, {"mesh": {"data": 4}})]:
        name = f"tiny-mamba2.{traffic}"
        spec["workloads"].append({"name": name, "config": "tiny-mamba2",
                                  "traffic": traffic, "chips": chips,
                                  "why": "test"})
        _write(tmp_path / "traffic" / f"{traffic}.json", dict(lm, **mesh))
        _copy(FIX / "limits" / f"{LM}.json",
              tmp_path / "limits" / f"{name}.json")
    _write(tmp_path / "benchmark.json", spec)
    config = json.loads((FIX / "configs" / "tiny-mamba2.json").read_text())
    config["dtype"] = config["program_overrides"]["dtype"] = dtype
    _write(tmp_path / "configs" / "tiny-mamba2.json", config)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", MESH_RUN, str(BENCH),
                           str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    one, four = out["tiny-mamba2.m4"], out["tiny-mamba2.m4-data4"]
    for r in (one, four):
        ok, rows = check.verdict(r["numbers"], r["limits"])
        assert ok, rows
    np.testing.assert_allclose(four["losses"], one["losses"], rtol=rtol)
