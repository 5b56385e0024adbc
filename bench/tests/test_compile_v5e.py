"""Every cell's programs compiled at their real sizes for a described TPU v5e
chip (no chip needed): the round and the reference that checks it. Each must fit one chip's memory.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_compile_v5e.py

A compile is not a run: it says nothing of results or times.
"""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import train_cell  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
TRAIN = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1
         and harness.Cell(w["name"], SPEC).traffic["kind"] == "train"]
CHIP_BYTES = 15.75e9  # what a v5e chip offers a program (of its 16 GB)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=sharding),
                        tree)


def _bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _weights(cell):
    fam, M = cell.family, cell.traffic["clients"]
    return jax.eval_shape(lambda: fam.make_params(
        jax.random.PRNGKey(0), fam.ref_cfg(cell.config), M))


def _batch(cell):
    t, c = cell.traffic, cell.config
    M, b = t["clients"], t["batch_per_client"]
    if t["data"] == "lm":
        return {"tokens": jax.ShapeDtypeStruct((M, b, t["seq_len"]),
                                               jnp.int32)}
    s, ch = c["image_size"], c["image_channels"]
    return {"image": jax.ShapeDtypeStruct((M, b, s, s, ch), jnp.float32),
            "label": jax.ShapeDtypeStruct((M, b), jnp.int32)}


@pytest.mark.parametrize("name", TRAIN)
def test_round_program_fits_one_chip(name, one_chip):
    from repro.core import lr_policy
    from repro.core.algorithms import HParams, get_algorithm
    from repro.core.mtsl import TrainState
    from repro.core.schedule import full_schedule

    cell = harness.Cell(name, SPEC)
    t, M = cell.traffic, cell.traffic["clients"]
    model = train_cell.layout(cell)[0]
    opt = train_cell.optimizer(t["optimizer"])
    hp = HParams(optimizer=opt, component_lr=lr_policy.server_scaled(
        M, t["server_lr_scale"]))
    params = _weights(cell)
    state = jax.eval_shape(lambda p: TrainState(
        p, opt.init(p), jnp.zeros((), jnp.int32)), params)
    sched = jax.eval_shape(lambda: full_schedule(M, 1))
    # the program's round as jit_round_fn builds it for the chip (donated
    # state; the CPU backend here would skip the donation)
    fn = jax.jit(get_algorithm("mtsl").round_fn(model, M, hp),
                 donate_argnums=(0,))
    compiled = fn.lower(_on(state, one_chip), _on(_batch(cell), one_chip),
                        _on(sched, one_chip)).compile()
    assert _bytes(compiled) < CHIP_BYTES


@pytest.mark.parametrize("name", TRAIN)
def test_reference_step_fits_one_chip(name, one_chip):
    from reference import mtsl as ref_mtsl

    cell = harness.Cell(name, SPEC)
    step, opt = train_cell.reference_step(cell, train_cell.layout(cell)[2])
    params = _weights(cell)
    opt_state = jax.eval_shape(lambda p: ref_mtsl.init_opt(opt, p), params)
    compiled = step.lower(_on(params, one_chip), _on(opt_state, one_chip),
                          _on(_batch(cell), one_chip),
                          jax.ShapeDtypeStruct((), jnp.float32,
                                               sharding=one_chip)).compile()
    assert _bytes(compiled) < CHIP_BYTES


def test_sharded_round_for_four_chips(topo):
    """The deferred four-chip cell: mamba2-130m at M=16 sharded data=4 (4
    towers per chip), compiled for the described 2x2 host: it fits each
    chip and all-reduces the server's gradients."""
    from repro.core import lr_policy
    from repro.core.algorithms import HParams, get_algorithm, shard_round_fn
    from repro.core.mtsl import TrainState
    from repro.core.schedule import full_schedule
    from repro.launch.mesh import make_mesh
    from repro.utils.sharding import client_sharding, replicated_sharding

    cell = harness.Cell("mamba2-130m.train.m8-s512", SPEC)
    t, M = dict(cell.traffic, clients=16), 16
    cell.traffic = t
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    alg = get_algorithm("mtsl")
    model = train_cell.layout(cell)[0]
    opt = train_cell.optimizer(t["optimizer"])
    hp = HParams(optimizer=opt, component_lr=lr_policy.server_scaled(
        M, 1.0 / M))
    state = jax.eval_shape(lambda p: TrainState(
        p, opt.init(p), jnp.zeros((), jnp.int32)), _weights(cell))
    cs, rs = client_sharding(mesh), replicated_sharding(mesh)
    marks = alg.client_axes(state)
    state = jax.tree.map(lambda x, m: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=cs if m else rs), state, marks)
    sched = _on(jax.eval_shape(lambda: full_schedule(M, 1)), cs)
    fn = shard_round_fn(alg, model, M, hp, mesh=mesh)
    compiled = fn.lower(state, _on(_batch(cell), cs), sched).compile()
    assert _bytes(compiled) < CHIP_BYTES
    assert compiled.as_text().count("all-reduce(") >= 1


def test_sharded_reference_for_four_chips(topo):
    """The deferred four-chip cell's reference: mamba2-130m at M=16 needs
    more than one chip (16.8 GB), so with the traffic's mesh it runs split
    by client over the 2x2 host (train_cell.reference_checks); it fits
    each chip."""
    from reference import mtsl as ref_mtsl
    from repro.launch.mesh import make_mesh

    cell = harness.Cell("mamba2-130m.train.m8-s512", SPEC)
    cell.traffic = dict(cell.traffic, clients=16)
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    where = train_cell.placement(mesh)
    split, whole = where["towers"], where["server"]
    step, opt = train_cell.reference_step(cell, train_cell.layout(cell)[2])
    params = _weights(cell)
    params = {"towers": _on(params["towers"], split),
              "server": _on(params["server"], whole)}
    opt_state = jax.eval_shape(lambda p: ref_mtsl.init_opt(opt, p), params)
    opt_state = jax.tree.map(
        lambda x, p: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=p.sharding),
        opt_state, {"mu": params, "nu": params})
    compiled = step.lower(params, opt_state, _on(_batch(cell), split),
                          jax.ShapeDtypeStruct((), jnp.float32,
                                               sharding=whole)).compile()
    assert _bytes(compiled) < CHIP_BYTES
