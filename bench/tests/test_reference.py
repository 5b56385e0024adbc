"""The plain references against the program, on the CPU at test sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_reference.py
"""
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import train_cell  # noqa: E402
import weights  # noqa: E402
from reference import mamba2 as ref_mamba2  # noqa: E402
from reference import mtsl as ref_mtsl  # noqa: E402

FIX = BENCH / "tests" / "fixtures"


def _config(name, **program):
    c = json.loads((FIX / "configs" / f"{name}.json").read_text())
    c["program_overrides"] = dict(c.get("program_overrides", {}), **program)
    c["dtype"] = program.get("dtype", c["dtype"])
    return c


def test_ssd_quadratic_form_is_the_recurrence():
    rng = np.random.default_rng(0)
    b, L, H, P, N = 2, 37, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(b, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(b, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, L, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, L, N)), jnp.float32)
    q = ref_mamba2.ssd_quadratic(x, dt, A, B, C)
    s = ref_mamba2.ssd_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(q, s, rtol=1e-5, atol=1e-5)


def _program_round(config, traffic, params, batch):
    """One MTSL round of the program, float32, on the benchmark's weights."""
    from repro.core import lr_policy
    from repro.core.algorithms import HParams, get_algorithm, jit_round_fn
    from repro.core.mtsl import TrainState
    from repro.models.registry import build_model

    M = traffic["clients"]
    model = build_model(train_cell.program_config(
        config, M, harness.family(config["family"])))
    opt = train_cell.optimizer(traffic["optimizer"])
    hp = HParams(optimizer=opt, component_lr=lr_policy.server_scaled(
        M, traffic["server_lr_scale"]))
    fn = jit_round_fn(get_algorithm("mtsl"), model, M, hp)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    return fn(state, batch, None)


def _params(config, traffic, seed):
    fam = harness.family(config["family"])
    return weights.make_params(fam, seed, fam.ref_cfg(config),
                               traffic["clients"])


def _ref_round(config, traffic, params, batch):
    fam = harness.family(config["family"])
    loss, grads = fam.loss_and_grads(params, batch, fam.ref_cfg(config))
    opt = dict(traffic["optimizer"], server_scale=traffic["server_lr_scale"])
    new, _ = ref_mtsl.apply_opt(opt, params, grads,
                                ref_mtsl.init_opt(opt, params), 1.0)
    return loss, grads, new


def _batch(config, traffic, seed):
    return jax.tree.map(jnp.asarray, next(iter(train_cell.source(
        type("C", (), {"config": config, "traffic": traffic}), seed))))


def _close(a, b, tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        scale = max(float(jnp.max(jnp.abs(y))), 1e-12)
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


def test_mamba2_round_matches_program():
    config = _config("tiny-mamba2", dtype="float32")
    traffic = json.loads((FIX / "traffic" / "train-lm.json").read_text())
    params = _params(config, traffic, 5)
    batch = _batch(config, traffic, 5)
    state, metrics = _program_round(config, traffic, params, batch)
    loss, grads, _ = _ref_round(config, traffic, params, batch)
    assert abs(float(metrics["loss"]) - float(loss)) <= 1e-5 * abs(float(loss))
    b1 = traffic["optimizer"]["b1"]
    _close(jax.tree.map(lambda m: m / (1 - b1), state.opt_state.mu), grads,
           1e-4)


def test_resnet_round_matches_program():
    config = _config("tiny-resnet")
    traffic = json.loads((FIX / "traffic" / "train-image.json").read_text())
    params = _params(config, traffic, 9)
    batch = _batch(config, traffic, 9)
    state, metrics = _program_round(config, traffic, params, batch)
    loss, _, new = _ref_round(config, traffic, params, batch)
    assert abs(float(metrics["loss"]) - float(loss)) <= 1e-5 * abs(float(loss))
    _close(jax.tree.map(jnp.subtract, state.params, params),
           jax.tree.map(jnp.subtract, new, params), 1e-4)

