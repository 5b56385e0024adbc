"""Integration: end-to-end training improves the MTL objective; MTSL beats
FedAvg under maximal heterogeneity (the paper's core claim, miniaturized);
the dry-run lowers on an emulated 8-device mesh (subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import lr_policy
from repro.core.mtsl import TrainState, build_eval_step, build_train_step, init_state
from repro.data.pipeline import client_batches
from repro.data.synthetic import MultiTaskImageSource
from repro.models import build_model
from repro.optim import sgd
from repro.utils.sharding import strip


def _train(alg, cfg, model, src, steps=60, lr=0.1, seed=0):
    M = cfg.num_clients
    opt = sgd(lr)
    params = strip(init_state(model, opt, jax.random.PRNGKey(seed), M, alg))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    step = jax.jit(build_train_step(model, opt, M, alg))
    clr = lr_policy.server_scaled(M) if alg == "mtsl" else lr_policy.uniform(M)
    for i, batch in enumerate(client_batches(src, 16, steps=steps, seed=seed)):
        state, metrics = step(state, batch, clr)
    return state


def _acc_mtl(cfg, model, state, src, seed=1):
    M = cfg.num_clients
    ev = jax.jit(build_eval_step(model, M))
    rng = np.random.default_rng(seed)
    imgs, labs = [], []
    for m in range(M):
        x, y = src.test_batch(rng, m, 64)
        imgs.append(x)
        labs.append(y)
    batch = {"image": jnp.asarray(np.stack(imgs)), "label": jnp.asarray(np.stack(labs))}
    return float(ev(state.params, batch)["acc_mtl"])


@pytest.mark.slow
def test_mtsl_beats_fedavg_under_heterogeneity():
    """Paper Table 2 (miniaturized): alpha=0, MTSL accuracy > FedAvg."""
    cfg = get_config("paper-mlp", smoke=True)
    model = build_model(cfg)
    src = MultiTaskImageSource(num_classes=cfg.num_clients,
                               image_size=cfg.image_size, alpha=0.0, seed=0)
    s_mtsl = _train("mtsl", cfg, model, src)
    s_fed = _train("fedavg", cfg, model, src)
    a_mtsl = _acc_mtl(cfg, model, s_mtsl, src)
    a_fed = _acc_mtl(cfg, model, s_fed, src)
    assert a_mtsl > 0.8, a_mtsl
    assert a_mtsl >= a_fed, (a_mtsl, a_fed)


def test_training_reduces_loss_lm():
    from repro.data.lm import MultiTaskLMSource

    cfg = get_config("mamba2-130m", smoke=True)
    model = build_model(cfg)
    M = cfg.num_clients
    opt = sgd(0.5)
    params = strip(init_state(model, opt, jax.random.PRNGKey(0), M, "mtsl"))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    step = jax.jit(build_train_step(model, opt, M, "mtsl"))
    src = MultiTaskLMSource(vocab_size=cfg.vocab_size, num_clients=M, seed=0)
    losses = []
    for i, batch in enumerate(client_batches(src, 8, seq_len=32, steps=30, seed=0)):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


DRYRUN_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import repro.launch.mesh as meshmod
meshmod.make_production_mesh = lambda multi_pod=False: (
    meshmod.make_mesh((2, 2, 2), ("pod", "data", "model")) if multi_pod
    else meshmod.make_mesh((2, 4), ("data", "model")))
import repro.launch.dryrun as dr
dr.make_production_mesh = meshmod.make_production_mesh
import repro.configs.base as cb
_orig = cb.get_config
dr.get_config = lambda name, smoke=False: _orig(name, smoke=True)
r1 = dr.lower_program("{arch}", "{shape}", multi_pod={mp}, verbose=False)
assert r1["status"] == "OK", r1
print("OK", r1["collective_bytes"])
"""


@pytest.mark.parametrize("arch,shape,mp", [
    ("gemma3-12b", "train_4k", False),
    ("qwen3-moe-30b-a3b", "train_4k", True),
    ("mamba2-130m", "decode_32k", False),
    ("whisper-tiny", "prefill_32k", False),
])
@pytest.mark.slow
def test_dryrun_lowers_on_emulated_mesh(arch, shape, mp):
    """The dry-run path (sharded lower+compile) works on an 8-device mesh.
    Subprocess: the device count must be set before jax initializes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    code = DRYRUN_SNIPPET.format(arch=arch, shape=shape, mp=mp)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_train_launcher_cli():
    from repro.launch.train import main

    state, history = main(["--arch", "paper-mlp", "--smoke", "--steps", "5",
                           "--batch-per-client", "4"])
    assert history and np.isfinite(history[-1]["loss"])
