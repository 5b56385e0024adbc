"""The program's own trace names: the MTSL round's phase scopes and the
training loop's host spans, as the benchmark's trace reducer
(bench/phasetrace.py) reads them.

  * A traced 3-round `train()` holds `repro.round`, `repro.dispatch`,
    `repro.input_wait` (with `queued`) and `repro.draw` spans whose
    `round` counters are 1..3.
  * Every dot and convolution of the mtsl round program, as lowered and as
    compiled, dense and client-chunked, carries exactly one phase scope,
    and the SSD of a mamba2 round carries `mamba.ssd`.
"""
import glob
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.append(str(REPO / "bench"))

import phasetrace  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.algorithms import HParams, get_algorithm, shard_round_fn  # noqa: E402
from repro.core.schedule import full_schedule  # noqa: E402
from repro.data.pipeline import client_batches  # noqa: E402
from repro.data.synthetic import MultiTaskImageSource  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import adamw, sgd  # noqa: E402
from repro.train.loop import TrainConfig, train  # noqa: E402

M = 4


def _host_spans(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name, []).append(dict(e.stats))
    return spans


def test_traced_train_holds_the_loop_spans(tmp_path):
    cfg = get_config("paper-mlp", smoke=True).with_updates(num_clients=M)
    model = build_model(cfg)
    src = MultiTaskImageSource(num_classes=M, image_size=cfg.image_size,
                               channels=cfg.image_channels, alpha=0.0,
                               seed=0)
    tcfg = TrainConfig(steps=3, algorithm="mtsl", log_every=0, prefetch=2)
    batches = client_batches(src, 4, seed=0, as_numpy=True)
    train(model, sgd(0.1), batches, tcfg, M, log=lambda s: None)  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        state, _ = train(model, sgd(0.1),
                         client_batches(src, 4, seed=0, as_numpy=True),
                         tcfg, M, log=lambda s: None)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _host_spans(path[-1])
    for name in ("repro.round", "repro.dispatch", "repro.input_wait"):
        assert sorted(s["round"] for s in spans[name]) == [1, 2, 3], name
    # the producer thread runs up to `prefetch` rounds ahead of the loop
    assert {1, 2, 3} <= {s["round"] for s in spans["repro.draw"]}
    assert all(s["step_num"] == s["round"] for s in spans["repro.round"])
    assert all(0 <= s["queued"] <= 2 for s in spans["repro.input_wait"])


def _round_program_texts(arch, client_chunk):
    """The mtsl round program's HLO as lowered, and as compiled."""
    cfg = get_config(arch, smoke=True).with_updates(num_clients=M)
    model = build_model(cfg)
    opt = adamw(1e-3) if cfg.family == "ssm" else sgd(0.1)
    alg = get_algorithm("mtsl")
    hp = HParams(optimizer=opt)
    state = jax.eval_shape(
        lambda: alg.init_state(model, jax.random.PRNGKey(0), M, hp))
    if cfg.family == "resnet":
        batch = {"image": jax.ShapeDtypeStruct(
                     (M, 2, cfg.image_size, cfg.image_size,
                      cfg.image_channels), jnp.float32),
                 "label": jax.ShapeDtypeStruct((M, 2), jnp.int32)}
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((M, 2, 32), jnp.int32)}
    fn = shard_round_fn(alg, model, M, hp, client_chunk=client_chunk)
    lowered = fn.lower(state, batch, full_schedule(M, 1))
    return (lowered.as_text(dialect="hlo", debug_info=True),
            lowered.compile().as_text())


def _scopes(line):
    """(phase scopes, all scope names) of one HLO instruction line."""
    m = re.search(r'op_name="([^"]*)"', line)
    names = phasetrace.scope_names(m.group(1)) if m else []
    return [n for n in names if n in phasetrace.PHASE_SCOPES], names


def _matmuls(text):
    return [ln for ln in text.splitlines()
            if re.search(r"= \S+ (dot|convolution)\(", ln)]


@pytest.mark.parametrize("arch", ["paper-resnet16", "mamba2-130m"])
@pytest.mark.parametrize("client_chunk", [None, 2])
def test_every_matmul_of_the_round_carries_one_phase(arch, client_chunk):
    lowered, compiled = _round_program_texts(arch, client_chunk)
    # as the program writes it, every dot and convolution is in one phase
    for ln in _matmuls(lowered):
        assert len(_scopes(ln)[0]) == 1, ln.strip()[:300]
    # so is each the compiler keeps or makes from them; one it makes from
    # nothing (the CPU backend's window-dilated convolution) has no op_name
    heavy = [ln for ln in _matmuls(compiled) if "op_name=" in ln]
    assert len(heavy) >= 0.9 * len(_matmuls(compiled)) > 0
    for ln in heavy:
        assert len(_scopes(ln)[0]) == 1, ln.strip()[:300]
    seen = {p for ln in compiled.splitlines() for p in _scopes(ln)[0]}
    assert seen == set(phasetrace.PHASE_SCOPES)
    if arch == "mamba2-130m":
        assert any(phasetrace.SSD_SCOPE in _scopes(ln)[1] for ln in heavy)
