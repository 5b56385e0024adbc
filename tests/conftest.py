"""Shared test fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches
must see the single real CPU device (the 512-device emulation is exclusive
to launch/dryrun.py, which tests spawn as a subprocess)."""
import os

import jax
import numpy as np
import pytest

from repro.utils.jit_cache import enable_compilation_cache

# Persistent jit-compile cache, opt-in for the suite (CI sets
# JAX_COMPILATION_CACHE_DIR and restores the directory between runs): the
# suite traces the same seven algorithms over and over — compile each
# program once per cache, not once per run.
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    enable_compilation_cache()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def nprng():
    return np.random.default_rng(0)


ASSIGNED_ARCHS = [
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
