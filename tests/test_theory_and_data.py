"""Convergence theory (paper §3) and synthetic-data behaviour."""
import numpy as np
import pytest

from repro.core.theory import paper_fig2_setup
from repro.data.lm import MultiTaskLMSource
from repro.data.synthetic import MultiTaskImageSource


# ---------------------------------------------------------------------------
# linear + quadratic case (Prop. 1 / Fig. 2)
# ---------------------------------------------------------------------------

P0 = {"w": 0.1, "d": 0.0, "b": [0.1, 0.1], "a": [0.0, 0.0]}


def test_gd_descends_with_lipschitz_lr():
    """eta_i = 0.1/L_i (recomputed at the iterate — the objective is bilinear
    so L is parameter-dependent; the safety factor covers the w<->b cross
    curvature the paper's per-component constants omit) gives monotone
    descent. Documented in EXPERIMENTS.md §Repro/Fig2."""
    sys = paper_fig2_setup()
    traj = sys.run_gd(P0, 0.1, np.full(2, 0.1), steps=400, adaptive=True)
    total = traj.sum(axis=1)
    assert np.all(np.diff(total) <= 1e-9), "loss must be non-increasing"
    assert total[-1] < total[0] * 1e-3


def test_high_moment_client_has_tighter_lr_range():
    """Paper Fig. 2d/e: the 10x-second-moment client (client 2) diverges at a
    learning rate the low-moment client tolerates."""
    sys = paper_fig2_setup(moment_ratio=10.0)
    diverge2 = sys.run_gd(P0, 0.002, [0.01, 0.5], steps=300)
    assert np.isnan(diverge2).any() or diverge2[-1].sum() > 1e3
    ok1 = sys.run_gd(P0, 0.002, [0.5, 0.01], steps=300)
    assert np.isfinite(ok1).all() and ok1[-1].sum() < 1.0


def test_lr_tuning_speeds_up_low_moment_client():
    """Paper Fig. 2d: doubling client-1's LR (low moment) speeds up task 1
    without breaking convergence."""
    sys = paper_fig2_setup()
    base = sys.run_gd(P0, 0.002, [0.01, 0.01], steps=100)
    fast1 = sys.run_gd(P0, 0.002, [0.02, 0.01], steps=100)
    assert fast1[-1, 0] < base[-1, 0]
    assert np.isfinite(fast1).all()


def test_convergence_rate_order_1_over_T():
    """Prop. 1 (convex): optimality gap = O(1/T) — the adaptive-1/L run must
    decay at least as fast as C/T."""
    sys = paper_fig2_setup(moment_ratio=2.0)
    traj = sys.run_gd(P0, 0.1, np.full(2, 0.1), steps=800, adaptive=True).sum(axis=1)
    for T in (100, 200, 400, 800):
        assert traj[T] <= traj[50] * 50 / T * 3.0


def test_mtsl_shared_server_helps_lagging_task():
    """Fig. 2a vs 2b: with a COMMON learning rate, the shared-server (MTSL)
    system converges faster on task 2 than fully separate networks."""
    sys = paper_fig2_setup()
    sep = sys.run_separate(P0, 0.01, steps=100)
    shared = sys.run_gd(P0, 0.01, [0.01, 0.01], steps=100)
    assert shared[100, 1] < sep[100, 1]


# ---------------------------------------------------------------------------
# data sources
# ---------------------------------------------------------------------------


def test_image_source_alpha_controls_heterogeneity(nprng):
    src = MultiTaskImageSource(num_classes=5, image_size=8, alpha=0.0, seed=1)
    _, labels = src.task_batch(nprng, task=3, batch=200)
    assert (labels == 3).all()
    src2 = MultiTaskImageSource(num_classes=5, image_size=8, alpha=0.8 * (1 - 1 / 5), seed=1)
    _, labels2 = src2.task_batch(nprng, task=3, batch=2000)
    frac = (labels2 == 3).mean()
    assert 0.25 < frac < 0.5  # 1 - alpha = 0.36


def test_image_classes_are_separable(nprng):
    # class-mean separation must survive averaging out the within-class
    # jitter (the defaults are deliberately near the Bayes boundary, so test
    # with the jitter scaled down and the signal held fixed)
    src = MultiTaskImageSource(num_classes=3, image_size=8, alpha=0.0,
                               jitter=0.3, class_sep=0.5, seed=2)
    x0, _ = src.test_batch(nprng, 0, 100)
    x1, _ = src.test_batch(nprng, 1, 100)
    within = np.linalg.norm(x0 - x0.mean(0), axis=(1, 2)).mean()
    between = np.linalg.norm(x0.mean(0) - x1.mean(0))
    assert between > within * 0.3  # class signal exists
    # and the default (hard) setting still has nonzero mean separation
    hard = MultiTaskImageSource(num_classes=3, image_size=8, alpha=0.0, seed=2)
    h0, _ = hard.test_batch(nprng, 0, 200)
    h1, _ = hard.test_batch(nprng, 1, 200)
    assert np.linalg.norm(h0.mean(0) - h1.mean(0)) > 0.1


def test_lm_source_heterogeneity(nprng):
    src = MultiTaskLMSource(vocab_size=32, num_clients=3, beta=1.0, seed=0)
    t = src.all_clients_batch(nprng, 4, 64)
    assert t.shape == (3, 4, 64)
    assert t.min() >= 0 and t.max() < 32
    # different clients' chains differ
    assert not np.allclose(src.chains[0], src.chains[1])
    src_iid = MultiTaskLMSource(vocab_size=32, num_clients=3, beta=0.0, seed=0)
    np.testing.assert_allclose(src_iid.chains[0], src_iid.chains[1])
    # entropy floor is a valid bound
    h = src.entropy_floor(0)
    assert 0.0 < h < np.log(32)


class _OverflowRng:
    """Adversarial rng for the inverse-CDF edge: every uniform lands above
    the (fp-rounded) last CDF column, every initial state is 0."""

    def integers(self, lo, hi, size=None):
        return np.zeros(size, np.int64)

    def random(self, size=None):
        return np.full(size, 1.0 - 1e-12)


def test_lm_inverse_cdf_clamps_fp_overflow():
    """Regression: fp rounding can leave a transition row's cumsum last
    column below 1.0; a uniform draw above it used to produce state ==
    vocab_size — an out-of-range token that IndexErrors the next step's
    cum[state] gather. Both sampling paths now clamp to V-1."""
    V = 8
    src = MultiTaskLMSource(vocab_size=V, num_clients=2, beta=1.0, seed=0)
    # force the edge deterministically: shrink every row's mass so the CDF
    # tops out strictly below the adversarial uniforms
    src.chains = [p * (1.0 - 1e-7) for p in src.chains]
    toks = src.client_tokens(_OverflowRng(), 0, batch=3, seq=5)
    assert toks.shape == (3, 5)
    assert toks.max() == V - 1  # clamped, not out of range
    vec = src.all_clients_batch(_OverflowRng(), 3, 5, vectorized=True)
    assert vec.shape == (2, 3, 5)
    assert vec.max() == V - 1


def test_lm_clamp_leaves_seeded_streams_unchanged(nprng):
    """The clamp only fires on overflow — normal seeded generation is
    byte-identical to the historical stream."""
    src = MultiTaskLMSource(vocab_size=16, num_clients=2, beta=0.5, seed=3)
    a = src.client_tokens(np.random.default_rng(9), 0, 4, 12)
    b = src.client_tokens(np.random.default_rng(9), 0, 4, 12)
    np.testing.assert_array_equal(a, b)
    assert 0 <= a.min() and a.max() < 16


def test_lm_source_real_vocab_bounded_build():
    """At mamba2-130m's V=50,280 the chains stay S x S (S = MAX_STATES),
    so building M+1 of them takes megabytes, not the ~20 GB per dense
    V x V chain."""
    import tracemalloc

    V, M = 50_280, 8
    tracemalloc.start()
    src = MultiTaskLMSource(vocab_size=V, num_clients=M, beta=1.0, seed=0)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    S = src.num_states
    assert S < V
    assert all(p.shape == (S, S) for p in src.chains)
    # M+1 float64 S x S matrices plus working copies
    assert peak < 8 * (M + 1) * S * S * 8, peak
    # the emission groups partition the vocabulary
    assert src._bounds[0] == 0 and src._bounds[-1] == V
    assert np.array_equal(np.sort(src._perm), np.arange(V))


@pytest.mark.parametrize("vectorized", [False, True])
def test_lm_source_real_vocab_draws_in_range(vectorized):
    """Draws at V=50,280 are in [0, V), cover many more ids than there are
    states, and each token's group names the state chain's next state."""
    V = 50_280
    src = MultiTaskLMSource(vocab_size=V, num_clients=4, beta=0.5, seed=1)
    t = src.all_clients_batch(np.random.default_rng(0), 4, 512,
                              vectorized=vectorized)
    assert t.shape == (4, 4, 512) and t.dtype == np.int64
    assert t.min() >= 0 and t.max() < V
    assert len(np.unique(t)) > src.num_states
    # token -> state is a function (the stream stays first-order Markov)
    inv = np.empty(V, np.int64)
    inv[src._perm] = np.arange(V)
    states = np.searchsorted(src._bounds, inv[t], side="right") - 1
    assert states.min() >= 0 and states.max() < src.num_states
    h = src.entropy_floor(0)
    assert np.log(V / src.num_states) < h < np.log(V)


def test_lm_source_small_vocab_keeps_tokens_as_states():
    """V <= MAX_STATES keeps the historical construction: states are the
    tokens, and the chains are V x V."""
    src = MultiTaskLMSource(vocab_size=32, num_clients=2, seed=0)
    assert src.num_states == 32
    assert src.chains[0].shape == (32, 32)
    assert not hasattr(src, "_perm")
