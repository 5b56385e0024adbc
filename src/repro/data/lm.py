"""Synthetic heterogeneous LM data: per-client Markov chains.

For the large-backbone training examples we need token streams with (a)
learnable structure and (b) *controllable client heterogeneity* — the
paper's setting transplanted to language modelling. Each client's stream is
a first-order Markov chain whose transition matrix interpolates between a
shared chain and a client-private chain:

    P_m = (1 - beta) * P_shared + beta * P_m_private

beta plays the role of the paper's heterogeneity (beta=0 -> i.i.d. clients;
beta=1 -> fully disjoint structure). A bigram model can reach the entropy
floor, so loss curves are meaningful.

The chains run over S = min(vocab_size, MAX_STATES) states, so host memory
and draw time stay bounded at a real vocabulary (dense V x V chains at
V=50,280 would be ~20 GB each). When S == vocab_size the states ARE the
tokens (the historical seeded stream). Otherwise a seeded permutation cuts
the vocabulary into S near-equal groups and each state emits one token of
its group uniformly: the token stream is still a first-order Markov chain
(a token names its state), it covers the whole vocabulary, and beta keeps
its meaning on the state chain.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def _random_transition(rng: np.random.Generator, vocab: int, concentration=0.3):
    p = rng.gamma(concentration, size=(vocab, vocab)).astype(np.float64)
    p /= p.sum(axis=1, keepdims=True)
    return p


# S x S float64 chains of 8 MiB each, whatever the vocabulary
MAX_STATES = 1024


@dataclass
class MultiTaskLMSource:
    vocab_size: int = 256
    num_clients: int = 4
    beta: float = 1.0  # heterogeneity
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        S = self.num_states = min(self.vocab_size, MAX_STATES)
        shared = _random_transition(rng, S)
        self.chains = []
        for _ in range(self.num_clients):
            private = _random_transition(rng, S)
            p = (1 - self.beta) * shared + self.beta * private
            self.chains.append(p / p.sum(axis=1, keepdims=True))
        if S < self.vocab_size:
            # state s emits a token of perm[bounds[s]:bounds[s+1]]
            self._perm = rng.permutation(self.vocab_size)
            self._bounds = np.arange(S + 1) * self.vocab_size // S

    def _emit(self, rng: np.random.Generator, states):
        """Tokens for a [..., seq] state array (identity when S == V)."""
        if self.num_states == self.vocab_size:
            return states
        lo = self._bounds[states]
        width = self._bounds[states + 1] - lo
        pick = (rng.random(states.shape) * width).astype(np.int64)
        return self._perm[lo + np.minimum(pick, width - 1)]

    def client_tokens(self, rng: np.random.Generator, client: int, batch: int, seq: int):
        P = self.chains[client]
        S = self.num_states
        cum = np.cumsum(P, axis=1)
        out = np.empty((batch, seq), np.int64)
        state = rng.integers(0, S, size=batch)
        out[:, 0] = state
        for t in range(1, seq):
            u = rng.random(batch)
            # clamp the inverse-CDF draw: fp rounding can leave cum's last
            # column below 1.0, and a u above it would yield state == S —
            # out of range, an IndexError at cum[state] on the next step
            # (the clamp only fires on that overflow, so existing seeded
            # streams are unchanged)
            state = np.minimum((cum[state] < u[:, None]).sum(axis=1), S - 1)
            out[:, t] = state
        return self._emit(rng, out)

    def all_clients_batch(self, rng: np.random.Generator, batch_per_client: int,
                          seq: int, vectorized: bool = False):
        """[M, b, S] token batch.

        vectorized=False is the historical per-client loop (byte-identical
        seeded stream). vectorized=True advances ALL clients' chains with
        one batched inverse-CDF draw per position — host cost per client
        stays flat as M grows (only the inherently sequential loop over the
        sequence remains). Same distribution, different (seeded) stream.
        """
        if not vectorized:
            return np.stack(
                [
                    self.client_tokens(rng, m, batch_per_client, seq)
                    for m in range(self.num_clients)
                ]
            )
        M, S, b = self.num_clients, self.num_states, batch_per_client
        cums = np.cumsum(np.stack(self.chains), axis=2)  # [M, S, S]
        out = np.empty((M, b, seq), np.int64)
        state = rng.integers(0, S, size=(M, b))
        out[..., 0] = state
        midx = np.arange(M)[:, None]
        for t in range(1, seq):
            u = rng.random((M, b))
            # same overflow clamp as the per-client path above
            state = np.minimum(
                (cums[midx, state] < u[..., None]).sum(axis=-1), S - 1)
            out[..., t] = state
        return self._emit(rng, out)

    def entropy_floor(self, client: int) -> float:
        """Stationary conditional entropy of client's token stream
        (nats/token): the state chain's, plus the uniform emission's."""
        P = self.chains[client]
        # stationary distribution via power iteration
        pi = np.full(P.shape[0], 1.0 / P.shape[0])
        for _ in range(500):
            pi = pi @ P
        h = -np.sum(pi[:, None] * P * np.log(P + 1e-12))
        if self.num_states < self.vocab_size:
            h += np.sum(pi * np.log(np.diff(self._bounds)))
        return float(h)
