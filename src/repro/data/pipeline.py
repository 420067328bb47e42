"""Deterministic, resumable synthetic data pipeline.

DL jobs in the paper stream data each epoch from object storage; failures
must resume mid-epoch without replaying or skipping data.  We get exact
resumability *by construction*: ``batch_at(step)`` is a pure function of
(seed, step), so a learner restored from a step-``k`` checkpoint continues
with batch ``k+1`` bit-identically — no iterator state to persist.

The stream is an order-2 noisy Markov chain over the vocabulary, so it has
learnable structure (cross-entropy decreases) while needing no files.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SyntheticLMData:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    noise: float = 0.1            # fraction of purely-random tokens

    # compiled once per dataset (the frozen dataclass is the static key):
    # run eagerly, the scan below would trace and compile on every call
    @functools.partial(jax.jit, static_argnums=0)
    def batch_at(self, step: int | jax.Array):
        """{tokens, labels}: labels[t] = tokens[t+1] (next-token LM)."""
        key = jax.random.fold_in(jax.random.key(self.seed), step)
        k1, k2, k3 = jax.random.split(key, 3)
        B, S, V = self.global_batch, self.seq_len, self.vocab_size
        start = jax.random.randint(k1, (B,), 0, V)
        noise_tok = jax.random.randint(k2, (B, S + 1), 0, V)
        is_noise = jax.random.bernoulli(k3, self.noise, (B, S + 1))

        # x_{t+1} = (a·x_t + b) mod V, resampled uniformly with prob `noise`
        a, b = 31, 17

        def step_fn(x, xs):
            nz, nt = xs
            x = jnp.where(nz, nt, (a * x + b) % V)
            return x, x

        _, seq = jax.lax.scan(
            step_fn, start, (is_noise.T, noise_tok.T))
        seq = seq.T.astype(jnp.int32)                      # (B, S+1)
        return {"tokens": seq[:, :S], "labels": seq[:, 1:S + 1]}
