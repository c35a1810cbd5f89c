"""Features and weights from ``--seed``, made on the device in one call.

Features are N(0,1) with a share of exact zeros (the configuration's
``feature_sparsity``); weights are N(0, 2/fan_in) (He), biases N(0, 0.1^2).
Features and weight matrices are rounded to values that bfloat16 holds
exactly (an f32 array still): the chip's default-precision product then
rounds none of them, so ``correct`` can separate f32 storage from bf16
storage (see ``bench/check.py``).  The values are served as f32, the
configuration's storage type.
"""

from __future__ import annotations

from functools import partial
from typing import List, Tuple

import numpy as np


def key_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed, 64-bit ones too."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def make_inputs(seed: int, n: int, dims: List[int], sparsity: float
                ) -> Tuple[np.ndarray, list]:
    """``(features (n, dims[0]) f32 host array, [(w, b), ...] device)``."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def gen(words, n, dims, sparsity):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        keys = jax.random.split(key, 2 + 2 * (len(dims) - 1))

        def bf16_exact(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        x = jax.random.normal(keys[0], (n, dims[0]), jnp.float32)
        zero = jax.random.uniform(keys[1], (n, dims[0])) < sparsity
        x = jnp.where(zero, 0.0, bf16_exact(x))
        layers = []
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            w = jax.random.normal(keys[2 + 2 * i], (d_in, d_out), jnp.float32)
            b = jax.random.normal(keys[3 + 2 * i], (d_out,), jnp.float32)
            layers.append((bf16_exact(w * jnp.sqrt(2.0 / d_in)), b * 0.1))
        return x, layers

    x, layers = gen(jnp.asarray(key_words(seed)), int(n), tuple(dims),
                    float(sparsity))
    return np.asarray(x), layers


def program_params(layers) -> dict:
    """The program's parameter pytree (``models.gcn.init_params``'s form)."""
    return {f"layer_{i}": {"w": w, "b": b} for i, (w, b) in enumerate(layers)}


def host_weights(layers) -> list:
    return [(np.asarray(w), np.asarray(b)) for w, b in layers]
