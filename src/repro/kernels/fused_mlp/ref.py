"""Pure-jnp oracle for the fused per-packet MLP pipeline.

This is the *same math* as core.mlalgos.mlp_forward and the generated Taurus
pipeline: x -> (dense + relu)* -> dense logits.  The kernel test sweeps
shapes/dtypes and asserts allclose against this function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mlp_ref(x: jax.Array, weights: list[jax.Array], biases: list[jax.Array]
            ) -> jax.Array:
    """x: [B, F]; weights[i]: [d_i, d_{i+1}]; biases[i]: [d_{i+1}].

    ReLU between layers, no activation on the output layer. All accumulation
    in fp32 (matches both the MXU accumulate dtype and the Pallas kernel).
    """
    h = x.astype(jnp.float32)
    L = len(weights)
    for i, (w, b) in enumerate(zip(weights, biases)):
        h = jnp.dot(h, w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST) + b.astype(
                        jnp.float32)
        if i < L - 1:
            h = jax.nn.relu(h)
    return h.astype(x.dtype)
