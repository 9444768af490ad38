"""LFM2's gated short-convolution mixer.

``B, C, x = split3(h @ in_proj)``, then
``y = (C * causal_depthwise_conv(B * x)) @ out_proj``: a depthwise
convolution over the last ``kernel`` positions (tap ``kernel - 1`` is the
current token), no biases.  Full-sequence (train / prefill) only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import truncated_normal


def init_conv(key, d: int, kernel: int, num_layers: int, dtype) -> dict:
    ki, kc, ko = jax.random.split(key, 3)
    out_std = 0.02 / max(1.0, (2.0 * num_layers) ** 0.5)
    return {
        "in_proj": truncated_normal(ki, (d, 3 * d), 0.02, dtype),
        "conv": truncated_normal(kc, (kernel, d), 0.02, dtype),
        "out_proj": truncated_normal(ko, (d, d), out_std, dtype),
    }


def causal_depthwise_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """x (b, s, d), w (kernel, d): out[t] = sum_j w[j] * x[t - kernel + 1 + j],
    positions before the sequence start read as zero."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = xp[:, k - 1:k - 1 + s] * w[k - 1]
    for j in range(k - 1):
        out = out + xp[:, j:j + s] * w[j]
    return out


def apply_conv(p: dict, h: jax.Array) -> jax.Array:
    with jax.named_scope("conv"):
        b, c, x = jnp.split(h @ p["in_proj"], 3, axis=-1)
        y = c * causal_depthwise_conv(b * x, p["conv"])
        return y @ p["out_proj"]
