"""Rotary position embeddings (RoPE)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float = 10000.0):
    """Inverse frequencies ``theta ** (-i / half)``, computed on the host.

    A constant every program embeds bit for bit.  Traced, the power is
    either folded on the host or evaluated by the device's ``pow``,
    depending on each program's fusions: on a TPU the decode step at
    batch 1 and at batch 8 then rotated by different angles.
    """
    half = head_dim // 2
    return jnp.asarray(1.0 / theta ** (np.arange(half) / half), jnp.float32)


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., s, h, hd); positions: broadcastable to (..., s).

    Angles in fp32; the rotation multiplies stay in ``x.dtype`` so no
    activation-sized fp32 buffers materialize (sin/cos precision is what
    matters; the product rounds to bf16 anyway).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., s, hd/2)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
