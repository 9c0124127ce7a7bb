"""Weights made by the benchmark from the seed, and the check of their
hand-over to the program.

The weights belong to the benchmark, not to the program: the reference
reads them as they are made here, and the program is handed the same
arrays in its own parameter layout (each model family's ``to_program``,
``bench/models/``).  So the reference takes nothing that the program
made.

A family names its leaves and their shapes; ``draw`` makes them on the
device in one jitted call, in bf16 (the type they are served in).  Norm
scales are drawn around 1 and biases around 0 with a visible spread, so
a program that dropped either would not agree with the reference.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1
BIAS_STD = 0.5


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(2 ** 31 - 1))


def draw(shapes: Dict[str, tuple], seed: int, dtype=jnp.bfloat16
         ) -> Dict[str, jax.Array]:
    """Every leaf of ``shapes``, drawn on the device in one call: one key
    per leaf, split in the leaves' sorted order.  A leaf's name sets its
    kind: ``embed``; a name ending in ``norm`` (scales around 1); a name
    starting with ``b`` (biases); any other is a ``(..., fan_in,
    fan_out)`` matrix scaled by ``fan_in ** -0.5``."""
    names = sorted(shapes)

    def one(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shp = shapes[name]
            z = jax.random.normal(k, shp, dtype)
            if name == "embed":
                out[name] = z * jnp.asarray(EMBED_STD, dtype)
            elif name.endswith("norm"):
                out[name] = 1 + z * jnp.asarray(NORM_STD, dtype)
            elif name.startswith("b"):
                out[name] = z * jnp.asarray(BIAS_STD, dtype)
            else:
                out[name] = z * jnp.asarray(shp[-2] ** -0.5, dtype)
        return out

    return jax.jit(one)(jax.random.PRNGKey(jax_seed(seed)))


def check_layout(params: Dict, expected) -> None:
    """Refuse a hand-over whose tree, shapes or dtypes differ from the
    program's own (``expected``: the program's parameter shapes)."""
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), expected)
    if got != want:
        raise ValueError(f"weights do not fit the program's layout:\n"
                         f"made {got}\nprogram wants {want}")
