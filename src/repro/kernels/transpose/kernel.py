"""Tile-transpose Pallas kernel — the ZA horizontal/vertical trick (Lst. 5).

The paper transposes 16x16 blocks of B by writing vector registers into a
ZA tile through its *horizontal* view and reading them back through the
*vertical* view, staging the result in aligned scratch memory.  The TPU
analogue: each grid step stages one (bt, bt) block in a VMEM scratch tile,
transposes it in-register (Mosaic lowers ``.T`` of a VMEM tile to its
native sublane/lane rotations — the horizontal/vertical-view analogue) and
writes it to the mirrored block position ``(j, i)`` of the output.

Used by the two-pass "panel transpose then NN-GEMM" path for ``C += A·B``
with strided-contraction B (§IV-C), benchmarked against the fused
in-kernel transpose in fig89.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _transpose_body(x_ref, o_ref, scratch_ref):
    # Stage the tile through scratch (the ZA tile), then emit its transpose.
    scratch_ref[...] = x_ref[0]
    o_ref[0] = scratch_ref[...].T


def build_transpose_kernel(rows: int, cols: int, bt_r: int = 256,
                           bt_c: int = 256, dtype=jnp.float32,
                           interpret: bool = False, batch: int = 0):
    """Generate a (nb, rows, cols) -> (nb, cols, rows) transpose.

    Block (bt_r, bt_c) is read at block-index (b, i, j) and written at
    (b, j, i); partial edge blocks rely on Pallas store clipping (reads of
    the padded region are garbage but land outside the clipped store).
    Batch walks as the leading grid dimension — a batched transpose is ONE
    ``pallas_call``, not ``vmap``-stacked launches (DESIGN.md §9); the
    caller reshapes the unbatched case to ``nb = 1``.
    """
    nb = max(1, batch)
    grid = (nb, pl.cdiv(rows, bt_r), pl.cdiv(cols, bt_c))
    return pl.pallas_call(
        _transpose_body,
        name="transpose",
        grid=grid,
        in_specs=[pl.BlockSpec((1, bt_r, bt_c), lambda b, i, j: (b, i, j))],
        out_specs=pl.BlockSpec((1, bt_c, bt_r), lambda b, i, j: (b, j, i)),
        out_shape=jax.ShapeDtypeStruct((nb, cols, rows), dtype),
        scratch_shapes=[pltpu.VMEM((bt_r, bt_c), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )
