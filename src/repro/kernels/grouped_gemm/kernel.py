"""Ragged grouped GEMM Pallas kernels (MoE expert compute).

The quintessential "batch of small, odd GEMMs" from the paper, §IV-B: each
expert's token group is a GEMM whose M dim is decided by the router at
runtime.  Two lowerings (DESIGN.md §9):

  * **fused** (``build_fused_grouped_kernel``): ONE ``pallas_call`` walks
    the ragged expert row-blocks directly.  The runtime tile table — one
    row per ``bm``-row block, ``(row0, row_end, row_start, expert,
    state)``, built from ``group_sizes`` by
    :meth:`repro.core.schedule.GroupedTileSchedule.tables` — rides in
    scalar-prefetch SMEM; the owning expert's weight panel is pulled by
    the table-driven BlockSpec index map; edge blocks use the two-step
    clamped-window load and a predicated RMW store, so there is **no
    pad-to-``t_padded`` intermediate and no gather-back** — tokens are
    touched exactly once.
  * **pad/scatter** (``build_grouped_gemm_kernel``, the pre-schedule
    lowering, kept for VMEM-oversized problems and as the autotuner's
    alternative): MegaBlocks-style mapping onto a static grid — tokens
    sorted by expert are padded to ``bm`` multiples by the caller, each
    row block belongs to exactly one expert (``block_expert`` scalar
    prefetch), and blocks past the padded total are skipped via
    ``pl.when``.

Both lowerings share the epilogue vocabulary (``repro.kernels.epilogue``)
with a *per-expert* bias operand of shape (E, N) — the scalar-prefetch
dispatch that selects an expert's weight panel selects its bias row the
same way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (TILE_COMPUTE, TILE_ZERO, GroupedTileSchedule,
                                 clamped_k_window, grouped_bwd_vmem_need,
                                 k_tail_mask, matmul_vmem_need,
                                 ownership_mask, predicated_store,
                                 vmem_limit)
from repro.kernels.epilogue import apply_epilogue, needs_bias


# ---------------------------------------------------------------------------
# Fused scheduled lowering (DESIGN.md §9): one launch, no pad, no gather
# ---------------------------------------------------------------------------

def _window(tbl_ref, g, j, ks, schedule):
    """Tile ``g``'s row origin and the (N, K) window origins of grid step
    ``(j, ks)``, each with the alignment the schedule proves (Mosaic must
    prove it to slice VMEM).  Returns ``(rs, col0, cs, k0, kstart)``."""
    sch = schedule
    rs = pl.multiple_of(tbl_ref[g, 2], sch.row_origin_align)
    col0 = j * sch.bn                       # nominal N-block start
    cs = pl.multiple_of(jnp.minimum(col0, sch.n_p - sch.bn), sch.col_align)
    k0, kstart = clamped_k_window(ks, sch.bk, sch.k_p)
    return rs, col0, cs, k0, pl.multiple_of(kstart, sch.k_align)


def _fused_grouped_kernel(tbl_ref, *refs, schedule, epilogue, out_dtype,
                          quant=None):
    """Walk the ragged tile table: one grid step = one (row-block, N-block,
    K-panel).  refs: x, w, [sx], [sw], [bias], out, acc_scratch — x/out
    staged whole (clamped row windows need element-granular origins),
    w/bias pulled per-expert by the table-driven index maps.

    Under a ``quant`` spec (DESIGN.md §13) the staged operands are the
    wire dtype, accumulation is exact-wide (int32 for int8, f32 for fp8
    / weight-only), and the dequant vectors ride alongside: ``sx`` the
    per-row activation scales ``(T, 1)`` (fully-quantized only), ``sw``
    the per-expert column scales ``(E, N)`` whose owning row the same
    table-driven index map selects — dequant fuses into the epilogue."""
    weight_only = quant is not None and quant.weight_only
    full_quant = quant is not None and not quant.weight_only
    acc_dt = jnp.int32 if (full_quant and quant.dtype == "int8") \
        else jnp.float32

    idx = 0
    x_ref = refs[idx]; idx += 1
    w_ref = refs[idx]; idx += 1
    sx_ref = sw_ref = None
    if full_quant:
        sx_ref = refs[idx]; idx += 1
    if quant is not None:
        sw_ref = refs[idx]; idx += 1
    bias_ref = None
    if needs_bias(epilogue):
        bias_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]

    kdim, n, bm, bk, bn = (schedule.k, schedule.n, schedule.bm, schedule.bk,
                           schedule.bn)
    k_steps = schedule.k_steps
    g = pl.program_id(0)
    j = pl.program_id(1)
    ks = pl.program_id(2)
    row0, row_end = tbl_ref[g, 0], tbl_ref[g, 1]
    state = tbl_ref[g, 4]
    rs, col0, cs, k0, kstart = _window(tbl_ref, g, j, ks, schedule)
    col_end = jnp.minimum(col0 + bn, n)

    @pl.when(state == TILE_COMPUTE)
    def _compute():
        @pl.when(ks == 0)
        def _init():
            acc_ref[...] = jnp.zeros((bm, bn), acc_dt)

        a = x_ref[pl.ds(rs, bm), pl.ds(kstart, bk)]
        b = w_ref[0, pl.ds(kstart, bk), pl.ds(cs, bn)]
        if weight_only:
            # int8 weight values are exact in the wide dtype; the column
            # scales stay in the epilogue.
            b = b.astype(a.dtype)
        if kdim % bk:  # K-tail predication: clamped overlap + padding
            a = k_tail_mask(a, 1, k0, kstart, kdim)
            b = k_tail_mask(b, 0, k0, kstart, kdim)
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dt)

        @pl.when(ks == k_steps - 1)
        def _store():
            out = acc_ref[...]
            dequant = None
            if sw_ref is not None:
                dequant = sw_ref[0:1, pl.ds(cs, bn)]
                if sx_ref is not None:
                    dequant = sx_ref[pl.ds(rs, bm), 0:1] * dequant
            bias_blk = None
            if bias_ref is not None:
                bias_blk = bias_ref[0:1, pl.ds(cs, bn)]
            out = apply_epilogue(out, epilogue, bias_blk, dequant)
            own = ownership_mask((bm, bn), rs, cs,
                                 row0, row_end, col0, col_end)
            predicated_store(o_ref, (pl.ds(rs, bm), pl.ds(cs, bn)),
                             out.astype(out_dtype), own)

    # Rows past sum(group_sizes) belong to no expert -> zero (matches
    # ref.py); the zero-fill pseudo-group's tiles own exactly those rows.
    @pl.when((state == TILE_ZERO) & (ks == k_steps - 1))
    def _zero():
        own = ownership_mask((bm, bn), rs, cs, row0, row_end, col0, col_end)
        predicated_store(o_ref, (pl.ds(rs, bm), pl.ds(cs, bn)),
                         jnp.zeros((bm, bn), out_dtype), own)


def build_fused_grouped_kernel(*, schedule: GroupedTileSchedule,
                               epilogue: Optional[str] = None,
                               in_dtype=jnp.float32, out_dtype=jnp.float32,
                               interpret: bool = False, quant=None):
    """Generate ONE pallas_call executing a whole ragged grouped dispatch.

    Returns ``f(table, x, w, [bias], sx=None, sw=None) -> (T, N)`` where
    ``table`` is the runtime ``(max_tiles, 5)`` int32 tile table
    (:meth:`GroupedTileSchedule.tables`), ``x: (T, K)`` rows sorted by
    group, ``w: (E, K, N)``, ``bias: (E, N)``.  The supergrid is
    ``(max_tiles, n_steps, k_steps)``.

    With a :class:`~repro.core.descriptor.QuantSpec` the operands arrive
    in the wire dtype and the dequant scales are extra operands: ``sx``
    per-row ``(T,)`` (fully-quantized only) staged whole as ``(T, 1)``,
    ``sw`` per-expert dense columns ``(E, N)`` whose owning row the tile
    table's expert column selects — same index map as the weight panel.
    """
    t, n = schedule.t, schedule.n
    # Staged at the schedule's padded extents: the blocks overhang ragged
    # operands so clamped windows keep aligned origins.
    t_p, k_p, n_p = schedule.t_p, schedule.k_p, schedule.n_p
    bm, bn = schedule.bm, schedule.bn
    has_bias = needs_bias(epilogue)
    has_sx = quant is not None and not quant.weight_only
    has_sw = quant is not None
    int_acc = has_sx and quant.dtype == "int8"

    body = functools.partial(
        _fused_grouped_kernel, schedule=schedule, epilogue=epilogue,
        out_dtype=jnp.dtype(out_dtype), quant=quant)

    in_specs = [
        pl.BlockSpec((t_p, k_p), lambda g, j, ks, tbl: (0, 0)),
        # the whole weight panel of the expert owning row-block g
        pl.BlockSpec((1, k_p, n_p), lambda g, j, ks, tbl: (tbl[g, 3], 0, 0)),
    ]
    if has_sx:
        # per-row activation scales, whole-staged like x (clamped row
        # windows need element-granular origins)
        in_specs.append(
            pl.BlockSpec((t_p, 1), lambda g, j, ks, tbl: (0, 0)))
    if has_sw:
        # the scale row of the expert owning row-block g
        in_specs.append(
            pl.BlockSpec((1, n_p), lambda g, j, ks, tbl: (tbl[g, 3], 0)))
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, n_p), lambda g, j, ks, tbl: (tbl[g, 3], 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the tile table
        grid=(schedule.max_tiles, schedule.n_steps, schedule.k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t_p, n_p), lambda g, j, ks, tbl: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32 if int_acc else jnp.float32)],
    )
    isz = jnp.dtype(in_dtype).itemsize  # x arrives in its wire dtype
    need = matmul_vmem_need(
        t_p, n_p, k_p, a_isz=isz,
        b_isz=quant.wire_itemsize if has_sw else isz,
        out_isz=jnp.dtype(out_dtype).itemsize, acc=(bm, bn),
        row_scales=has_sx, col_rows=int(has_sw) + int(has_bias))

    kernel = pl.pallas_call(
        body,
        name="grouped_gemm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.dtype(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )

    def run(table, x, w, bias=None, sx=None, sw=None):
        args = [table, x, w]
        if has_sx:
            assert sx is not None
            args.append(sx.reshape(t, 1).astype(jnp.float32))
        if has_sw:
            assert sw is not None
            args.append(sw.astype(jnp.float32))
        if has_bias:
            assert bias is not None
            args.append(bias)
        return kernel(*args)

    return run


# ---------------------------------------------------------------------------
# Fused scheduled backward (DESIGN.md §11): ONE launch over the same
# runtime tile tables computes dgrad (dX = dY @ W^T) and wgrad
# (dW = X^T @ dY, plus db for biased epilogues) — neither gradient ever
# touches the pad/scatter path
# ---------------------------------------------------------------------------

def _fused_grouped_bwd_kernel(tbl_ref, *refs, schedule, with_db):
    """Walk the ragged tile table with the grid reordered to
    ``(row-block, K-panel, N-block)``: the dX tile ``(bm, bk)``
    accumulates over the innermost N walk in scratch and drains with a
    predicated store; dW (and db) are whole-staged fp32 and accumulate by
    read-modify-write — contributions outside a tile's owned rows /
    nominal columns are masked to zero, so clamped-window overlap and
    revisits add nothing.  dW/db zero at the very first grid step,
    *outside* the tile-state conditional, so zero-size experts (which own
    no COMPUTE tile) still come back zero rather than garbage."""
    idx = 0
    x_ref = refs[idx]; idx += 1
    dy_ref = refs[idx]; idx += 1
    w_ref = refs[idx]; idx += 1
    dx_ref = refs[idx]; idx += 1
    dw_ref = refs[idx]; idx += 1
    db_ref = None
    if with_db:
        db_ref = refs[idx]; idx += 1
    dxacc_ref = refs[idx]

    kdim, n, bm, bk, bn = (schedule.k, schedule.n, schedule.bm, schedule.bk,
                           schedule.bn)
    n_steps = schedule.n_steps
    g = pl.program_id(0)
    ks = pl.program_id(1)
    j = pl.program_id(2)
    row0, row_end = tbl_ref[g, 0], tbl_ref[g, 1]
    e = tbl_ref[g, 3]
    state = tbl_ref[g, 4]

    @pl.when((g == 0) & (ks == 0) & (j == 0))
    def _zero_wgrad():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    rs, col0, cs, k0, kstart = _window(tbl_ref, g, j, ks, schedule)
    k_end = jnp.minimum(k0 + bk, kdim)

    @pl.when(state == TILE_COMPUTE)
    def _compute():
        @pl.when(j == 0)
        def _init():
            dxacc_ref[...] = jnp.zeros_like(dxacc_ref)

        # dY window, masked to owned rows and nominal columns (the
        # clamped N window may revisit columns of the previous block).
        dy_blk = dy_ref[pl.ds(rs, bm), pl.ds(cs, bn)].astype(jnp.float32)
        own_dy = ownership_mask((bm, bn), rs, cs, row0, row_end, col0, n)
        dy_m = jnp.where(own_dy, dy_blk, 0.0)
        w_blk = w_ref[0, pl.ds(kstart, bk), pl.ds(cs, bn)].astype(jnp.float32)
        if schedule.n_p != n:
            # Padding columns may be non-finite: zero them before they
            # meet dY's zeroed columns in the contraction.
            cols = cs + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
            w_blk = jnp.where(cols < n, w_blk, 0.0)

        # dgrad: dX[rows, kpanel] += dY @ W^T — masked dY zeroes every
        # term another tile owns, so no W-side mask is needed.
        dxacc_ref[...] += jax.lax.dot_general(
            dy_m, w_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        # wgrad: dW[e, kpanel, nblock] += X^T @ dY.
        x_blk = x_ref[pl.ds(rs, bm), pl.ds(kstart, bk)].astype(jnp.float32)
        own_x = ownership_mask((bm, bk), rs, kstart, row0, row_end, k0, kdim)
        x_m = jnp.where(own_x, x_blk, 0.0)
        dw_ref[pl.ds(e, 1), pl.ds(kstart, bk), pl.ds(cs, bn)] += (
            jax.lax.dot_general(x_m, dy_m, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)[None])

        if db_ref is not None:
            @pl.when(ks == 0)
            def _db():
                db_ref[pl.ds(e, 1), pl.ds(cs, bn)] += (
                    jnp.sum(dy_m, axis=0, keepdims=True))

        @pl.when(j == n_steps - 1)
        def _store_dx():
            own = ownership_mask((bm, bk), rs, kstart,
                                 row0, row_end, k0, k_end)
            predicated_store(dx_ref, (pl.ds(rs, bm), pl.ds(kstart, bk)),
                             dxacc_ref[...], own)

    # Rows past sum(group_sizes) belong to no expert -> zero dX rows.
    @pl.when((state == TILE_ZERO) & (j == n_steps - 1))
    def _zero_dx():
        own = ownership_mask((bm, bk), rs, kstart, row0, row_end, k0, k_end)
        predicated_store(dx_ref, (pl.ds(rs, bm), pl.ds(kstart, bk)),
                         jnp.zeros((bm, bk), jnp.float32), own)


def build_fused_grouped_bwd_kernel(*, schedule: GroupedTileSchedule,
                                   with_db: bool = False,
                                   in_dtype=jnp.float32,
                                   interpret: bool = False):
    """Generate ONE pallas_call executing a whole grouped backward.

    Returns ``f(table, x, dy, w) -> (dx, dw[, db])`` with
    ``x: (T, K)``, ``dy: (T, N)`` (the *pre-epilogue* cotangent — the ops
    wrapper peels activations off first), ``w: (E, K, N)``; gradients
    come back fp32 (the ops wrapper casts).  The supergrid is
    ``(max_tiles, k_steps, n_steps)`` — K outside N so the dX tile drains
    once per K-panel (DESIGN.md §11).
    """
    t, kdim, n = schedule.t, schedule.k, schedule.n
    t_p, k_p, n_p = schedule.t_p, schedule.k_p, schedule.n_p
    bm, bk = schedule.bm, schedule.bk
    e = schedule.num_experts

    body = functools.partial(_fused_grouped_bwd_kernel, schedule=schedule,
                             with_db=with_db)

    in_specs = [
        pl.BlockSpec((t_p, k_p), lambda g, ks, j, tbl: (0, 0)),
        pl.BlockSpec((t_p, n_p), lambda g, ks, j, tbl: (0, 0)),
        pl.BlockSpec((1, k_p, n_p), lambda g, ks, j, tbl: (tbl[g, 3], 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((t_p, k_p), lambda g, ks, j, tbl: (0, 0)),
        pl.BlockSpec((e, k_p, n_p), lambda g, ks, j, tbl: (0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((t, kdim), jnp.float32),
        jax.ShapeDtypeStruct((e, kdim, n), jnp.float32),
    ]
    if with_db:
        out_specs.append(pl.BlockSpec((e, n_p), lambda g, ks, j, tbl: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((e, n), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the tile table
        grid=(schedule.max_tiles, schedule.k_steps, schedule.n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
    )

    need = grouped_bwd_vmem_need(t_p, k_p, n_p, experts=e,
                                 isz=jnp.dtype(in_dtype).itemsize,
                                 acc=(bm, bk), with_db=with_db)
    kernel = pl.pallas_call(
        body,
        name="grouped_gemm_bwd",
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )

    def run(table, x, dy, w):
        return tuple(kernel(table, x, dy, w))

    return run


# ---------------------------------------------------------------------------
# Pad/scatter lowering (pre-schedule fallback + autotune alternative)
# ---------------------------------------------------------------------------

def _grouped_kernel(block_expert_ref, nrows_ref, *refs, bm, bk, bn,
                    k_steps, k_rem, epilogue, out_dtype):
    idx = 0
    x_ref = refs[idx]; idx += 1
    w_ref = refs[idx]; idx += 1
    bias_ref = None
    if needs_bias(epilogue):
        bias_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]

    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    active = (i * bm) < nrows_ref[0]

    @pl.when(active)
    def _():
        a = x_ref[...]
        b = w_ref[0]
        if k_rem:
            kidx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            valid = jnp.where(kk == k_steps - 1, k_rem, bk)
            a = jnp.where(kidx < valid, a, 0)
            kidx_b = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
            b = jnp.where(kidx_b < valid, b, 0)
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _():
        out = acc_ref[...]
        bias_blk = bias_ref[...] if bias_ref is not None else None
        out = apply_epilogue(out, epilogue, bias_blk)
        o_ref[...] = out.astype(out_dtype)


def build_grouped_gemm_kernel(*, t_padded: int, k: int, n: int, num_experts: int,
                              bm: int = 128, bk: int = 512, bn: int = 256,
                              epilogue: Optional[str] = None,
                              in_dtype=jnp.float32, out_dtype=jnp.float32,
                              interpret: bool = False):
    """Returns f(x:(Tp,K), w:(E,K,N), [bias:(E,N)], block_expert:(nb,),
    nrows:(1,)) -> (Tp,N)."""
    bn = min(bn, n)
    bk = min(bk, k)
    grid_m = pl.cdiv(t_padded, bm)
    grid_n = pl.cdiv(n, bn)
    grid_k = pl.cdiv(k, bk)
    has_bias = needs_bias(epilogue)

    body = functools.partial(_grouped_kernel, bm=bm, bk=bk, bn=bn,
                             k_steps=grid_k, k_rem=k % bk, epilogue=epilogue,
                             out_dtype=jnp.dtype(out_dtype))

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk, be, nr: (i, kk)),
        # weight tile of the expert owning row-block i
        pl.BlockSpec((1, bk, bn),
                     lambda i, j, kk, be, nr: (be[i], kk, j)),
    ]
    if has_bias:
        # ... and the same expert's bias row
        in_specs.append(
            pl.BlockSpec((1, bn), lambda i, j, kk, be, nr: (be[i], j)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_expert, nrows
        grid=(grid_m, grid_n, grid_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, be, nr: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )

    kernel = pl.pallas_call(
        body,
        name="grouped_gemm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_padded, n), out_dtype),
        interpret=interpret,
    )

    def run(x, w, block_expert, nrows, bias=None):
        args = [block_expert, nrows, x, w]
        if has_bias:
            assert bias is not None
            args.append(bias)
        return kernel(*args)

    return run
