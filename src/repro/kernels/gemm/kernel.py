"""Shape-specialized blocked GEMM Pallas kernel — the SME microkernel analogue.

Paper mapping (Lst. 4 / Fig. 6):

  * the ZA accumulator tiles      -> an fp32 VMEM scratch accumulator block
    holding a (bm, bn) sub-block of C for the whole K loop;
  * the FMOPA outer-product chain -> one rank-``bk`` MXU update per K grid
    step, ``acc += A[bm,bk] @ B[bk,bn]`` (a systolic array consumes a
    K-panel; bk plays the role the 4-deep FMOPA tile rotation plays on SME:
    it hides the unit's accumulation latency);
  * predicate registers P0/P1      -> trace-time-specialized ``jnp.where``
    masks on the K tail (only emitted when ``K % bk != 0`` — the JIT
    "hardwires" the mask exactly like LIBXSMM hardwires loop trip counts);
  * the two-step load path         -> the Pallas grid pipeline, which stages
    HBM blocks into VMEM with double buffering;
  * transposed-B handling (§IV-C)  -> the "nt" variant contracts against
    B's minor dimension in-register (fused transpose); the two-pass
    scratch-panel variant lives in ``repro.kernels.transpose``.

The kernel is *generated*: ``build_gemm_kernel`` closes over all static
metadata (block shapes, layout, masking, epilogue) so each distinct
descriptor produces a distinct specialized kernel, cached by
``repro.core.jit_cache``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (k_tail_mask, matmul_vmem_need,
                                 ownership_mask, pack_table,
                                 predicated_store, vmem_limit)
from repro.kernels.epilogue import apply_epilogue, needs_bias


def _gemm_kernel_body(*refs, layout, k_steps, k_rem, bk, epilogue,
                      accumulate, out_dtype):
    """Kernel body. refs: a, b, [bias], [c_in], out, acc_scratch."""
    idx = 0
    a_ref = refs[idx]; idx += 1
    b_ref = refs[idx]; idx += 1
    bias_ref = None
    if needs_bias(epilogue):
        bias_ref = refs[idx]; idx += 1
    c_ref = None
    if accumulate:
        c_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if accumulate:
            acc_ref[...] = c_ref[...].astype(jnp.float32)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]

    if k_rem:  # K tail masking — the predicate-register analogue (§IV-B).
        # Only the final K step is partial; `where` (not multiply) because
        # out-of-bounds pads may be NaN.
        kk = jax.lax.broadcasted_iota(jnp.int32, a.shape, dimension=1)
        valid = jnp.where(k == k_steps - 1, k_rem, bk)
        a = jnp.where(kk < valid, a, 0)
        if layout == "nn":
            kkb = jax.lax.broadcasted_iota(jnp.int32, b.shape, dimension=0)
        else:
            kkb = jax.lax.broadcasted_iota(jnp.int32, b.shape, dimension=1)
        b = jnp.where(kkb < valid, b, 0)

    if layout == "nn":
        dn = (((1,), (0,)), ((), ()))
    else:  # nt: B block is (bn, bk); contract minor dims (fused transpose)
        dn = (((1,), (1,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(a, b, dn,
                                        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _store():
        out = acc_ref[...]
        bias_blk = bias_ref[...] if bias_ref is not None else None
        out = apply_epilogue(out, epilogue, bias_blk)
        o_ref[...] = out.astype(out_dtype)


def build_gemm_kernel(*, m: int, n: int, k: int, bm: int, bn: int, bk: int,
                      layout: str = "nn", epilogue: Optional[str] = None,
                      accumulate: bool = False, in_dtype=jnp.float32,
                      out_dtype=jnp.float32, interpret: bool = False):
    """Generate the shape-specialized pallas_call for one GEMM region.

    Returns a function ``f(a, b, [bias], [c_in]) -> out`` of exact shapes
    ``a:(m,k)``, ``b:(k,n)|(n,k)``, ``out:(m,n)``.  All metadata is
    hardwired at build time (the LIBXSMM JIT analogue).
    """
    grid_m, grid_n, grid_k = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    k_rem = k % bk

    body = functools.partial(
        _gemm_kernel_body, layout=layout, k_steps=grid_k, k_rem=k_rem,
        bk=bk, epilogue=epilogue, accumulate=accumulate,
        out_dtype=jnp.dtype(out_dtype))

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)) if layout == "nn"
        else pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
    ]
    if needs_bias(epilogue):
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if accumulate:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))

    kernel = pl.pallas_call(
        body,
        name="gemm",
        grid=(grid_m, grid_n, grid_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )

    def run(a, b, bias=None, c_in=None):
        args = [a, b]
        if needs_bias(epilogue):
            assert bias is not None
            args.append(bias.reshape(1, n))
        if accumulate:
            assert c_in is not None
            args.append(c_in)
        return kernel(*args)

    return run


# ---------------------------------------------------------------------------
# Fused single-launch plan execution (DESIGN.md §8/§9)
# ---------------------------------------------------------------------------

def _fused_kernel_body(tbl_ref, *refs, schedule, layout, epilogue,
                       accumulate, out_dtype, quant=None):
    """Walk the flattened tile schedule: one grid step = one (tile, K-panel).

    refs: a, b, [sa], [sb], [bias], [c_in], out, acc_scratch — each a full
    per-batch operand block.  The tile table rides in scalar-prefetch
    SMEM; per-tile geometry is selected by ``lax.switch`` over the
    distinct effective block shapes, and every load/store is the paper's
    two-step path: a fixed-shape window at a clamped origin plus an
    ownership mask (the predication helpers of ``repro.core.schedule``,
    DESIGN.md §9).

    Under a ``quant`` spec (DESIGN.md §13) the operands arrive in the
    wire dtype, accumulation is exact-wide (int32 for int8, f32 for fp8
    / weight-only), and ``sa``/``sb`` are the expanded f32 dequant
    vectors — column scales ``(1, n)`` and, for fully quantized runs, row
    scales ``(m, 1)`` — windowed by the same clamped tile origins as the
    operands and applied in :func:`apply_epilogue` before bias/act, so a
    quantized output never round-trips through a separate dequant launch.
    """
    weight_only = quant is not None and quant.weight_only
    full_quant = quant is not None and not quant.weight_only
    int_acc = full_quant and quant.dtype == "int8"
    acc_dt = jnp.int32 if int_acc else jnp.float32

    idx = 0
    a_ref = refs[idx]; idx += 1
    b_ref = refs[idx]; idx += 1
    sa_ref = sb_ref = None
    if full_quant:
        sa_ref = refs[idx]; idx += 1
    if quant is not None:
        sb_ref = refs[idx]; idx += 1
    bias_ref = None
    if needs_bias(epilogue):
        bias_ref = refs[idx]; idx += 1
    c_ref = None
    if accumulate:
        c_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]

    k, bk, k_steps = schedule.k, schedule.bk, schedule.k_steps
    t = pl.program_id(1)
    ks = pl.program_id(2)
    row0, col0 = tbl_ref[t, 0], tbl_ref[t, 1]
    row_end, col_end = tbl_ref[t, 2], tbl_ref[t, 3]
    # Window origins come from SMEM; the schedule proves their alignment,
    # which Mosaic needs to lower the sliced loads and stores.
    rs = pl.multiple_of(tbl_ref[t, 4], schedule.row_align)
    cs = pl.multiple_of(tbl_ref[t, 5], schedule.col_align)

    k0, kstart = schedule.k_window(ks)  # two-step K load (tail)
    kstart = pl.multiple_of(kstart, schedule.k_align)

    def make_branch(bm_e, bn_e):
        def branch():
            @pl.when(ks == 0)
            def _init():
                if accumulate:
                    cw = c_ref[0, pl.ds(rs, bm_e), pl.ds(cs, bn_e)]
                    acc_ref[0:bm_e, 0:bn_e] = cw.astype(jnp.float32)
                else:
                    acc_ref[0:bm_e, 0:bn_e] = jnp.zeros((bm_e, bn_e),
                                                        acc_dt)

            a = a_ref[0, pl.ds(rs, bm_e), pl.ds(kstart, bk)]
            if layout == "nn":
                b = b_ref[0, pl.ds(kstart, bk), pl.ds(cs, bn_e)]
                dn = (((1,), (0,)), ((), ()))
                b_k_dim = 0
            else:  # nt: B window is (bn_e, bk); contract minor dims
                b = b_ref[0, pl.ds(cs, bn_e), pl.ds(kstart, bk)]
                dn = (((1,), (1,)), ((), ()))
                b_k_dim = 1
            if weight_only:
                # W8A16: int8 weight values are exactly representable in
                # the wide dtype; the column scales stay in the epilogue.
                b = b.astype(a.dtype)
            if k % bk:
                # K-tail predication: the last window overlaps the
                # previous panel or the staged padding; keep only lanes
                # in [k0, k) (repro.core.schedule.k_tail_mask).
                a = k_tail_mask(a, 1, k0, kstart, k)
                b = k_tail_mask(b, b_k_dim, k0, kstart, k)
            acc_ref[0:bm_e, 0:bn_e] += jax.lax.dot_general(
                a, b, dn, preferred_element_type=acc_dt)

            @pl.when(ks == k_steps - 1)
            def _store():
                out = acc_ref[0:bm_e, 0:bn_e]
                dequant = None
                if sb_ref is not None:
                    dequant = sb_ref[0:1, pl.ds(cs, bn_e)]
                    if sa_ref is not None:
                        dequant = sa_ref[pl.ds(rs, bm_e), 0:1] * dequant
                bias_blk = None
                if bias_ref is not None:
                    bias_blk = bias_ref[0:1, pl.ds(cs, bn_e)]
                out = apply_epilogue(out, epilogue, bias_blk, dequant)
                out = out.astype(out_dtype)
                # Predicated two-step store: write only the elements this
                # tile owns, preserving neighbours under the clamped
                # window (each C element is owned by exactly one tile).
                own = ownership_mask((bm_e, bn_e), rs, cs,
                                     row0, row_end, col0, col_end)
                predicated_store(
                    o_ref, (0, pl.ds(rs, bm_e), pl.ds(cs, bn_e)), out, own)
        return branch

    branches = [make_branch(bm_e, bn_e) for bm_e, bn_e in schedule.blocks]
    if len(branches) == 1:
        branches[0]()
    else:
        jax.lax.switch(tbl_ref[t, 6], branches)


def build_fused_gemm_kernel(*, schedule, batch: int = 0, layout: str = "nn",
                            epilogue: Optional[str] = None,
                            accumulate: bool = False, in_dtype=jnp.float32,
                            out_dtype=jnp.float32, interpret: bool = False,
                            quant=None):
    """Generate ONE pallas_call executing a whole blocking plan + batch.

    ``schedule`` is a :class:`repro.core.blocking.TileSchedule`.  Returns
    ``f(a, b, [bias], [c_in], [sa], [sb]) -> out`` over rank-3 operands
    ``a:(nb,m,k)``, ``b:(nb,k,n)|(nb,n,k)``, ``out:(nb,m,n)`` with
    ``nb = max(1, batch)`` — the batch is a leading grid dimension, not a
    ``vmap``.  The supergrid is ``(batch, tiles, k_steps)``; the tile
    table travels as a scalar-prefetch operand (DESIGN.md §8).

    With a :class:`~repro.core.descriptor.QuantSpec` ``quant``, the
    operand dtypes are the wire format, the accumulator scratch is int32
    (int8) or f32 (fp8 / weight-only), and the expanded dequant vectors
    ride as extra operands — ``sa: (m, 1)`` row scales (fully-quantized
    runs only) and ``sb: (1, n)`` column scales — fused into the epilogue
    (DESIGN.md §13).
    """
    m, n = schedule.m, schedule.n
    # Operands are staged at the schedule's padded extents: the blocks
    # overhang ragged operands so clamped windows keep aligned origins.
    m_p, n_p, k_p = schedule.m_p, schedule.n_p, schedule.k_p
    nb = max(1, batch)
    has_bias = needs_bias(epilogue)
    has_sa = quant is not None and not quant.weight_only
    has_sb = quant is not None
    int_acc = has_sa and quant.dtype == "int8"
    bm_max = max(b[0] for b in schedule.blocks)
    bn_max = max(b[1] for b in schedule.blocks)
    table = pack_table(schedule.tiles)  # (tiles, 8) int32, trace-time

    body = functools.partial(
        _fused_kernel_body, schedule=schedule, layout=layout,
        epilogue=epilogue, accumulate=accumulate,
        out_dtype=jnp.dtype(out_dtype), quant=quant)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, t, ks, tbl: (0,) * len(shape))

    def per_batch(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b, t, ks, tbl: (b,) + (0,) * len(shape))

    in_specs = [per_batch(m_p, k_p),
                per_batch(k_p, n_p) if layout == "nn" else per_batch(n_p, k_p)]
    if has_sa:
        in_specs.append(whole(m_p, 1))
    if has_sb:
        in_specs.append(whole(1, n_p))
    if has_bias:
        in_specs.append(whole(1, n_p))
    if accumulate:
        in_specs.append(per_batch(m_p, n_p))

    acc_dtype = jnp.int32 if int_acc else jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the tile table
        grid=(nb, schedule.num_tiles, schedule.k_steps),
        in_specs=in_specs,
        out_specs=per_batch(m_p, n_p),
        scratch_shapes=[pltpu.VMEM((bm_max, bn_max), acc_dtype)],
    )
    in_isz = jnp.dtype(in_dtype).itemsize
    need = matmul_vmem_need(
        m_p, n_p, k_p, a_isz=quant.wire_itemsize if has_sa else in_isz,
        b_isz=quant.wire_itemsize if has_sb else in_isz,
        out_isz=jnp.dtype(out_dtype).itemsize, acc=(bm_max, bn_max),
        layout=layout, accumulate=accumulate, row_scales=has_sa,
        col_rows=int(has_sb) + int(has_bias))

    kernel = pl.pallas_call(
        body,
        name="gemm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, m, n), jnp.dtype(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )

    def run(a, b, bias=None, c_in=None, sa=None, sb=None):
        if m < 8:
            # XLA stores an array of fewer than 8 rows in narrower tiles,
            # whose packed values Mosaic cannot mask (the K tail): stage
            # A as whole register tiles instead.
            a = jnp.pad(a, ((0, 0), (0, m_p - m), (0, 0)))
        args = [table, a, b]
        if has_sa:
            assert sa is not None
            args.append(sa.reshape(m, 1).astype(jnp.float32))
        if has_sb:
            assert sb is not None
            args.append(sb.reshape(1, n).astype(jnp.float32))
        if has_bias:
            assert bias is not None
            args.append(bias.reshape(1, n))
        if accumulate:
            assert c_in is not None
            args.append(c_in)
        return kernel(*args)

    return run
