"""Blocked GEMM — the engine's founding kernel family.

Executes a :class:`repro.core.blocking.BlockingPlan` one of two ways
(DESIGN.md §8):

  * **fused** (``plan.fused``, the paper's §IV stance): the whole plan —
    every region's tile grid *and* the batch — runs in ONE
    ``pallas_call``.  The plan's flattened :meth:`tile_schedule` rides in
    a scalar-prefetch table; the kernel walks a ``(batch, tiles, k)``
    supergrid, selects per-region block geometry by static table, and
    writes each tile straight into the real output buffer with predicated
    two-step stores.  No ``dynamic_slice`` operand copies, no ``zeros`` +
    ``dynamic_update_slice`` assembly, no ``vmap``.
  * **multi-launch** (the pre-fusion lowering, kept for VMEM-oversized
    problems and as the autotuner's alternative): each plan region becomes
    one shape-specialized ``pallas_call`` (the paper's "seven microkernel
    executions", Fig 7) whose outputs are stitched into C with
    ``dynamic_update_slice``; batch goes through ``jax.vmap``.

Which path runs is ``config.fused`` ("auto" follows the plan bit that the
planner/autotuner set; "on"/"off" force it).  Both paths report traced
launch counts through ``engine.count_launches`` → ``engine.stats()``.

Registered with :mod:`repro.core.engine` as family ``"gemm"``: planning,
caching (plan and kernel layers, descriptor-derived keys) and interpret
policy all live in the engine; this module owns only the lowering.

Edge strategies for the multi-launch path (benchmarked in fig45_alignment):

  * ``mask`` — exact-shape kernels; Pallas clips partial output blocks and
    the kernel masks the K tail (the SME predication analogue);
  * ``pad``  — operands zero-padded to block multiples outside the kernel
    (the copy-based strategy the paper's predication avoids).

The fused path subsumes both: masking is inherent to its tile schedule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.blocking import BlockingPlan, plan_gemm, round_up
from repro.core.schedule import plan_launches
from repro.core.descriptor import GemmDescriptor, check_bias
from repro.kernels.gemm.kernel import (build_fused_gemm_kernel,
                                       build_gemm_kernel)


def _region_executor(desc: GemmDescriptor, region, bk: int, edge: str,
                     interpret: bool):
    """Build (and cache) the kernel for one plan region."""
    rows, cols, k = region.rows, region.cols, desc.k
    bm, bn = region.bm, region.bn
    if edge == "pad":
        rows_p, cols_p, k_p = round_up(rows, bm), round_up(cols, bn), round_up(k, bk)
    else:
        rows_p, cols_p, k_p = rows, cols, k
    # Key on the region build inputs only — NOT the whole-problem (m, n)
    # — so descriptors of different shapes share identical region/corner
    # kernels (the cross-shape reuse the kernel cache exists for).
    key = (desc.family, "region", rows_p, cols_p, k_p, bm, bn, bk,
           desc.layout, desc.epilogue, desc.accumulate, desc.in_dtype,
           desc.out_dtype, interpret)

    def builder():
        # bk clamps to the (padded) K extent: tiny-K builds must not stage
        # oversized K panels (k_p is already bk-aligned under "pad").
        return build_gemm_kernel(
            m=rows_p, n=cols_p, k=k_p, bm=bm, bn=bn, bk=min(bk, k_p),
            layout=desc.layout, epilogue=desc.epilogue,
            accumulate=desc.accumulate,
            in_dtype=jnp.dtype(desc.in_dtype), out_dtype=jnp.dtype(desc.out_dtype),
            interpret=interpret)

    kernel = engine.build_cached(key, builder)

    def run(a_r, b_r, bias_r, c_r):
        if edge == "pad":
            a_r = jnp.pad(a_r, ((0, rows_p - rows), (0, k_p - k)))
            if desc.layout == "nn":
                b_r = jnp.pad(b_r, ((0, k_p - k), (0, cols_p - cols)))
            else:
                b_r = jnp.pad(b_r, ((0, cols_p - cols), (0, k_p - k)))
            if bias_r is not None:
                bias_r = jnp.pad(bias_r, ((0, cols_p - cols),))
            if c_r is not None:
                c_r = jnp.pad(c_r, ((0, rows_p - rows), (0, cols_p - cols)))
        out = kernel(a_r, b_r, bias_r, c_r)
        if edge == "pad" and (rows_p != rows or cols_p != cols):
            out = out[:rows, :cols]
        return out

    return run


def gemm_region(a, b, region, desc: GemmDescriptor, bk: int,
                bias=None, c=None, edge: str = "mask",
                interpret: Optional[bool] = None):
    """Run one region's microkernel on the corresponding operand slices."""
    if interpret is None:
        from repro.core.config import get_config, resolve_interpret
        interpret = resolve_interpret(get_config().interpret)
    r = region
    a_r = jax.lax.dynamic_slice(a, (r.row0, 0), (r.rows, desc.k))
    if desc.layout == "nn":
        b_r = jax.lax.dynamic_slice(b, (0, r.col0), (desc.k, r.cols))
    else:
        b_r = jax.lax.dynamic_slice(b, (r.col0, 0), (r.cols, desc.k))
    bias_r = None
    if bias is not None:
        bias_r = jax.lax.dynamic_slice(bias, (r.col0,), (r.cols,))
    c_r = None
    if c is not None:
        c_r = jax.lax.dynamic_slice(c, (r.row0, r.col0), (r.rows, r.cols))
    run = _region_executor(desc, r, bk, edge, interpret)
    return run(a_r, b_r, bias_r, c_r)


def _gemm2d(a, b, plan: BlockingPlan, bias, c, interpret: bool):
    desc = plan.desc
    if len(plan.regions) == 1 and plan.regions[0].rows == desc.m \
            and plan.regions[0].cols == desc.n:
        return gemm_region(a, b, plan.regions[0], desc, plan.bk,
                           bias, c, desc.edge, interpret)
    out = jnp.zeros((desc.m, desc.n), jnp.dtype(desc.out_dtype))
    for r in plan.regions:
        blk = gemm_region(a, b, r, desc, plan.bk, bias, c, desc.edge, interpret)
        out = jax.lax.dynamic_update_slice(out, blk, (r.row0, r.col0))
    return out


def _fused_executor(desc: GemmDescriptor, plan: BlockingPlan,
                    interpret: bool):
    """Build (and cache) the single fused kernel for a whole plan.

    ``(regions, bk)`` fully determine the tile schedule, so the cache key
    stays O(regions) and the O(tiles) flattening only runs on a miss.
    ``desc.edge`` is normalized out: it selects between multi-launch edge
    strategies and the fused kernel ignores it (masking is inherent).
    """
    key = (dataclasses.replace(desc, edge="mask").cache_key()
           + ("fused", plan.regions, plan.bk, interpret))

    def builder():
        return build_fused_gemm_kernel(
            schedule=plan.tile_schedule(), batch=desc.batch,
            layout=desc.layout, epilogue=desc.epilogue,
            accumulate=desc.accumulate, in_dtype=jnp.dtype(desc.in_dtype),
            out_dtype=jnp.dtype(desc.out_dtype), interpret=interpret,
            quant=desc.quant)

    return engine.build_cached(key, builder)


def _xla_quant_gemm(desc: GemmDescriptor, a, b, bias, sa, sb):
    """The pre-quant fallback lowering: one XLA dot in the exact-wide
    accumulator dtype, dequant + epilogue as jnp ops (DESIGN.md §13).

    This is what "a separate dequant launch" looks like — the path the
    fused kernel exists to beat — kept as the non-fused lowering and
    autotune candidate.  int32 accumulation is exact, and the dequant /
    bias / activation ops match :func:`apply_epilogue` term for term, so
    for int8 this is bit-identical to the fused kernel.
    """
    from repro.kernels.epilogue import apply_epilogue
    q = desc.quant
    dn = (((1,), (0,)), ((), ())) if desc.layout == "nn" \
        else (((1,), (1,)), ((), ()))
    if q.weight_only:
        acc = jax.lax.dot_general(a, b.astype(a.dtype), dn,
                                  preferred_element_type=jnp.float32)
        factor = sb.reshape(1, desc.n).astype(jnp.float32)
    else:
        pref = jnp.int32 if q.dtype == "int8" else jnp.float32
        acc = jax.lax.dot_general(a, b, dn, preferred_element_type=pref)
        factor = (sa.reshape(desc.m, 1).astype(jnp.float32)
                  * sb.reshape(1, desc.n).astype(jnp.float32))
    bias_blk = None if bias is None else bias.reshape(1, desc.n)
    out = apply_epilogue(acc, desc.epilogue, bias_blk, factor)
    return out.astype(jnp.dtype(desc.out_dtype))


def execute(desc: GemmDescriptor, plan: BlockingPlan, a, b, *,
            bias=None, c=None, sa=None, sb=None,
            interpret: bool = False) -> jax.Array:
    """Engine executor: run one planned (possibly batched) GEMM.

    ``sa``/``sb`` are the expanded f32 dequant vectors of a quantized
    descriptor (``(m,)`` row scales for fully-quantized runs, ``(n,)``
    column scales for any quant spec) — the public entry point quantized
    the operands and expanded the scheme-shaped scales before dispatch.
    """
    check_bias(desc.epilogue, bias)
    if desc.quant is not None:
        if engine.resolve_fused(plan):
            engine.count_launches("gemm", plan_launches(plan, fused=True),
                                  fused=True)
            run = _fused_executor(desc, plan, interpret)
            return run(a[None], b[None], bias, None, sa=sa, sb=sb)[0]
        # The pre-quant path: no pallas_call at all — quantized operands,
        # one XLA dot, dequant+epilogue as separate jnp ops.
        engine.count_launches("gemm", 0)
        return _xla_quant_gemm(desc, a, b, bias, sa, sb)
    if engine.resolve_fused(plan):
        engine.count_launches("gemm", plan_launches(plan, fused=True),
                              fused=True)
        run = _fused_executor(desc, plan, interpret)
        if desc.batch:
            out = run(a, b, bias, c)
        else:
            out = run(a[None], b[None], bias,
                      None if c is None else c[None])
            out = out[0]
        return out
    engine.count_launches("gemm", plan_launches(plan, fused=False))
    f = functools.partial(_gemm2d, plan=plan, interpret=interpret)
    if desc.batch:
        def batched(a_, b_, c_):
            return f(a_, b_, bias=bias, c=c_)
        return jax.vmap(batched, in_axes=(0, 0, 0 if c is not None else None))(a, b, c)
    return f(a, b, bias=bias, c=c)


engine.register_family("gemm", planner=plan_gemm, execute=execute)


def gemm(a, b, c: Optional[jax.Array] = None, *, layout: str = "nn",
         epilogue: Optional[str] = None, bias: Optional[jax.Array] = None,
         out_dtype=None, edge: str = "mask", plan: Optional[BlockingPlan] = None,
         heterogeneous: bool = True, fused: Optional[bool] = None,
         quant=None) -> jax.Array:
    """Planned, shape-specialized (batched) GEMM via the engine.

    ``a``: (..., M, K); ``b``: (..., K, N) for layout "nn" or (..., N, K)
    for "nt"; optional ``c`` accumulator of shape (..., M, N).  Interpret
    policy comes from :mod:`repro.core.config`; ``fused=True/False`` pins
    the single-launch vs multi-launch lowering for this call (default:
    follow config + plan, DESIGN.md §8).

    ``quant`` selects the low-precision axis (DESIGN.md §13): a
    :class:`~repro.core.descriptor.QuantSpec`, a shorthand string
    (``"int8"``/``"w8a16"``/``"fp8"``), ``False`` to opt out of an
    ambient ``config.quant``, or ``None`` to follow the config.  Wide
    operands are quantized here at dispatch; alternatively ``b`` may be a
    pre-quantized :class:`~repro.optim.compression.QuantizedTensor`
    (the quantize-once-at-load W8A16 path), whose spec then wins.
    """
    from repro.optim.compression import (QuantizedTensor, expand_scale,
                                         quantize_operand)
    sa = sb = None
    spec = None
    if isinstance(b, QuantizedTensor):
        # Quantized-at-load weights: always weight-only — A stays wide.
        spec = dataclasses.replace(b.spec, weight_only=True)
        n_axis = 1 if layout == "nn" else 0
        if b.axis % b.ndim != n_axis:
            raise ValueError(
                f"QuantizedTensor b is quantized along axis {b.axis}, but "
                f"layout {layout!r} needs output-column (axis {n_axis}) "
                f"scales for the dequant to commute through the GEMM")
        sb = expand_scale(b.scale, b.spec, b.shape[n_axis])
        b = b.q
    else:
        from repro.core.config import get_config
        from repro.core.descriptor import resolve_quant
        spec = resolve_quant(get_config().quant if quant is None else quant)
        if spec is not None:
            if a.ndim != 2:
                raise ValueError("quantized GEMM is unbatched; flatten "
                                 "leading dims first")
            out_dtype = out_dtype or a.dtype
            b, sb = quantize_operand(b, spec,
                                     axis=1 if layout == "nn" else 0)
            if not spec.weight_only:
                a, sa = quantize_operand(a, spec, axis=0)
    desc = GemmDescriptor.from_operands(
        a, b, layout=layout, accumulate=c is not None, epilogue=epilogue,
        out_dtype=out_dtype or a.dtype, edge=edge, quant=spec)
    if plan is None and not heterogeneous:
        # Non-default planner knob: plan directly, bypassing the plan cache
        # (the cache serves only the canonical planner configuration).
        plan = plan_gemm(desc, heterogeneous=False)
    if fused is None:
        return engine.dispatch(desc, a, b, plan=plan, bias=bias, c=c,
                               sa=sa, sb=sb)
    from repro.core.config import use
    with use(fused="on" if fused else "off"):
        return engine.dispatch(desc, a, b, plan=plan, bias=bias, c=c,
                               sa=sa, sb=sb)
