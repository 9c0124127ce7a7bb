"""Flash-attention family: engine-planned block sizes, engine-cached build.

Executes a :class:`repro.core.blocking.FlashPlan` one of two ways,
resolved by ``engine.resolve_fused`` exactly as for dense GEMM
(DESIGN.md §10):

  * **fused** (``plan.fused``, default whenever the staged operands fit
    VMEM): the plan's causal-aware
    :class:`~repro.core.schedule.FlashTileSchedule` drops fully-masked
    k-blocks at plan time and ONE ``pallas_call`` walks the surviving
    tiles over a ``(batch_heads, tiles)`` supergrid, with the
    online-softmax carry threaded through the walk as accumulator state;
  * **dense grid** (the pre-schedule lowering, kept for VMEM-oversized
    problems and as the autotuner's alternative): a
    ``(b*h, q_blocks, k_blocks)`` grid that branches masked causal tiles
    away at run time but still pays their grid steps.

``block_q``/``block_k`` default to the machine-model-driven plan
(:func:`repro.core.blocking.plan_flash`); explicit values pin the plan
(benchmark sweeps, tests).  Both paths report traced launch counts
through ``engine.count_launches`` → ``engine.stats()``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.blocking import (FlashDecodePlan, FlashPlan,
                                 flash_bwd_fused_legal, plan_flash,
                                 plan_flash_bwd, plan_flash_decode)
from repro.core.config import get_config, resolve_interpret
from repro.core.descriptor import (FlashBwdDescriptor, FlashDecodeDescriptor,
                                   FlashDescriptor)
from repro.core.machine import canonical_dtype
from repro.core.schedule import plan_launches
from repro.kernels.flash_attention.kernel import (NEG_INF,
                                                  build_decode_flash_kernel,
                                                  build_flash_kernel,
                                                  build_fused_flash_bwd_kernel,
                                                  build_fused_flash_kernel)


def _fused_executor(desc: FlashDescriptor, plan: FlashPlan, dtype,
                    interpret: bool):
    """Build (and cache) the single scheduled kernel for one flash plan.

    ``(block_q, block_k)`` fully determine the tile table, so the cache
    key stays O(1) and the O(tiles) flattening only runs on a miss."""
    key = desc.cache_key() + ("fused", plan.block_q, plan.block_k, interpret)
    return engine.build_cached(key, lambda: build_fused_flash_kernel(
        schedule=plan.tile_schedule(), batch_heads=desc.batch_heads,
        d=desc.d, dtype=dtype, interpret=interpret))


def execute(desc: FlashDescriptor, plan: FlashPlan, qf, kf, vf, *,
            interpret: bool = False) -> jax.Array:
    """Engine executor: run one planned flash attention forward."""
    fused = engine.resolve_fused(plan)
    engine.count_launches("flash_attention", plan_launches(plan, fused),
                          fused=fused)
    if fused:
        return _fused_executor(desc, plan, qf.dtype, interpret)(qf, kf, vf)
    key = desc.cache_key() + ("kernel", plan.block_q, plan.block_k, interpret)
    kernel = engine.build_cached(key, lambda: build_flash_kernel(
        batch_heads=desc.batch_heads, sq=desc.sq, sk=desc.sk, d=desc.d,
        block_q=plan.block_q, block_k=plan.block_k, causal=desc.causal,
        dtype=qf.dtype, interpret=interpret))
    return kernel(qf, kf, vf)


engine.register_family("flash_attention", planner=plan_flash, execute=execute)


# ---------------------------------------------------------------------------
# Backward family (DESIGN.md §11): ONE pallas_call walks the forward's
# causal-pruned tile table, producing dQ/dK/dV
# ---------------------------------------------------------------------------

def execute_bwd(desc: FlashBwdDescriptor, plan: FlashPlan, qf, kf, vf, o, do,
                lse, *, interpret: bool = False):
    """Engine executor: run one planned flash attention backward.

    Single lowering — the scheduled walk; illegal descriptors never reach
    the engine (the custom VJP falls back to reference autodiff first).
    """
    engine.count_launches("flash_attention_bwd", 1)
    key = desc.cache_key() + ("fused", plan.block_q, plan.block_k, interpret)
    kernel = engine.build_cached(key, lambda: build_fused_flash_bwd_kernel(
        schedule=plan.tile_schedule(), batch_heads=desc.batch_heads,
        d=desc.d, dtype=qf.dtype, interpret=interpret))
    return kernel(qf, kf, vf, o, do, lse)


engine.register_family("flash_attention_bwd", planner=plan_flash_bwd,
                       execute=execute_bwd)


# ---------------------------------------------------------------------------
# Paged decode family (DESIGN.md §12): ONE pallas_call per decode step,
# riding the runtime DecodeTileSchedule tables over live KV pages
# ---------------------------------------------------------------------------

def execute_decode(desc: FlashDecodeDescriptor, plan: FlashDecodePlan,
                   q, k_pool, v_pool, block_tables, lengths, *,
                   k_scale=None, v_scale=None, interpret: bool = False):
    """Engine executor: run one planned paged decode-attention step.

    The kernel is cached on the static pool geometry alone; the batch
    composition (block tables + lengths) becomes the runtime tile table,
    built with jnp ops at trace time and shipped as a scalar-prefetch
    operand — so a churning batch re-enters the same compiled launch.
    KV-int8 pools (DESIGN.md §13) ride the same launch: per-token scale
    rows ``(pages, page_size)`` join as two extra table-indexed operands.
    """
    engine.count_launches("flash_decode", 1, fused=True)
    kv_quant = k_scale is not None
    schedule = plan.tile_schedule()
    key = desc.cache_key() + ("decode", canonical_dtype(k_pool.dtype),
                              kv_quant, interpret)
    kernel = engine.build_cached(key, lambda: build_decode_flash_kernel(
        schedule=schedule, num_heads=desc.num_heads,
        num_kv_heads=desc.num_kv_heads, head_dim=desc.head_dim,
        dtype=q.dtype, kv_dtype=k_pool.dtype, kv_quant=kv_quant,
        interpret=interpret))
    table = schedule.tables(block_tables, lengths)
    if kv_quant:
        # One scale per score column (position-major, KV head minor).
        def per_column(sc):
            return jnp.repeat(sc.astype(jnp.float32), desc.num_kv_heads,
                              axis=1)
        return kernel(table, q, k_pool, v_pool, per_column(k_scale),
                      per_column(v_scale))
    return kernel(table, q, k_pool, v_pool)


engine.register_family("flash_decode", planner=plan_flash_decode,
                       execute=execute_decode)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           k_scale=None, v_scale=None) -> jax.Array:
    """One decode step against a paged KV pool (DESIGN.md §12).

    q: (S, h, hd) — one query row per decode slot; k_pool/v_pool:
    (pages, page_size, hkv, hd); block_tables: (S, max_blocks) int32 page
    ids; lengths: (S,) live KV length per slot (0 = inactive, output row
    is zeros).  Returns (S, h, hd).

    With int8 pools, ``k_scale``/``v_scale`` are the per-token dequant
    rows ``(pages, page_size)`` f32 (DESIGN.md §13) — same launch count,
    the scales fold into the score/PV algebra in-kernel.
    """
    desc = FlashDecodeDescriptor.from_operands(q, k_pool, block_tables)
    return engine.dispatch(desc, q, k_pool, v_pool, block_tables, lengths,
                           k_scale=k_scale, v_scale=v_scale)


def _flat_desc(causal, qf, kf) -> FlashDescriptor:
    return FlashDescriptor(batch_heads=qf.shape[0], sq=qf.shape[1],
                           sk=kf.shape[1], d=qf.shape[2], causal=causal,
                           dtype=canonical_dtype(qf.dtype))


def _ref_flat(causal, qf, kf, vf):
    """Pure-jnp reference over flattened (BH, s, d) operands — the
    differentiable oracle the VJP falls back to when the scheduled
    backward is not legal (and the gradient-parity baseline in tests)."""
    scale = qf.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", qf.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if causal:
        # Same convention as the kernels: kpos <= qpos, no diagonal offset.
        sq, sk = qf.shape[1], kf.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      vf.astype(jnp.float32)).astype(qf.dtype)


def _flash_dispatch(causal, qf, kf, vf):
    """The engine-dispatched forward on flattened operands (primal path)."""
    return engine.dispatch(_flat_desc(causal, qf, kf), qf, kf, vf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_vjp(causal, qf, kf, vf):
    """Differentiable flattened flash attention (custom VJP,
    DESIGN.md §11): forward = the engine-dispatched kernel; backward =
    the scheduled single-launch dQ/dK/dV walk when legal, reference-path
    autodiff otherwise."""
    return _flash_dispatch(causal, qf, kf, vf)


def _flash_vjp_fwd(causal, qf, kf, vf):
    cfg = get_config()
    desc = _flat_desc(causal, qf, kf)
    bdesc = FlashBwdDescriptor.from_forward(desc)
    fused_ok = (cfg.fused != "off"
                and flash_bwd_fused_legal(bdesc, cfg.machine_model))
    if fused_ok:
        plan = engine.plan_for(desc)
        fused_ok = engine.resolve_fused(plan)
    if not fused_ok:
        # Reference-path fallback: primal still runs the engine forward;
        # only the backward re-derives through the jnp reference.
        return _flash_dispatch(causal, qf, kf, vf), {"ref": (qf, kf, vf)}
    # Forward with the LSE rows drained for the backward walk — same
    # schedule, same online-softmax math as the primal fused kernel.
    interpret = resolve_interpret(cfg.interpret)
    key = desc.cache_key() + ("fused_lse", plan.block_q, plan.block_k,
                              interpret)
    kernel = engine.build_cached(key, lambda: build_fused_flash_kernel(
        schedule=plan.tile_schedule(), batch_heads=desc.batch_heads,
        d=desc.d, dtype=qf.dtype, interpret=interpret, return_lse=True))
    engine.count_launches("flash_attention", 1, fused=True)
    o, lse = kernel(qf, kf, vf)
    return o, {"fused": (qf, kf, vf, o, lse)}


def _flash_vjp_bwd(causal, res, g):
    if "fused" in res:
        qf, kf, vf, o, lse = res["fused"]
        bdesc = FlashBwdDescriptor.from_forward(_flat_desc(causal, qf, kf))
        dq, dk, dv = engine.dispatch(bdesc, qf, kf, vf, o, g, lse)
    else:
        qf, kf, vf = res["ref"]
        _, vjp = jax.vjp(functools.partial(_ref_flat, causal), qf, kf, vf)
        dq, dk, dv = vjp(g.astype(qf.dtype))
    return (dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype))


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    fused: Optional[bool] = None) -> jax.Array:
    """q/k/v: (b, s, h, d) -> (b, s, h, d).

    ``fused=True/False`` pins the scheduled single-launch vs dense-grid
    lowering for this call (default: follow config + plan, DESIGN.md §10).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    desc = FlashDescriptor.from_operands(q, k, causal=causal)
    plan = None
    if block_q is not None or block_k is not None:
        # Fill unpinned knobs from the (cached) engine plan.
        auto = engine.plan_for(desc)
        plan = FlashPlan(desc, block_q or auto.block_q,
                         block_k or auto.block_k, fused=auto.fused)
    if plan is None and fused is None:
        # Default path: differentiable — training flows through the
        # custom VJP onto the scheduled backward walk (DESIGN.md §11).
        out = _flash_vjp(causal, qf, kf, vf)
    elif fused is None:
        out = engine.dispatch(desc, qf, kf, vf, plan=plan)
    else:
        from repro.core.config import use
        with use(fused="on" if fused else "off"):
            out = engine.dispatch(desc, qf, kf, vf, plan=plan)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
