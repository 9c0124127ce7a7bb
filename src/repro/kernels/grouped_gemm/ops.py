"""Ragged grouped GEMM family (MoE expert compute).

Takes pre-sorted rows + group sizes and dispatches one of two lowerings
(DESIGN.md §9), resolved by ``engine.resolve_fused`` exactly as for
dense GEMM:

  * **fused** (``plan.fused``, default whenever the staged operands fit
    VMEM): the plan's :class:`~repro.core.schedule.GroupedTileSchedule`
    turns ``group_sizes`` into a runtime tile table and ONE
    ``pallas_call`` walks the ragged expert row-blocks directly —
    no pad-to-``t_padded`` intermediate, no ``out_padded[dest]``
    gather-back;
  * **pad/scatter** (the pre-schedule lowering, kept for VMEM-oversized
    problems and as the autotuner's alternative): pad each group to the
    row-block multiple, build the block→expert map, dispatch the static
    grid, gather the rows back out.

Epilogues (bias/gelu/silu/relu, per-expert bias of shape (E, N)) lower
through ``repro.kernels.epilogue`` on both paths.  Tile sizes
(bm, bk, bn) come from the engine's machine-model planner
(:func:`repro.core.blocking.plan_grouped`); explicit kwargs pin the plan.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.blocking import (GroupedGemmPlan, grouped_bwd_fused_legal,
                                 mesh_comm_events, plan_grouped,
                                 plan_grouped_bwd)
from repro.core.config import get_config
from repro.core.descriptor import (GroupedGemmBwdDescriptor,
                                   GroupedGemmDescriptor, MeshSpec,
                                   check_bias)
from repro.core.schedule import plan_launches
from repro.kernels.epilogue import apply_epilogue, needs_bias
from repro.kernels.grouped_gemm.kernel import (build_fused_grouped_bwd_kernel,
                                               build_fused_grouped_kernel,
                                               build_grouped_gemm_kernel)


def plan_groups(group_sizes: jax.Array, num_experts: int, bm: int,
                t_padded: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Row offsets per group after padding each group to a bm multiple.

    Returns (padded_offsets (E+1,), block_expert (nb,), nrows (1,)).
    All shapes static; values dynamic (runtime router output).
    """
    sizes = group_sizes.astype(jnp.int32)
    padded = ((sizes + bm - 1) // bm) * bm
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)])
    nb = t_padded // bm
    block_row = jnp.arange(nb, dtype=jnp.int32) * bm
    block_expert = jnp.clip(
        jnp.searchsorted(offsets, block_row, side="right") - 1,
        0, num_experts - 1).astype(jnp.int32)
    nrows = offsets[-1:].astype(jnp.int32)
    return offsets, block_expert, nrows


def scatter_rows(x_sorted_by_group, group_sizes, offsets, bm, t_padded):
    """Place each group's rows at its padded offset (zeros between)."""
    t, kdim = x_sorted_by_group.shape
    src_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes.astype(jnp.int32))])
    row = jnp.arange(t, dtype=jnp.int32)
    grp = jnp.clip(jnp.searchsorted(src_off, row, side="right") - 1,
                   0, group_sizes.shape[0] - 1)
    dest = offsets[grp] + (row - src_off[grp])
    out = jnp.zeros((t_padded, kdim), x_sorted_by_group.dtype)
    return out.at[dest].set(x_sorted_by_group), dest


def _execute_fused(desc: GroupedGemmDescriptor, plan: GroupedGemmPlan, x, w,
                   group_sizes, bias, interpret: bool,
                   sx=None, sw=None) -> jax.Array:
    """Single scheduled launch: runtime tables, direct ragged stores."""
    sched = plan.tile_schedule()
    table = sched.tables(group_sizes)
    key = desc.cache_key() + ("fused", sched.bm, sched.bk, sched.bn,
                              interpret)
    kernel = engine.build_cached(key, lambda: build_fused_grouped_kernel(
        schedule=sched, epilogue=desc.epilogue, in_dtype=x.dtype,
        out_dtype=jnp.dtype(desc.dtype), interpret=interpret,
        quant=desc.quant))
    return kernel(table, x, w, bias, sx=sx, sw=sw)


def _execute_padded(desc: GroupedGemmDescriptor, plan: GroupedGemmPlan, x, w,
                    group_sizes, bias, interpret: bool) -> jax.Array:
    """Pad/scatter lowering: pad groups to bm multiples, gather back."""
    bm, bk, bn = plan.bm, plan.bk, plan.bn
    t_padded = plan.t_padded
    offsets, block_expert, nrows = plan_groups(
        group_sizes, desc.num_experts, bm, t_padded)
    x_padded, dest = scatter_rows(x, group_sizes, offsets, bm, t_padded)

    key = desc.cache_key() + ("kernel", bm, bk, bn, interpret)
    kernel = engine.build_cached(key, lambda: build_grouped_gemm_kernel(
        t_padded=t_padded, k=desc.k, n=desc.n,
        num_experts=desc.num_experts, bm=bm, bk=bk, bn=bn,
        epilogue=desc.epilogue, in_dtype=x.dtype, out_dtype=x.dtype,
        interpret=interpret))
    out_padded = kernel(x_padded, w, block_expert, nrows, bias)
    # gather back to the caller's (sorted, unpadded) row order; rows past
    # sum(group_sizes) belong to no group -> zero (matches ref).
    total = jnp.sum(group_sizes.astype(jnp.int32))
    valid = (jnp.arange(desc.t, dtype=jnp.int32) < total)[:, None]
    return jnp.where(valid, out_padded[dest], 0).astype(x.dtype)


def _xla_quant_grouped(desc: GroupedGemmDescriptor, x, w, group_sizes,
                       bias, sx, sw) -> jax.Array:
    """Non-fused quant lowering: the XLA formulation.

    Quantized operands -> one exact-wide-accumulation contraction ->
    dequant + epilogue through the SAME :func:`apply_epilogue` the fused
    kernel calls, term for term — bit-identical for int8 (integer
    accumulation is exact under any tiling) and the parity oracle for
    tests.  No ``pallas_call``: counts zero launches.  The pad/scatter
    kernel stays wide-only (DESIGN.md §13).
    """
    q = desc.quant
    t = x.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    row = jnp.arange(t, dtype=jnp.int32)
    grp = jnp.clip(jnp.searchsorted(offsets, row, side="right") - 1,
                   0, group_sizes.shape[0] - 1)
    if q.weight_only:
        acc = jnp.einsum("tk,tkn->tn", x, w[grp].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        factor = sw[grp].astype(jnp.float32)
    else:
        pref = jnp.int32 if q.dtype == "int8" else jnp.float32
        acc = jnp.einsum("tk,tkn->tn", x, w[grp],
                         preferred_element_type=pref)
        factor = (sx.reshape(t, 1).astype(jnp.float32)
                  * sw[grp].astype(jnp.float32))
    out = apply_epilogue(acc, desc.epilogue,
                         None if bias is None else bias[grp], factor)
    valid = (row < offsets[-1])[:, None]
    return jnp.where(valid, out, 0).astype(jnp.dtype(desc.dtype))


def _execute_mesh(desc: GroupedGemmDescriptor, plan: GroupedGemmPlan, x4, w,
                  group_sizes, bias, interpret: bool) -> jax.Array:
    """Mesh execution (DESIGN.md §14): run the plan's strategy under
    ``shard_map`` over the descriptor's mesh axis.

    ``x4`` is the capacity-slot layout ``(n, e, cap, k)`` with the token
    group dim ``n`` sharded over the axis and ``w`` the ``(e, k, f)``
    expert bank sharded (or gathered) over its expert dim.  Both
    strategies reduce to the SAME per-shard local grouped call
    (``plan.local_desc`` with the plan's tiling knobs), so the fused
    single-launch property holds per shard:

      * **gathered** — ``w`` enters replicated (``P(None)``): any weight
        movement is XLA-implicit outside the engine, and the engine comm
        counters stay zero;
      * **distributed** — ``w`` stays expert-sharded and two explicit
        ``lax.all_to_all`` calls move the capacity slots to their
        expert's owner and back (the olmax ``all2all`` idiom), counted
        via ``engine.count_comm`` at trace time.
    """
    if desc.quant is not None:
        raise NotImplementedError("mesh grouped GEMM is wide-only")
    if bias is not None:
        raise NotImplementedError("mesh grouped GEMM has no bias path")
    from jax.sharding import PartitionSpec as P
    from repro.runtime.shardlib import current_mesh
    mesh = current_mesh()
    axis, s = desc.mesh.axis, desc.mesh.size
    if mesh is None or mesh.shape.get(axis, 0) != s:
        raise ValueError(f"descriptor mesh {desc.mesh} does not match the "
                         f"active device mesh {mesh}")
    comm = plan.comm or "gathered"
    local = plan.local_desc
    lplan = GroupedGemmPlan(local, plan.bm, plan.bk, plan.bn,
                            fused=plan.fused, plan_source=plan.plan_source)
    nt, e, cap, k = x4.shape
    f = desc.n
    e_loc = e // s

    def run_local(rows, w_loc, n_groups):
        sizes = jnp.full((n_groups,), rows.shape[0] // n_groups, jnp.int32)
        return execute(local, lplan, rows, w_loc, sizes, bias=None,
                       interpret=interpret)

    if comm == "gathered":
        def body(xl, w_full):
            nl = xl.shape[0]
            rows = xl.transpose(1, 0, 2, 3).reshape(e * nl * cap, k)
            y = run_local(rows, w_full, e)
            return y.reshape(e, nl, cap, f).transpose(1, 0, 2, 3)

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(None)),
                           out_specs=P(axis), check_vma=False)
        return fn(x4, w)

    events = mesh_comm_events(desc, "distributed")
    engine.count_comm("grouped_gemm", sum(b for _, b in events),
                      launches=len(events))

    def body(xl, w_loc):
        nl = xl.shape[0]
        # Slot tokens by owner shard: (s, nl, e_loc, cap, k), dim0 = the
        # destination; all_to_all turns dim0 into the SOURCE shard index.
        h = xl.reshape(nl, s, e_loc, cap, k).transpose(1, 0, 2, 3, 4)
        h = jax.lax.all_to_all(h, axis, split_axis=0, concat_axis=0)
        # Rows sorted by local expert, uniform s*nl*cap rows each.
        rows = h.transpose(2, 0, 1, 3, 4).reshape(e_loc * s * nl * cap, k)
        y = run_local(rows, w_loc, e_loc)
        # Inverse shuffle: back to (nl, e, cap, f) token-major layout.
        y = y.reshape(e_loc, s, nl, cap, f).transpose(1, 2, 0, 3, 4)
        y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
        return y.transpose(1, 0, 2, 3, 4).reshape(nl, e, cap, f)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return fn(x4, w)


def execute(desc: GroupedGemmDescriptor, plan: GroupedGemmPlan, x, w,
            group_sizes, *, bias=None, sx=None, sw=None,
            interpret: bool = False) -> jax.Array:
    if desc.mesh is not None:
        # Mesh descriptor (DESIGN.md §14): gathered / distributed
        # execution under shard_map; the operand layout is the 4-D
        # capacity-slot form (see expert_parallel_grouped_gemm).
        return _execute_mesh(desc, plan, x, w, group_sizes, bias, interpret)
    check_bias(desc.epilogue, bias)
    if desc.quant is not None:
        # Quantized axis (DESIGN.md §13): fused -> the scheduled walk in
        # the wire dtype with dequant in the epilogue; otherwise the XLA
        # formulation (zero engine launches).
        if engine.resolve_fused(plan):
            engine.count_launches("grouped_gemm",
                                  plan_launches(plan, fused=True),
                                  fused=True)
            return _execute_fused(desc, plan, x, w, group_sizes, bias,
                                  interpret, sx=sx, sw=sw)
        engine.count_launches("grouped_gemm", 0)
        return _xla_quant_grouped(desc, x, w, group_sizes, bias, sx, sw)
    fused = engine.resolve_fused(plan)
    engine.count_launches("grouped_gemm", plan_launches(plan, fused=fused),
                          fused=fused)
    if fused:
        return _execute_fused(desc, plan, x, w, group_sizes, bias, interpret)
    return _execute_padded(desc, plan, x, w, group_sizes, bias, interpret)


engine.register_family("grouped_gemm", planner=plan_grouped, execute=execute)


# ---------------------------------------------------------------------------
# Backward family (DESIGN.md §11): ONE pallas_call walks the same runtime
# tile tables producing dX and dW (and db) — never the pad/scatter path
# ---------------------------------------------------------------------------

def execute_bwd(desc: GroupedGemmBwdDescriptor, plan: GroupedGemmPlan, x, dy,
                w, group_sizes, *, interpret: bool = False):
    """Engine executor: run one planned grouped-GEMM backward.

    ``dy`` is the *pre-epilogue* cotangent (the custom VJP peels the
    activation chain off first).  Single lowering — the scheduled walk;
    illegal descriptors never reach the engine (the custom VJP falls back
    to reference autodiff first).
    """
    engine.count_launches("grouped_gemm_bwd", 1)
    sched = plan.tile_schedule()
    table = sched.tables(group_sizes)
    key = desc.cache_key() + ("fused", sched.bm, sched.bk, sched.bn,
                              interpret)
    kernel = engine.build_cached(key, lambda: build_fused_grouped_bwd_kernel(
        schedule=sched, with_db=needs_bias(desc.epilogue),
        in_dtype=x.dtype, interpret=interpret))
    return kernel(table, x, dy, w)


engine.register_family("grouped_gemm_bwd", planner=plan_grouped_bwd,
                       execute=execute_bwd)


_ACTIVATIONS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu,
                "relu": lambda p: jnp.maximum(p, 0)}


def _act_name(epilogue: Optional[str]) -> Optional[str]:
    """The activation half of an epilogue name (None when linear)."""
    if epilogue is None or epilogue == "bias":
        return None
    return epilogue.split("_")[-1]


def _ref_grouped(epilogue, x, w, group_sizes, bias):
    """Pure-jnp epilogue-aware reference — the differentiable oracle the
    VJP falls back to when the scheduled backward is not legal (and the
    gradient-parity baseline in tests).  Rows past ``sum(group_sizes)``
    are zero regardless of epilogue, matching both kernel lowerings."""
    t = x.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    row = jnp.arange(t, dtype=jnp.int32)
    grp = jnp.clip(jnp.searchsorted(offsets, row, side="right") - 1,
                   0, group_sizes.shape[0] - 1)
    out = jnp.einsum("tk,tkn->tn", x.astype(jnp.float32),
                     w.astype(jnp.float32)[grp])
    out = apply_epilogue(out, epilogue,
                         None if bias is None else bias[grp])
    valid = (row < offsets[-1])[:, None]
    return jnp.where(valid, out, 0).astype(x.dtype)


def _grouped_dispatch(epilogue, x, w, group_sizes, bias):
    """The engine-dispatched forward (primal path)."""
    desc = GroupedGemmDescriptor.from_operands(x, w, epilogue=epilogue)
    return engine.dispatch(desc, x, w, group_sizes, bias=bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_vjp(epilogue, x, w, group_sizes, bias):
    """Differentiable grouped GEMM (custom VJP, DESIGN.md §11): forward =
    the engine-dispatched kernel; backward = the scheduled single-launch
    dX/dW walk over the same runtime tile tables when legal,
    reference-path autodiff otherwise."""
    return _grouped_dispatch(epilogue, x, w, group_sizes, bias)


def _grouped_vjp_fwd(epilogue, x, w, group_sizes, bias):
    cfg = get_config()
    desc = GroupedGemmDescriptor.from_operands(x, w, epilogue=epilogue)
    bdesc = GroupedGemmBwdDescriptor.from_forward(desc)
    fused_ok = (cfg.fused != "off"
                and grouped_bwd_fused_legal(bdesc, cfg.machine_model))
    out = engine.dispatch(desc, x, w, group_sizes, bias=bias)
    # Residual dict keys are pytree *structure* — the backward branch is
    # resolved at trace time, not with traced booleans.
    res = {"fused" if fused_ok else "ref": (x, w, group_sizes, bias)}
    return out, res


def _grouped_vjp_bwd(epilogue, res, g):
    if "fused" in res:
        x, w, group_sizes, bias = res["fused"]
        dpre = g.astype(jnp.float32)
        act = _act_name(epilogue)
        if act is not None:
            # Peel the activation off the chain: recompute the
            # pre-activation via the engine forward with the activation
            # stripped from the epilogue, then pull ``g`` through the
            # activation alone.  What remains (``dpre``) is the cotangent
            # of x @ w (+ bias), which the scheduled walk consumes — the
            # same quantity db sums per expert.
            biased = needs_bias(epilogue)
            pre = _grouped_dispatch("bias" if biased else None, x, w,
                                    group_sizes, bias if biased else None)
            _, act_vjp = jax.vjp(
                lambda p: _ACTIVATIONS[act](p.astype(jnp.float32)), pre)
            dpre = act_vjp(dpre)[0]
        bdesc = GroupedGemmBwdDescriptor.from_forward(
            GroupedGemmDescriptor.from_operands(x, w, epilogue=epilogue))
        grads = engine.dispatch(bdesc, x, dpre, w, group_sizes)
        dx, dw = grads[0], grads[1]
        db = grads[2].astype(bias.dtype) if needs_bias(epilogue) else None
    else:
        x, w, group_sizes, bias = res["ref"]
        if bias is None:
            _, vjp = jax.vjp(
                lambda x_, w_: _ref_grouped(epilogue, x_, w_, group_sizes,
                                            None), x, w)
            (dx, dw), db = vjp(g.astype(x.dtype)), None
        else:
            _, vjp = jax.vjp(
                lambda x_, w_, b_: _ref_grouped(epilogue, x_, w_,
                                                group_sizes, b_), x, w, bias)
            dx, dw, db = vjp(g.astype(x.dtype))
    return (dx.astype(x.dtype), dw.astype(w.dtype), None, db)


_grouped_vjp.defvjp(_grouped_vjp_fwd, _grouped_vjp_bwd)


def _quantize_grouped_w(w, spec):
    """Per-expert quantization of the (E, K, N) bank along output columns.

    Every expert panel gets its own scales (the schemes resolve per
    expert: per_tensor -> one scalar each, per_channel -> per output
    column, per_tile -> per 128-column block), expanded dense so the
    kernel stages one ``(E, N)`` f32 scale table indexed by the tile
    table's expert column.
    """
    from repro.optim.compression import quantize_operand
    wq, sw = jax.vmap(lambda wi: quantize_operand(wi, spec, axis=1))(w)
    return wq, sw


def grouped_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                 epilogue: Optional[str] = None,
                 bias: Optional[jax.Array] = None,
                 bm: Optional[int] = None, bk: Optional[int] = None,
                 bn: Optional[int] = None,
                 fused: Optional[bool] = None,
                 quant=None) -> jax.Array:
    """Ragged grouped GEMM via the engine.

    x: (T, K) rows sorted by group; w: (E, K, N); group_sizes: (E,)
    (dynamic, sum <= T).  Returns (T, N): row i multiplied by its group's
    weight; rows beyond sum(group_sizes) are zero.  ``epilogue`` fuses the
    GEMM tail (``bias`` is per-expert, shape (E, N)); ``fused=True/False``
    pins the scheduled single-launch vs pad/scatter lowering for this
    call (default: follow config + plan, DESIGN.md §9).

    ``quant`` selects the low-precision axis (DESIGN.md §13): a spec /
    alias ("int8", "w8a16", "fp8") quantizes at dispatch — the expert
    bank per expert along output columns, the activations per row for
    fully-quantized specs — with dequant fused into the epilogue.
    ``quant=False`` opts this call out of an ambient ``config.quant``.
    The quant path is inference-only (no custom VJP; the wide path keeps
    the scheduled backward).
    """
    from repro.core.descriptor import resolve_quant
    spec = resolve_quant(get_config().quant if quant is None else quant)
    sx = sw = None
    if spec is not None:
        # Descriptor from the *wide* operands: desc.dtype stays the
        # logical compute/output dtype, the spec implies wire dtypes.
        desc = GroupedGemmDescriptor.from_operands(x, w, epilogue=epilogue,
                                                   quant=spec)
        from repro.optim.compression import quantize_operand
        w, sw = _quantize_grouped_w(w, spec)
        if not spec.weight_only:
            x, sx = quantize_operand(x, spec, axis=0)
    else:
        desc = GroupedGemmDescriptor.from_operands(x, w, epilogue=epilogue)
    plan = None
    if bm is not None or bk is not None or bn is not None:
        # Fill unpinned knobs from the (cached) engine plan.
        auto = engine.plan_for(desc)
        plan = GroupedGemmPlan(desc, bm or auto.bm, bk or auto.bk,
                               bn or auto.bn, fused=auto.fused)
    if spec is not None:
        # Inference-direct dispatch (no VJP wrapper on the quant axis).
        check_bias(epilogue, bias)
        if fused is None:
            return engine.dispatch(desc, x, w, group_sizes, plan=plan,
                                   bias=bias, sx=sx, sw=sw)
        from repro.core.config import use
        with use(fused="on" if fused else "off"):
            return engine.dispatch(desc, x, w, group_sizes, plan=plan,
                                   bias=bias, sx=sx, sw=sw)
    if plan is None and fused is None:
        # Default path: differentiable — training flows through the
        # custom VJP onto the scheduled backward walk (DESIGN.md §11).
        check_bias(epilogue, bias)
        return _grouped_vjp(epilogue, x, w, group_sizes, bias)
    if fused is None:
        return engine.dispatch(desc, x, w, group_sizes, plan=plan, bias=bias)
    from repro.core.config import use
    with use(fused="on" if fused else "off"):
        return engine.dispatch(desc, x, w, group_sizes, plan=plan, bias=bias)


# ---------------------------------------------------------------------------
# Expert-parallel entry point (DESIGN.md §14)
# ---------------------------------------------------------------------------

def _ref_ep(epilogue, x4, w):
    """Differentiable XLA oracle of the capacity-slot expert GEMM — the
    custom VJP's backward formulation (partitions under SPMD) and the
    numerical baseline in tests."""
    out = jnp.einsum("neck,ekf->necf", x4.astype(jnp.float32),
                     w.astype(jnp.float32))
    out = apply_epilogue(out, epilogue, None)
    return out.astype(x4.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ep_vjp(axis, epilogue, x4, w):
    """Forward = the engine's mesh dispatch; backward = autodiff of the
    XLA oracle (the olmax all2all custom-gradient idiom: the collective
    shuffle is engine-owned on the forward pass, while gradients flow
    through a formulation XLA partitions on its own)."""
    return _ep_dispatch(axis, epilogue, x4, w)


def _ep_dispatch(axis, epilogue, x4, w):
    from repro.core.descriptor import canonical_dtype
    from repro.runtime.shardlib import current_mesh
    mesh = current_mesh()
    s = mesh.shape.get(axis, 1) if mesh is not None else 1
    nt, e, cap, k = x4.shape
    desc = GroupedGemmDescriptor(
        t=nt * e * cap, k=k, n=int(w.shape[-1]), num_experts=e,
        dtype=canonical_dtype(x4.dtype), epilogue=epilogue,
        mesh=MeshSpec(axis, s))
    return engine.dispatch(desc, x4, w, None).reshape(nt, e, cap, -1)


def _ep_vjp_fwd(axis, epilogue, x4, w):
    return _ep_dispatch(axis, epilogue, x4, w), (x4, w)


def _ep_vjp_bwd(axis, epilogue, res, g):
    x4, w = res
    _, vjp = jax.vjp(lambda a, b: _ref_ep(epilogue, a, b), x4, w)
    dx, dw = vjp(g.astype(x4.dtype))
    return dx.astype(x4.dtype), dw.astype(w.dtype)


_ep_vjp.defvjp(_ep_vjp_fwd, _ep_vjp_bwd)


def expert_parallel_grouped_gemm(x4: jax.Array, w: jax.Array, *,
                                 axis: str = "model",
                                 epilogue: Optional[str] = None) -> jax.Array:
    """Expert-parallel capacity-slot grouped GEMM (DESIGN.md §14).

    ``x4``: ``(n, e, cap, k)`` dispatch slots (MoE layout — ``n`` token
    groups, ``e`` experts, ``cap`` capacity); ``w``: ``(e, k, f)`` expert
    bank.  Returns ``(n, e, cap, f)``.

    Under an active mesh whose ``axis`` divides both ``n`` and ``e``, the
    call enters the engine as a MESH descriptor: the comm-charged planner
    arbitrates *gathered* (all-gather weights, compute locally) vs
    *distributed* (keep weight shards, ``all_to_all`` the slots) and the
    chosen strategy runs under ``shard_map`` with the fused single-launch
    property per shard.  Off-mesh (or on indivisible shapes) it degrades
    to the ordinary differentiable :func:`grouped_gemm` path.
    """
    nt, e, cap, k = x4.shape
    from repro.runtime.shardlib import current_mesh
    mesh = current_mesh()
    s = mesh.shape.get(axis, 1) if mesh is not None else 1
    if s <= 1 or e % s or nt % s:
        xt = x4.transpose(1, 0, 2, 3).reshape(e * nt * cap, k)
        sizes = jnp.full((e,), nt * cap, jnp.int32)
        out = grouped_gemm(xt, w, sizes, epilogue=epilogue)
        return out.reshape(e, nt, cap, -1).transpose(1, 0, 2, 3)
    return _ep_vjp(axis, epilogue, x4, w)
