"""Mixture-of-Experts with top-k routing: dropless serving and
capacity-factor training dispatch.

The expert FFNs are batches of *small, ragged* GEMMs — exactly the
population the paper's engine targets; on TPU hardware the expert compute
routes through ``repro.kernels.grouped_gemm`` (see kernels/).  Two
dispatches, chosen by what the call is (DESIGN.md §16):

  * **dropless** (:func:`moe_dropless`) — every call that serves (a cache
    is passed) or whose layer holds only some of the experts
    (``cfg.experts_held``).  Each pick that lands on a held expert is one
    row; rows sorted by expert run through ``grouped_gemm`` with the
    runtime ``group_sizes``, so no token is dropped and a token's output
    does not depend on the rest of the batch.
  * **capacity** (:func:`moe_apply` otherwise) — the GShard/T5X one-hot
    formulation (dense einsums) that partitions deterministically under
    SPMD for the training mesh: tokens are processed in groups of
    ``cfg.moe_group`` so dispatch stays O(g·E·C) per group instead of
    O(T·E·C).  Routing priority is k-major (all top-1 assignments beat
    any top-2), the T5X convention.  Dropped tokens pass through the
    residual stream only.  Returns the GShard auxiliary load-balancing
    loss.

A shared expert (``cfg.shared_expert_d_ff``), an MLP every token runs,
is added to either dispatch's output.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.config import get_config
from repro.models import common
from repro.models.mlp import mlp_apply, mlp_init


_MAX_BATCH_SHARDS = 32  # pod x data on the largest production mesh


def _expert_gemm_grouped(x4, w, epilogue=None):
    """(n, e, cap, k) x (e, k, f) -> (n, e, cap, f) via the engine's
    ragged grouped-GEMM family.

    The capacity slots are uniform, so the "ragged" split degenerates to
    E equal groups of n*cap rows — rows sorted by expert after a
    transpose, exactly the layout the kernel's scalar-prefetch dispatch
    expects.  ``epilogue`` fuses the activation into the kernel's store
    (DESIGN.md §9) instead of a follow-up elementwise pass.
    Differentiable: training pulls gradients through the family's custom
    VJP, whose backward is ONE scheduled dX/dW walk over the same
    runtime tile tables — never the pad/scatter path (DESIGN.md §11).
    """
    from repro.kernels.grouped_gemm import grouped_gemm
    n, e, cap, k = x4.shape
    xt = x4.transpose(1, 0, 2, 3).reshape(e * n * cap, k)
    sizes = jnp.full((e,), n * cap, jnp.int32)
    out = grouped_gemm(xt, w, sizes, epilogue=epilogue)
    return out.reshape(e, n, cap, -1).transpose(1, 0, 2, 3)


def moe_init(rng, cfg):
    """The router scores all ``num_experts``; only the held experts'
    weights are stacked (on a leading dim)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held_experts
    rr, rg, ru, rd, rs = common.split_rngs(rng, 5)
    p = {
        "router": common.linear_init(rr, d, cfg.num_experts, bias=False),
        "w_up": {"w": common.scaled_init(ru, (e, d, f), d)},
        "w_down": {"w": common.scaled_init(rd, (e, f, d), f)},
    }
    if cfg.mlp_gated:
        p["w_gate"] = {"w": common.scaled_init(rg, (e, d, f), d)}
    if cfg.shared_expert_d_ff:
        p["shared"] = mlp_init(rs, _shared_cfg(cfg))
    return p


def _shared_cfg(cfg):
    return dataclasses.replace(cfg, d_ff=cfg.shared_expert_d_ff)


def route(cfg, logits):
    """fp32 router logits (t, E) -> (gates, experts), each (t, k).

    With ``moe_renormalize`` the gates are a softmax over the k kept
    logits (equal to the top-k softmax probabilities renormalised);
    without, the top-k of the softmax over all experts."""
    k = cfg.num_experts_per_tok
    if cfg.moe_renormalize:
        top, idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(top, axis=-1), idx
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


def _expert_rows(x, w, sizes, epilogue=None):
    """Rows sorted by expert (R, K) x (E, K, N) -> (R, N); rows past
    ``sum(sizes)`` are zero."""
    if get_config().backend == "pallas":
        from repro.kernels.grouped_gemm import grouped_gemm
        return grouped_gemm(x, w, sizes, epilogue=epilogue)
    out = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    return (_act(out, epilogue) if epilogue else out).astype(x.dtype)


def moe_dropless(params, cfg, x, live=None):
    """x: (b, s, d) -> (y, counts): the held experts' share of the MoE
    output, no token dropped.  ``live`` (b, s) bool, if given, routes
    only the live tokens (a decode step's inactive slots carry garbage
    rows): the others get no expert rows, and read zeros.

    Every token is routed over all ``num_experts``; the picks that land
    on a held expert become rows, sorted by expert (a stable sort, so a
    token's rows keep its pick order), and run through the expert GEMMs
    with runtime ``group_sizes``.  The static row bound is tokens x
    min(k, held); rows past the groups' total are zero and unread.  Each
    token gathers its own rows back and sums them with its gates in
    float32: every operation on a row reads that row alone, so a token's
    output is the same in any batch.  ``counts`` is int32 ``(rows routed
    to held experts, held experts with a row)``."""
    dt = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    t, k, held = b * s, cfg.num_experts_per_tok, cfg.held_experts
    xt = x.reshape(t, d).astype(dt)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32))
    gates, idx = route(cfg, logits)                      # (t, k)
    local = idx - cfg.first_expert_held
    mine = (local >= 0) & (local < held)
    if live is not None:
        mine &= live.reshape(t, 1)
    key = jnp.where(mine, local, held).reshape(t * k)    # held = not ours
    r = t * min(k, held)
    order = jnp.argsort(key, stable=True)[:r]            # (r,) pick ids
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    rows = xt[order // k]
    w_up = common.cast_param(params["w_up"]["w"], dt)
    w_down = common.cast_param(params["w_down"]["w"], dt)
    if cfg.mlp_gated:
        w_gate = common.cast_param(params["w_gate"]["w"], dt)
        h = _expert_rows(rows, w_gate, sizes, cfg.mlp_act) * \
            _expert_rows(rows, w_up, sizes)
    else:
        h = _expert_rows(rows, w_up, sizes, cfg.mlp_act)
    out = _expert_rows(h, w_down, sizes)                 # (r, d)
    # Where each pick's row landed: the inverse of the sort; picks off
    # this chip point past the rows and read zeros.
    slot = jnp.full((t * k,), r, jnp.int32).at[order].set(
        jnp.arange(r, dtype=jnp.int32))
    mine_rows = jnp.take(out, slot, axis=0, mode="fill", fill_value=0)
    y = jnp.sum(mine_rows.reshape(t, k, d).astype(jnp.float32)
                * jnp.where(mine, gates, 0.0)[..., None], axis=1)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)])
    return y.astype(dt).reshape(b, s, d), counts.astype(jnp.int32)


def moe_layer(params, cfg, x, *, live=None):
    """The MoE block's output (routed experts plus the shared expert),
    its aux loss and its counts.  A serving call passes ``live`` (b, s)
    (the tokens at a position): it, and any call of a layer holding a
    subset of the experts, takes the dropless dispatch, every other the
    capacity dispatch (module docstring).  ``counts`` as in
    :func:`moe_dropless`, zeros on the capacity path."""
    if live is not None or cfg.held_experts < cfg.num_experts:
        y, counts = moe_dropless(params, cfg, x, live)
        aux = jnp.zeros((), jnp.float32)
    else:
        y, aux = moe_apply(params, cfg, x)
        counts = jnp.zeros((2,), jnp.int32)
    if cfg.shared_expert_d_ff:
        y = y + mlp_apply(params["shared"], _shared_cfg(cfg), x)
    return y, aux, counts


def _act(x, kind):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": lambda v: jnp.maximum(v, 0)}[kind](x)


def moe_apply(params, cfg, x):
    """The capacity-factor dispatch over all experts: x: (b, s, d) ->
    (y, aux_loss)."""
    dt = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    # Keep >= MAX_BATCH_SHARDS groups so the group dim stays batch-sharded
    # on the production mesh even at decode shapes (t small).
    g = min(cfg.moe_group, max(1, t // _MAX_BATCH_SHARDS))
    while t % g:
        g -= 1
    n = t // g
    cap = int(cfg.capacity_factor * g * k / e)
    cap = max(8, -(-cap // 8) * 8)

    from repro.runtime.shardlib import current_mesh, shard_activation
    mesh = current_mesh()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    ep = msize > 1 and e % msize == 0  # expert parallelism when E divides

    xg = x.reshape(n, g, d).astype(dt)
    xg = shard_activation(xg, (("pod", "data"), None, None))

    # --- routing (fp32) ---------------------------------------------------
    logits = jnp.einsum("ngd,de->nge", xg.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (n, g, e)
    gate_vals, gate_idx = route(cfg, logits)  # (n, g, k)

    # GShard aux loss.
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux_loss = e * jnp.sum(me * ce)

    # --- capacity assignment (k-major priority) ----------------------------
    mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (n, g, k, e)
    mask_flat = mask.transpose(0, 2, 1, 3).reshape(n, k * g, e)
    pos_flat = jnp.cumsum(mask_flat, axis=1) - 1.0
    pos = pos_flat.reshape(n, k, g, e).transpose(0, 2, 1, 3)  # (n, g, k, e)
    keep = mask * (pos < cap)  # (n, g, k, e)
    slot = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # (n, g, k)
    slot_oh = jax.nn.one_hot(slot, cap, dtype=jnp.float32) * \
        jnp.sum(keep, axis=-1, keepdims=True)  # (n, g, k, cap)

    dispatch = jnp.einsum("ngke,ngkc->ngec", keep, slot_oh).astype(dt)
    combine = jnp.einsum("ngke,ngkc->ngec", keep * gate_vals[..., None],
                         slot_oh).astype(dt)

    # Two SPMD layouts (DESIGN.md §5):
    #   * EP  (E % model == 0, e.g. phi3.5-moe): experts live on "model";
    #     dispatch produces e-sharded slot buffers — an all-to-all moves
    #     tokens to their experts, weights never move.
    #   * TP-f fallback (grok-1: E=8 < 16): tokens stay data-sharded, the
    #     expert FFN dim f is model-sharded (Megatron inside each expert).
    bd = ("pod", "data")
    # Mesh-aware engine dispatch (DESIGN.md §14): under the pallas
    # backend, EP expert compute enters the engine as a MESH descriptor
    # — the comm-charged planner arbitrates gathered vs distributed
    # (all_to_all) dispatch per shape, keeping the fused single-launch
    # property per shard.  Needs the token-group dim divisible too.
    ep_mesh = ep and get_config().backend == "pallas" and n % msize == 0
    if ep:
        dispatch = shard_activation(dispatch, (bd, None, "model", None))
        combine = shard_activation(combine, (bd, None, "model", None))
        if ep_mesh:
            # shard_map shards the token-group dim over "model"; matching
            # constraints avoid reshard ping-pong between the three GEMMs.
            xin_spec = ("model", None, None, None)
            h_spec = ("model", None, None, None)
        else:
            xin_spec = (bd, "model", None, None)
            h_spec = (bd, "model", None, None)
    elif t <= 2048:
        # Decode-scale token counts: replicate the (tiny) token block so
        # the 2D-sharded expert weights never move — XLA partial-contracts
        # the data-sharded d dim and all-reduces the small activations
        # instead of all-gathering GBs of weights per step.
        xin_spec = (None, None, None, None)
        h_spec = (None, None, None, "model")
    else:
        xin_spec = (bd, None, None, None)
        h_spec = (bd, None, None, "model")

    # --- expert compute (batched small GEMMs over the E dim) --------------
    # Under the pallas backend the three expert GEMMs route through the
    # engine's grouped-GEMM family (descriptor-planned tiles), with the
    # activation fused into the kernel epilogue (DESIGN.md §9); the XLA
    # default keeps the einsum formulation, which partitions under SPMD.
    if get_config().backend == "pallas":
        if ep_mesh:
            from repro.kernels.grouped_gemm import expert_parallel_grouped_gemm

            def mm(x4, w, epilogue=None):
                return expert_parallel_grouped_gemm(x4, w, axis="model",
                                                    epilogue=epilogue)
        else:
            mm = _expert_gemm_grouped
    else:
        def mm(x4, w, epilogue=None):
            out = jnp.einsum("neck,ekf->necf", x4, w)
            return _act(out, epilogue) if epilogue else out
    xin = jnp.einsum("ngec,ngd->necd", dispatch, xg)  # (n, e, cap, d)
    xin = shard_activation(xin, xin_spec)
    w_up = common.cast_param(params["w_up"]["w"], dt)
    w_down = common.cast_param(params["w_down"]["w"], dt)
    if cfg.mlp_gated:
        up = shard_activation(mm(xin, w_up), h_spec)
        w_gate = common.cast_param(params["w_gate"]["w"], dt)
        gate = shard_activation(mm(xin, w_gate, epilogue=cfg.mlp_act), h_spec)
        h = gate * up
    else:
        h = shard_activation(mm(xin, w_up, epilogue=cfg.mlp_act), h_spec)
    y_slots = mm(h, w_down)
    y_slots = shard_activation(y_slots, xin_spec)
    y = jnp.einsum("ngec,necd->ngd", combine, y_slots)
    return y.reshape(b, s, d), aux_loss
