"""Residual blocks and layer stacks.

A *block* = (norm → mixer → residual) [+ (norm → mlp|moe → residual)].
Mixer kinds: "attn" (global attention), "local" (sliding-window attention),
"rec" (RG-LRU), "ssm" (Mamba-2 SSD).  An architecture is a repeating
``block_pattern`` (e.g. ("rec","rec","local") for recurrentgemma); the
stack scans over pattern *groups* with stacked params so compile time is
O(1) in depth, with any non-multiple remainder applied unscanned.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models import common
from repro.models.attention import (PagedKVCache, attention_apply,
                                    attention_init, init_kv_cache,
                                    init_paged_kv_cache)
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_init, moe_layer
from repro.models.rglru import init_recurrent_state, rglru_apply, rglru_init
from repro.models.ssd import init_ssm_state, ssd_apply, ssd_init

from repro.runtime.shardlib import shard_activation


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def block_init(rng, cfg, kind: str, cross: bool = False):
    r_mix, r_ff, r_cross = common.split_rngs(rng, 3)
    p: Dict[str, Any] = {"norm_mix": common.norm_init(cfg.norm_type, cfg.d_model)}
    if kind in ("attn", "local"):
        p["mixer"] = attention_init(r_mix, cfg)
    elif kind == "rec":
        p["mixer"] = rglru_init(r_mix, cfg)
    elif kind == "ssm":
        p["mixer"] = ssd_init(r_mix, cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    if cross:
        p["norm_cross"] = common.norm_init(cfg.norm_type, cfg.d_model)
        p["cross"] = attention_init(r_cross, cfg, cross=True)
    if cfg.block_has_mlp:
        p["norm_ff"] = common.norm_init(cfg.norm_type, cfg.d_model)
        p["ff"] = moe_init(r_ff, cfg) if cfg.num_experts else mlp_init(r_ff, cfg)
    return p


def block_cache(batch, cfg, kind: str, capacity: int, paged=None):
    """Initial decode-state for one block (None for stateless train).

    ``paged`` (a :class:`repro.models.attention.PageSpec`) switches "attn"
    blocks to the paged pool layout of the continuous-batching serving
    runtime (DESIGN.md §12); "local"/"rec"/"ssm" states are already
    O(window)/O(1) per slot, so they stay slot-major dense."""
    if kind == "attn":
        if paged is not None:
            return init_paged_kv_cache(batch, paged, cfg.num_kv_heads,
                                       cfg.head_dim,
                                       jnp.dtype(cfg.kv_cache_dtype))
        return init_kv_cache(batch, capacity, cfg.num_kv_heads, cfg.head_dim,
                             jnp.dtype(cfg.kv_cache_dtype))
    if kind == "local":
        cap = min(capacity, cfg.attn_window)
        return init_kv_cache(batch, cap, cfg.num_kv_heads, cfg.head_dim,
                             jnp.dtype(cfg.kv_cache_dtype))
    if kind == "rec":
        return init_recurrent_state(batch, cfg)
    if kind == "ssm":
        return init_ssm_state(batch, cfg)
    raise ValueError(kind)


def _no_aux(cfg):
    """(aux_loss, expert counts) of a block without experts; a model
    without experts carries no counts."""
    counts = jnp.zeros((2,), jnp.int32) if cfg.num_experts else None
    return jnp.zeros((), jnp.float32), counts


def _residual(cfg, x, y):
    """x + y, the branch ``y`` scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def block_apply(params, cfg, kind: str, x, positions, *, cache=None,
                enc_out=None) -> Tuple[jax.Array, Any, Any]:
    """Returns (x, new_cache, (aux_loss, expert_counts)): expert counts
    as in :func:`repro.models.moe.moe_dropless` (zeros in a block without
    experts, None in a model without them)."""
    aux = _no_aux(cfg)
    h = common.norm_apply(cfg.norm_type, params["norm_mix"], x, cfg.norm_eps)
    if kind in ("attn", "local"):
        window = cfg.attn_window if kind == "local" else None
        y, new_cache = attention_apply(params["mixer"], cfg, h, positions,
                                       cache=cache, window=window)
    elif kind == "rec":
        y, new_cache = rglru_apply(params["mixer"], cfg, h, state=cache)
    elif kind == "ssm":
        y, new_cache = ssd_apply(params["mixer"], cfg, h, state=cache)
    else:
        raise ValueError(kind)
    x = _residual(cfg, x, y)
    x = shard_activation(x, (("pod", "data"), "model", None))

    if enc_out is not None and "cross" in params:
        h = common.norm_apply(cfg.norm_type, params["norm_cross"], x, cfg.norm_eps)
        y, _ = attention_apply(params["cross"], cfg, h, positions,
                               kv_override=enc_out)
        x = x + y

    if cfg.block_has_mlp:
        h = common.norm_apply(cfg.norm_type, params["norm_ff"], x, cfg.norm_eps)
        if cfg.num_experts:
            live = None if cache is None else \
                jnp.broadcast_to(positions >= 0, h.shape[:2])
            y, loss, counts = moe_layer(params["ff"], cfg, h, live=live)
            aux = (loss, counts)
        else:
            y = mlp_apply(params["ff"], cfg, h)
        x = _residual(cfg, x, y)
        x = shard_activation(x, (("pod", "data"), "model", None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack: scan over pattern groups
# ---------------------------------------------------------------------------

def stack_layout(cfg) -> Tuple[int, Tuple[str, ...]]:
    """(num_scanned_groups, remainder_kinds)."""
    pat = cfg.block_pattern
    groups = cfg.num_layers // len(pat)
    rem = cfg.num_layers - groups * len(pat)
    return groups, tuple(pat[:rem])


def stack_init(rng, cfg, cross: bool = False):
    pat = cfg.block_pattern
    groups, rem = stack_layout(cfg)
    r_groups, r_rem = jax.random.split(rng)

    def one_group(r):
        rs = common.split_rngs(r, len(pat))
        return {f"b{i}": block_init(rs[i], cfg, kind, cross)
                for i, kind in enumerate(pat)}

    stacked = jax.vmap(one_group)(jax.random.split(r_groups, groups)) \
        if groups else None
    rem_params = [block_init(r, cfg, kind, cross)
                  for r, kind in zip(common.split_rngs(r_rem, max(1, len(rem))), rem)]
    return {"groups": stacked, "rem": rem_params}


def stack_cache(batch, cfg, capacity: int, paged=None):
    pat = cfg.block_pattern
    groups, rem = stack_layout(cfg)

    def one_group(_):
        return {f"b{i}": block_cache(batch, cfg, kind, capacity, paged)
                for i, kind in enumerate(pat)}

    stacked = None
    if groups:
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one_group(g) for g in range(groups)]) \
            if groups > 1 else jax.tree.map(lambda x: x[None], one_group(0))
    rem_caches = [block_cache(batch, cfg, kind, capacity, paged)
                  for kind in rem]
    return {"groups": stacked, "rem": rem_caches}


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _group_apply(group_params, cfg, x, positions, group_cache, enc_out):
    pat = cfg.block_pattern
    new_cache = {} if group_cache is not None else None
    aux = _no_aux(cfg)
    for i, kind in enumerate(pat):
        c = group_cache[f"b{i}"] if group_cache is not None else None
        x, nc, a = block_apply(group_params[f"b{i}"], cfg, kind, x, positions,
                               cache=c, enc_out=enc_out)
        aux = _add(aux, a)
        if new_cache is not None:
            new_cache[f"b{i}"] = nc
    return x, new_cache, aux


def _pools_out(group_cache):
    """Split a stacked group cache for the layer scan: ``(xs, pools)``.

    Each :class:`PagedKVCache` block leaves only its block tables in
    ``xs``; its stacked pools (and int8 scales) go to ``pools``, which
    the scan *carries*.  A pool in ``xs``/``ys`` would be sliced out per
    layer, written back per layer and copied whole after the loop; as a
    carry it aliases the donated cache and is updated in place
    (DESIGN.md §12).  Every other leaf keeps its ``xs`` path."""
    pools = {k: c._replace(tables=None) for k, c in group_cache.items()
             if isinstance(c, PagedKVCache)}
    xs = {k: c.tables if k in pools else c for k, c in group_cache.items()}
    return xs, pools


def stack_apply(params, cfg, x, positions, *, cache=None, enc_out=None):
    """Returns (x, new_cache, aux_loss_sum, expert_counts): the counts
    (int32 ``(rows routed to held experts, held experts hit)``) summed
    over the MoE layers, zeros without experts."""
    groups, rem = stack_layout(cfg)
    aux_total = _no_aux(cfg)
    new_group_cache = None

    if groups:
        gcache, pools = None, {}
        if cache is not None:
            gcache, pools = _pools_out(cache["groups"])

        def body(carry, xs):
            h, aux, pools = carry
            if cache is not None:
                gp, gc, layer = xs
                gc = {k: pools[k]._replace(tables=c, layer=layer)
                      if k in pools else c for k, c in gc.items()}
            else:
                gp, gc = xs, None
            # Name the (bf16) carry so the remat policy saves exactly this
            # tensor per layer group — without the name, XLA is free to
            # save an fp32-converted copy of the whole stack (observed:
            # +3.8 GiB/device on starcoder2, EXPERIMENTS.md §Perf).
            h = checkpoint_name(h, "block_carry")
            h, nc, a = _group_apply(gp, cfg, h, positions, gc, enc_out)
            if nc is not None:
                pools = {k: nc[k]._replace(tables=None, layer=None)
                         for k in pools}
                nc = {k: c for k, c in nc.items() if k not in pools}
            return (h, _add(aux, a), pools), nc

        if cfg.remat:
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "block_carry"),
                prevent_cse=False)
        xs = (params["groups"], gcache, jnp.arange(groups)) \
            if cache is not None else params["groups"]
        (x, aux_total, pools), ys = jax.lax.scan(
            body, (x, aux_total, pools), xs)
        if cache is not None:
            new_group_cache = {
                k: pools[k]._replace(tables=c.tables) if k in pools
                else ys[k] for k, c in cache["groups"].items()}

    new_rem = []
    for i, kind in enumerate(rem):
        c = cache["rem"][i] if cache is not None else None
        x, nc, a = block_apply(params["rem"][i], cfg, kind, x, positions,
                               cache=c, enc_out=enc_out)
        aux_total = _add(aux_total, a)
        new_rem.append(nc)

    new_cache = None
    if cache is not None:
        new_cache = {"groups": new_group_cache, "rem": new_rem}
    loss, counts = aux_total
    if counts is None:
        counts = jnp.zeros((2,), jnp.int32)
    return x, new_cache, loss, counts
