"""Multi-head attention: GQA / MQA, RoPE, qk-norm, QKV-bias, logit softcap,
local (sliding-window) attention, chunked long-context attention, and
ring-buffer KV caches for decode.

Layout: heads are kept *flattened* (b, s, h, hd) with K/V repeated to the
full head count for GQA — the standard tensor-parallel formulation: the
head dim shards on "model" when divisible; otherwise the score matrix
shards over the query dim instead (context-parallel fallback, used by e.g.
internvl2's 14-head backbone).  All projections route through
``repro.core.matmul``.

For sequences above ``Q_CHUNK`` the score matrix is never fully
materialized: a ``lax.scan`` over query chunks attends against the full
(or windowed) KV — linear activation memory in sequence length (the
XLA-path analogue of the Pallas flash-attention kernel in
``repro.kernels.flash_attention``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.config import get_config
from repro.models import common
from repro.models.rotary import apply_rope
from repro.runtime.shardlib import current_mesh, shard_activation

Q_CHUNK = 512

NEG_INF = -1e30

# Page size of the decode attention walk.  Under the pallas backend a
# dense decode cache is read by the same ``flash_decode`` kernel as the
# serving runtime's page pool, viewed as pages of this size, so a request
# decoded alone and one decoded in a continuous batch with this page size
# run the same online-softmax steps on the same values.
DECODE_PAGE_SIZE = 16


class KVCache(NamedTuple):
    k: jax.Array  # (b, S, h_kv, hd)
    v: jax.Array  # (b, S, h_kv, hd)
    pos: jax.Array  # (b, S) absolute position of each slot, -1 = empty


def init_kv_cache(batch, capacity, n_kv, head_dim, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
    )


class PageSpec(NamedTuple):
    """Static paged-cache geometry (the serving runtime's pool shape).

    Threaded through ``block_cache``/``stack_cache``/``init_cache``: when
    present, "attn" blocks get a :class:`PagedKVCache` pool instead of a
    dense per-slot ring (DESIGN.md §12).  ``max_blocks * page_size`` caps
    the per-sequence context length the block tables can map.
    ``kv_quant="int8"`` stores the pools in int8 with per-token f32
    dequant scales (DESIGN.md §13) — half the KV bytes per token, scales
    folded into the decode kernel's score/PV algebra."""
    num_pages: int
    page_size: int
    max_blocks: int
    kv_quant: Optional[str] = None


class PagedKVCache(NamedTuple):
    """Paged KV pool + per-slot block tables (continuous batching).

    Unlike :class:`KVCache`, storage is not per-slot: ``k``/``v`` pool
    pages are allocated to sequences by the host-side free-list allocator
    (``repro.runtime.pages.PagePool``) and mapped by ``tables`` — so a
    slot's KV footprint tracks its actual length, and admitting/evicting
    a sequence moves page *indices*, never KV bytes.  Position ``p`` of
    slot ``i`` lives at ``(tables[i, p // P], p % P)``.

    ``k_scale``/``v_scale`` (int8 pools only, else None): per-token f32
    dequant scales, same page layout as the pools with the head/feature
    dims reduced away — ``(num_pages, page_size)``.

    ``layer`` is None except inside the decode step's layer scan
    (``repro.models.blocks.stack_apply``): there the pools (and scales)
    are the whole stack ``(L, num_pages, ...)``, carried through the scan
    and donated by the caller, and ``layer`` is the scalar index of the
    layer being applied.  :func:`_paged_decode` then writes the new row
    at ``[layer, page, offset]`` in place and the decode kernel reads
    that layer's pages straight from the stack, so no layer's pool is
    ever sliced out or copied back (DESIGN.md §12).  ``walk`` is likewise
    set only there: the ``flash_decode`` tile table of the step, built
    once before the scan by :func:`paged_walk`."""
    k: jax.Array       # (num_pages, page_size, h_kv, hd)
    v: jax.Array       # (num_pages, page_size, h_kv, hd)
    tables: jax.Array  # (num_slots, max_blocks) int32 page ids
    k_scale: Optional[jax.Array] = None  # (num_pages, page_size) f32
    v_scale: Optional[jax.Array] = None
    layer: Optional[jax.Array] = None    # int32 scalar, stacked pools only
    walk: Optional[jax.Array] = None     # (max_tiles, 5) int32, ditto


def init_paged_kv_cache(num_slots, spec: PageSpec, n_kv, head_dim,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    kv_quant = getattr(spec, "kv_quant", None)
    pool_dtype = jnp.int8 if kv_quant == "int8" else dtype
    scale = (jnp.zeros((spec.num_pages, spec.page_size), jnp.float32)
             if kv_quant == "int8" else None)
    return PagedKVCache(
        k=jnp.zeros((spec.num_pages, spec.page_size, n_kv, head_dim),
                    pool_dtype),
        v=jnp.zeros((spec.num_pages, spec.page_size, n_kv, head_dim),
                    pool_dtype),
        tables=jnp.zeros((num_slots, spec.max_blocks), jnp.int32),
        k_scale=scale, v_scale=scale,
    )


def attention_init(rng, cfg, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rq, rk, rv, ro, rn = common.split_rngs(rng, 5)
    p = {
        "wq": common.linear_init(rq, d, hq * hd, bias=cfg.qkv_bias),
        "wk": common.linear_init(rk, d, hkv * hd, bias=cfg.qkv_bias),
        "wv": common.linear_init(rv, d, hkv * hd, bias=cfg.qkv_bias),
        "wo": common.linear_init(ro, hq * hd, d, bias=False),
    }
    if cfg.qk_norm:
        p["q_norm"] = common.rmsnorm_init(hd)
        p["k_norm"] = common.rmsnorm_init(hd)
    return p


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _head_axes(n_heads: int):
    """Sharding specs for (b, s|q, h, hd) and (b, h, q, k) tensors.

    Heads shard on "model" when divisible; otherwise the query/sequence
    dim takes the model axis (context-parallel fallback).
    """
    mesh = current_mesh()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    heads_ok = msize <= 1 or n_heads % msize == 0
    if heads_ok:
        return (("pod", "data"), None, "model", None), \
               (("pod", "data"), "model", None, None)
    return (("pod", "data"), "model", None, None), \
           (("pod", "data"), None, "model", None)


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=2)


def _attend(q, k, v, mask, softcap: Optional[float], *,
            kv_seq_sharded: bool = False):
    """q: (b, sq, h, hd); k/v: (b, sk, h, hd); mask broadcast (b,h,sq,sk).

    Inputs stay bf16 (fp32 *accumulation* via preferred_element_type —
    upcasting the inputs would double every gather/buffer); scores/softmax
    run in fp32.

    ``kv_seq_sharded``: decode against a sequence-sharded KV cache (GQA
    head counts that don't divide the model axis).  Scores stay sharded
    over the KV-sequence dim; XLA turns the softmax/weighted-sum into
    partial reductions + tiny all-reduces — SPMD FlashDecoding split-K —
    instead of all-gathering the whole cache every step.
    """
    h = q.shape[2]
    if kv_seq_sharded:
        qspec = (("pod", "data"), None, None, None)
        sspec = (("pod", "data"), None, None, "model")
    else:
        qspec, sspec = _head_axes(h)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask, scores, NEG_INF)
    scores = shard_activation(scores, sspec)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return shard_activation(out.astype(v.dtype), qspec)


def _causal_mask(q_pos, k_pos, window: Optional[int]):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    m &= (k_pos >= 0)[None, :]
    return m[None, None]  # (1, 1, sq, sk)


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------

def _attention_seq(q, k, v, q_pos, k_pos, window, softcap):
    """Chunked causal attention, linear activation memory in sq."""
    b, sq, h, hd = q.shape
    # Engine routing: under the pallas backend the plain-causal full-seq
    # case lowers to the flash-attention kernel family (descriptor-planned
    # block sizes, engine-cached build; fused plans walk the causal-aware
    # tile table in one launch — DESIGN.md §10).  The routed call is
    # differentiable: training pulls gradients through the family's
    # custom VJP, whose backward is ONE scheduled dQ/dK/dV walk over the
    # same causal-pruned tile table (DESIGN.md §11) — not XLA autodiff of
    # the kernel.  Windowing, softcap and shifted q/k stay on the XLA
    # formulation; positions are assumed contiguous ascending here (true
    # for the train/prefill callers).
    if (get_config().backend == "pallas" and window is None
            and not softcap and sq == k.shape[1]):
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=True)
    if sq <= Q_CHUNK:
        return _attend(q, k, v, _causal_mask(q_pos, k_pos, window), softcap)

    assert sq % Q_CHUNK == 0, f"seq {sq} not divisible by q-chunk {Q_CHUNK}"
    nc = sq // Q_CHUNK
    qs = q.reshape(b, nc, Q_CHUNK, h, hd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(nc, Q_CHUNK)

    if window is not None and k.shape[1] > window + Q_CHUNK:
        # Sliding window: each q chunk touches a static-size KV slice.
        pad = ((0, 0), (window, 0), (0, 0), (0, 0))
        kp_pad = jnp.pad(k_pos, (window, 0), constant_values=-1)
        k_pad, v_pad = jnp.pad(k, pad), jnp.pad(v, pad)

        def body(_, args):
            qc, qpc, start = args
            ks = jax.lax.dynamic_slice_in_dim(k_pad, start, window + Q_CHUNK, 1)
            vs = jax.lax.dynamic_slice_in_dim(v_pad, start, window + Q_CHUNK, 1)
            kps = jax.lax.dynamic_slice_in_dim(kp_pad, start, window + Q_CHUNK, 0)
            return None, _attend(qc, ks, vs, _causal_mask(qpc, kps, window),
                                 softcap)

        starts = jnp.arange(nc) * Q_CHUNK
        # remat: without it the backward keeps every chunk's fp32 score
        # matrix alive at once — the flash-attention memory argument.
        _, out = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                              None, (qs, qp, starts))
    else:
        def body(_, args):
            qc, qpc = args
            return None, _attend(qc, k, v, _causal_mask(qpc, k_pos, window),
                                 softcap)

        _, out = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                              None, (qs, qp))

    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# Paged decode (continuous batching, DESIGN.md §12)
# ---------------------------------------------------------------------------

def _slot_positions(pos2d, S):
    """(S,) decode positions from ``(S|1, 1)`` per-slot positions."""
    return pos2d[:, 0] if pos2d.shape[0] == S else \
        jnp.broadcast_to(pos2d[:, 0], (S,))


def paged_walk(cfg, cache: PagedKVCache, positions):
    """The ``flash_decode`` tile table of one decode step over ``cache``
    at per-slot ``positions`` ``(S, 1)``, or None where
    :func:`_paged_decode` takes the XLA path.  A decode step that scans
    many layers builds it once and sets it as each layer's
    ``cache.walk``, since every layer reads the same lengths."""
    if get_config().backend != "pallas" or cfg.attn_logit_softcap:
        return None
    from repro.kernels.flash_attention import decode_walk
    S = cache.tables.shape[0]
    pos = _slot_positions(
        positions if positions.ndim == 2 else positions[None, :], S)
    q = jax.ShapeDtypeStruct((S, cfg.num_heads, cfg.head_dim),
                             jnp.dtype(cfg.dtype))
    return decode_walk(q, cache.k, cache.tables,
                       jnp.where(pos >= 0, pos + 1, 0))


def _paged_decode(cfg, cache: PagedKVCache, q, k, v, pos2d, dt, g):
    """One decode step against the paged KV pool.

    q/k/v: (S, 1, h|hkv, hd); ``pos2d``: (S, 1) per-slot positions (the
    slot's current length; -1 = inactive).  The new token's KV scatters
    into page ``tables[i, pos // P]`` at offset ``pos % P`` — inactive
    rows scatter to an out-of-bounds page id, which ``mode="drop"``
    discards, so dead slots never touch the pool (their *output* rows
    are garbage the step-level merge masks).

    With ``cache.layer`` set, the pools are the whole layer stack the
    decode step carries through its layer scan: the row is written at
    ``[layer, page, offset]`` into the carry itself, and attention reads
    layer ``layer`` of the stack, so the pool is updated in place and
    never sliced per layer (DESIGN.md §12).  Attention runs either
    through the engine's ``flash_decode`` family (pallas backend: ONE
    launch walking the runtime
    :class:`~repro.core.schedule.DecodeTileSchedule`) or the XLA gather
    formulation (``ref_paged_decode_attention``'s math)."""
    S = q.shape[0]
    layer = cache.layer
    pages, P, hkv, hd = cache.k.shape[-4:]
    B = cache.tables.shape[1]
    pos = _slot_positions(pos2d, S)
    active = pos >= 0
    safe = jnp.clip(pos, 0)
    blk = jnp.take_along_axis(cache.tables, (safe // P)[:, None], axis=1)[:, 0]
    # Inactive rows scatter to page id == pages: out of bounds, which
    # mode="drop" discards (NOT -1 — negative indices wrap in jnp).
    pid = jnp.where(active, blk, pages)
    off = safe % P
    row = (pid, off) if layer is None else (layer, pid, off)

    def write(pool, new):
        return pool.at[row].set(new.astype(pool.dtype), mode="drop")

    ks_new = vs_new = None
    if cache.k_scale is not None:
        # int8 pools (DESIGN.md §13): symmetric per-token quantization at
        # write time — one f32 scale per (page, offset) row, the row's
        # absmax over heads x features divided by the int8 range.
        def _qrow(row):  # (S, hkv, hd) wide -> int8 values + (S,) scales
            r32 = row.astype(jnp.float32)
            s = jnp.max(jnp.abs(r32), axis=(1, 2)) / 127.0 + 1e-12
            qv = jnp.clip(jnp.round(r32 / s[:, None, None]), -127, 127)
            return qv.astype(jnp.int8), s.astype(jnp.float32)
        kq, ks = _qrow(k[:, 0])
        vq, vs = _qrow(v[:, 0])
        k_new, v_new = write(cache.k, kq), write(cache.v, vq)
        ks_new, vs_new = write(cache.k_scale, ks), write(cache.v_scale, vs)
    else:
        k_new, v_new = write(cache.k, k[:, 0]), write(cache.v, v[:, 0])
    new_cache = PagedKVCache(k_new, v_new, cache.tables, ks_new, vs_new,
                             layer)
    lengths = jnp.where(active, pos + 1, 0)

    if get_config().backend == "pallas" and not cfg.attn_logit_softcap:
        from repro.kernels.flash_attention import paged_decode_attention
        out = paged_decode_attention(q[:, 0], k_new, v_new, cache.tables,
                                     lengths, k_scale=ks_new,
                                     v_scale=vs_new, layer=layer,
                                     walk=cache.walk)[:, None]
        return new_cache, out
    # XLA fallback: gather the block-table pages into a contiguous view
    # (gathered column j holds absolute position j) and mask j >= length
    # — identical math to ref_paged_decode_attention, expressed through
    # the shared _attend so float ops match the dense decode path.
    gidx = jnp.clip(cache.tables, 0, pages - 1)
    pages_of = (lambda pool: pool[gidx]) if layer is None else \
        (lambda pool: pool[layer, gidx])  # one gather off the stack
    gk = pages_of(k_new)  # (S, B, P, hkv, hd)
    gv = pages_of(v_new)
    if ks_new is not None:
        # dequant in f32 before entering the shared attention math
        gk = gk.astype(jnp.float32) * pages_of(ks_new)[..., None, None]
        gv = gv.astype(jnp.float32) * pages_of(vs_new)[..., None, None]
    gk = _repeat_kv(gk.reshape(S, B * P, hkv, hd).astype(dt), g)
    gv = _repeat_kv(gv.reshape(S, B * P, hkv, hd).astype(dt), g)
    live = jnp.arange(B * P)[None, :] < lengths[:, None]  # (S, B*P)
    out = _attend(q, gk, gv, live[:, None, None, :], cfg.attn_logit_softcap)
    return new_cache, out


def _dense_decode_paged(cache: KVCache, q, pos2d):
    """Decode attention over a dense cache with the ``flash_decode``
    kernel: row ``i``'s ``(capacity, hkv, hd)`` cache is ``capacity / P``
    consecutive pages of a pool whose block table is the identity.
    Needs ``capacity % P == 0`` and an unwrapped cache (position ``p`` at
    slot ``p``)."""
    from repro.kernels.flash_attention import paged_decode_attention
    b, cap, hkv, hd = cache.k.shape
    nblk = cap // DECODE_PAGE_SIZE
    shape = (b * nblk, DECODE_PAGE_SIZE, hkv, hd)
    tables = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    lengths = jnp.broadcast_to(jnp.minimum(pos2d[:, -1] + 1, cap), (b,))
    return paged_decode_attention(q[:, 0], cache.k.reshape(shape),
                                  cache.v.reshape(shape), tables,
                                  lengths)[:, None]


# ---------------------------------------------------------------------------
# Public apply
# ---------------------------------------------------------------------------

def attention_apply(params, cfg, x, positions, *, cache: Optional[KVCache] = None,
                    window: Optional[int] = None, kv_override=None):
    """Self-attention (or cross-attention when ``kv_override`` is given).

    positions: (s,) absolute positions of the ``s`` tokens in ``x``, or
    (b, s) *per-row* positions (the continuous-batching decode step: each
    slot sits at its own length; -1 marks an inactive slot whose row is
    garbage the step-level merge discards — DESIGN.md §12).
    Returns (y, new_cache).  With a cache and s==1 this is one decode step.
    """
    dt = jnp.dtype(cfg.dtype)
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    qspec, _ = _head_axes(hq)
    pos2d = positions if positions.ndim == 2 else positions[None, :]

    q = _split_heads(common.linear(params["wq"], x, compute_dtype=dt), hq, hd)
    kv_src = x if kv_override is None else kv_override
    k = _split_heads(common.linear(params["wk"], kv_src, compute_dtype=dt), hkv, hd)
    v = _split_heads(common.linear(params["wv"], kv_src, compute_dtype=dt), hkv, hd)

    if cfg.qk_norm:
        q = common.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = common.rmsnorm(params["k_norm"], k, cfg.norm_eps)

    if cfg.rope:
        q = apply_rope(q, pos2d, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, pos2d, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        # Every attention path scales scores by hd ** -0.5; a published
        # scale enters as q pre-scaled by the ratio (one bf16 rounding).
        ratio = cfg.attention_multiplier * hd ** 0.5
        q = (q.astype(jnp.float32) * ratio).astype(q.dtype)

    q = shard_activation(q, qspec)

    new_cache = None
    if isinstance(cache, PagedKVCache) and kv_override is None:
        new_cache, out = _paged_decode(cfg, cache, q, k, v, pos2d, dt, g)
    elif cache is not None and kv_override is None:
        # Ring-buffer write: slot = pos % capacity (windowed caches stay
        # O(window) even at 500k-token contexts).
        cap = cache.k.shape[1]
        slots = pos2d % cap  # (1|b, s); broadcasts against bidx
        bidx = jnp.arange(b)[:, None]
        k_new = cache.k.at[bidx, slots].set(k.astype(cache.k.dtype))
        v_new = cache.v.at[bidx, slots].set(v.astype(cache.v.dtype))
        pos_new = cache.pos.at[bidx, slots].set(
            jnp.broadcast_to(pos2d, (b, s)))
        new_cache = KVCache(k_new, v_new, pos_new)
        if s == 1:
            # Decode: attend over the cache with per-slot positions.
            mesh = current_mesh()
            msize = mesh.shape.get("model", 1) if mesh is not None else 1
            seq_sharded = msize > 1 and hkv % msize != 0 \
                and cache.k.shape[1] % msize == 0
            if (get_config().backend == "pallas" and window is None
                    and not cfg.attn_logit_softcap and not seq_sharded
                    and cap % DECODE_PAGE_SIZE == 0
                    and new_cache.k.dtype == dt):
                # The serving runtime's decode kernel and page walk.
                out = _dense_decode_paged(new_cache, q, pos2d)
            else:
                kf = _repeat_kv(new_cache.k.astype(dt), g)
                vf = _repeat_kv(new_cache.v.astype(dt), g)
                if seq_sharded:
                    kv_spec = (("pod", "data"), "model", None, None)
                    kf = shard_activation(kf, kv_spec)
                    vf = shard_activation(vf, kv_spec)
                qpos = pos2d[:, -1].reshape(-1, 1, 1, 1)  # (1|b, 1, 1, 1)
                mask = (new_cache.pos[:, None, None, :] <= qpos)
                if window is not None:
                    mask &= new_cache.pos[:, None, None, :] > qpos - window
                mask &= new_cache.pos[:, None, None, :] >= 0
                out = _attend(q, kf, vf, mask, cfg.attn_logit_softcap,
                              kv_seq_sharded=seq_sharded)
        else:
            out = _attention_seq(q, _repeat_kv(k, g), _repeat_kv(v, g),
                                 positions, positions, window,
                                 cfg.attn_logit_softcap)
    elif kv_override is not None:
        # Cross-attention: all encoder positions visible.  Under the
        # pallas backend this is the non-causal flash case — the schedule
        # layer's ragged sq/sk handling (DESIGN.md §10) covers decoder
        # and encoder lengths that disagree, so no mask tensor is built.
        if get_config().backend == "pallas" and not cfg.attn_logit_softcap:
            from repro.kernels.flash_attention import flash_attention
            out = flash_attention(q, _repeat_kv(k, g), _repeat_kv(v, g),
                                  causal=False)
        else:
            sk = k.shape[1]
            mask = jnp.ones((1, 1, s, sk), bool)
            out = _attend(q, _repeat_kv(k, g), _repeat_kv(v, g), mask,
                          cfg.attn_logit_softcap)
    else:
        out = _attention_seq(q, _repeat_kv(k, g), _repeat_kv(v, g),
                             positions, positions, window,
                             cfg.attn_logit_softcap)

    out = out.reshape(b, s, hq * hd)
    y = common.linear(params["wo"], out, compute_dtype=dt)
    return y, new_cache
