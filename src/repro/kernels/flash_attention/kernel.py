"""Flash-attention forward Pallas kernels (causal, online softmax).

Built from the same microkernel discipline as the GEMM engine: the
(block_q, block_k) score tile is the ZA-accumulator analogue, the K-grid
is the contraction loop, and causal masking is trace-time-specialized
predication (§IV-B).  Two lowerings (DESIGN.md §10):

  * **fused** (``build_fused_flash_kernel``): ONE ``pallas_call`` walks
    the causal-aware :class:`~repro.core.schedule.FlashTileSchedule` —
    fully-masked k-blocks are dropped at *plan* time, so the supergrid is
    ``(batch_heads, active_tiles)`` rather than the dense
    ``(b*h, q_blocks, k_blocks)`` cube.  The online-softmax m/l/acc carry
    threads through the flat tile walk as VMEM accumulator state (reset
    at each q-block's ``first`` tile, drained at its ``last``); ragged
    sq/sk tails use the schedule layer's two-step clamped windows and
    predicated RMW stores instead of padding.
  * **dense grid** (``build_flash_kernel``, the pre-schedule lowering,
    kept for VMEM-oversized problems and as the autotuner's
    alternative): grid = (b*h, q_blocks, k_blocks); masked causal tiles
    are branched away with ``pl.when`` but still pay their grid steps.

Serving path on TPU; training uses the XLA chunked formulation in
``repro.models.attention`` (same math, autodiff-friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (DecodeTileSchedule, FlashTileSchedule,
                                 decode_vmem_need, flash_bwd_vmem_need,
                                 flash_vmem_need,
                                 ownership_mask, pack_table,
                                 predicated_store, vmem_limit)

NEG_INF = -1e30


def _carry_init(m_ref, l_ref, acc_ref):
    """Reset the online-softmax carry (running max / denominator / output
    accumulator) — shared by both lowerings so their float ops coincide."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref):
    """One online-softmax step on a masked score tile ``s`` (fp32) and its
    value tile ``v``.  Both lowerings call exactly this, which is what the
    fused path's bit-identical parity contract rests on (DESIGN.md §10)."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _carry_drain(l_ref, acc_ref, out_dtype):
    """Normalized output of a drained carry, cast to the output dtype."""
    return (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(out_dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q, block_k, k_steps, sk, causal, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _carry_init(m_ref, l_ref, acc_ref)

    # causal: skip tiles strictly above the diagonal (ZA-cover analogue)
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    k_ragged = sk % block_k != 0

    @pl.when(run)
    def _step():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        if k_ragged:
            # KV-tail predication (trace-time specialized, §IV-B): padded
            # rows may be garbage/NaN — `where`, never multiply.
            krow = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            v = jnp.where(krow < sk, v, 0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal and k_ragged:
            s = jnp.where((kpos <= qpos) & (kpos < sk), s, NEG_INF)
        elif causal:
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        elif k_ragged:
            s = jnp.where(kpos < sk, s, NEG_INF)

        _online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(ki == k_steps - 1)
    def _store():
        o_ref[0] = _carry_drain(l_ref, acc_ref, o_ref.dtype)


def build_flash_kernel(*, batch_heads: int, sq: int, sk: int, d: int,
                       block_q: int = 512, block_k: int = 512,
                       causal: bool = True, dtype=jnp.bfloat16,
                       interpret: bool = False):
    """Returns f(q:(BH,sq,d), k:(BH,sk,d), v:(BH,sk,d)) -> (BH,sq,d)."""
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (batch_heads, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    body = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        k_steps=grid[2], sk=sk, causal=causal, scale=d ** -0.5)
    return pl.pallas_call(
        body,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch_heads, sq, d), dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused scheduled lowering (DESIGN.md §10): one launch, causal tiles
# dropped at plan time, m/l carry threaded through the flat tile walk
# ---------------------------------------------------------------------------

def _fused_flash_kernel(tbl_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, schedule, d, scale,
                        lse_ref=None):
    """Walk the flattened causal-aware tile table: one grid step = one
    active (q-block, k-block) pair.  q/k/v/out are staged whole per
    batch-head slice (clamped ragged windows need element-granular
    origins); the online-softmax carry lives in VMEM scratch, reset at
    ``first`` tiles and drained into the output — with a predicated
    two-step RMW store over the owned query rows — at ``last`` tiles."""
    bq, bk, causal = schedule.bq, schedule.bk, schedule.causal
    t = pl.program_id(1)
    q0, q_end, k0, k_end = (tbl_ref[t, 0], tbl_ref[t, 1], tbl_ref[t, 3],
                            tbl_ref[t, 4])
    qs, ks = _window_origins(tbl_ref, t, schedule)

    @pl.when(tbl_ref[t, 6] == 1)
    def _init():
        _carry_init(m_ref, l_ref, acc_ref)

    q = q_ref[0, pl.ds(qs, bq), :]  # (bq, d), two-step clamped window
    k = k_ref[0, pl.ds(ks, bk), :]  # (bk, d)
    v = _rows_below(v_ref[0, pl.ds(ks, bk), :], ks, schedule.sk,
                    schedule.sk_p)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # Predicate the tile's contribution range [k0, k_end): the clamped
    # window may revisit columns owned by the previous k tile (sk tail)
    # — plus the causal triangle.  `where`, never multiply (§IV-B).
    qpos = qs + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ks + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = (kpos >= k0) & (kpos < k_end)
    if causal:
        valid &= kpos <= qpos
    s = jnp.where(valid, s, NEG_INF)

    _online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(tbl_ref[t, 7] == 1)
    def _store():
        out = _carry_drain(l_ref, acc_ref, o_ref.dtype)
        # Predicated two-step store: the clamped window covers rows the
        # previous q-block already drained — write only owned rows.
        own = ownership_mask((bq, d), qs, 0, q0, q_end, 0, d)
        predicated_store(o_ref, (0, pl.ds(qs, bq), pl.ds(0, d)), out, own)
        if lse_ref is not None:
            # Log-sum-exp rows for the backward walk (DESIGN.md §11):
            # lse = m + log(l), the softmax statistics the VJP recomputes
            # P from without re-running the online reduction.
            lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
            own1 = ownership_mask((bq, 1), qs, 0, q0, q_end, 0, 1)
            predicated_store(lse_ref, (0, pl.ds(qs, bq), pl.ds(0, 1)),
                             lse, own1)


def _window_origins(tbl_ref, t, schedule):
    """The tile's clamped q / k window origins, with the alignment the
    schedule proves for them (Mosaic must prove it to slice VMEM)."""
    return (pl.multiple_of(tbl_ref[t, 2], schedule.q_align),
            pl.multiple_of(tbl_ref[t, 5], schedule.k_align))


def _rows_below(x, origin, extent, staged):
    """Zero the rows of a window at/after ``extent``: a window over a
    padded staged buffer (``staged > extent``) reads block padding, which
    may be non-finite and would leak through a zero probability."""
    if staged == extent:
        return x
    rows = origin + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < extent, x, 0)


def build_fused_flash_kernel(*, schedule: FlashTileSchedule,
                             batch_heads: int, d: int,
                             dtype=jnp.bfloat16, interpret: bool = False,
                             return_lse: bool = False):
    """Generate ONE pallas_call executing a whole flash tile schedule.

    Returns ``f(q:(BH,sq,d), k:(BH,sk,d), v:(BH,sk,d)) -> (BH,sq,d)``.
    The supergrid is ``(batch_heads, schedule.num_tiles)`` — batch x heads
    folded in as the leading parallel dimension, the causal-pruned tile
    walk as the sequential carry dimension — and the tile table rides in
    scalar-prefetch SMEM (DESIGN.md §10).

    ``return_lse=True`` additionally drains the log-sum-exp rows
    (``(BH, sq)`` fp32) — the residual the backward walk recomputes P
    from (DESIGN.md §11); the forward math is bit-identical either way.
    """
    sq = schedule.sq
    bq = schedule.bq
    table = pack_table(schedule.tiles)  # (tiles, 8) int32, trace-time
    # Slices are staged at the schedule's padded extents (the blocks
    # overhang ragged sequences so clamped windows keep aligned origins).
    spec_q = pl.BlockSpec((1, schedule.sq_p, d), lambda b, t, tbl: (b, 0, 0))
    spec_k = pl.BlockSpec((1, schedule.sk_p, d), lambda b, t, tbl: (b, 0, 0))

    opts = dict(schedule=schedule, d=d, scale=d ** -0.5)
    if return_lse:
        def body(tbl, q, k, v, o_ref, lse_ref, m_ref, l_ref, acc_ref):
            _fused_flash_kernel(tbl, q, k, v, o_ref, m_ref, l_ref, acc_ref,
                                lse_ref=lse_ref, **opts)
        out_shape = [jax.ShapeDtypeStruct((batch_heads, sq, d), dtype),
                     jax.ShapeDtypeStruct((batch_heads, sq, 1), jnp.float32)]
        out_specs = [spec_q, pl.BlockSpec((1, schedule.sq_p, 1),
                                          lambda b, t, tbl: (b, 0, 0))]
    else:
        body = functools.partial(_fused_flash_kernel, **opts)
        out_shape = jax.ShapeDtypeStruct((batch_heads, sq, d), dtype)
        out_specs = spec_q

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the tile table
        grid=(batch_heads, schedule.num_tiles),
        in_specs=[spec_q, spec_k, spec_k],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denom
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
    )

    kernel = pl.pallas_call(
        body,
        name="flash_attention",
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # batch x heads parallel; the tile walk is the sequential
            # carry dimension (the online-softmax state threads it)
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(flash_vmem_need(
                schedule.sq_p, schedule.sk_p, d,
                isz=jnp.dtype(dtype).itemsize, bq=bq, bk=schedule.bk,
                lse=return_lse)),
        ),
        interpret=interpret,
    )

    def run(q, k, v):
        if return_lse:
            o, lse = kernel(table, q, k, v)
            return o, lse[..., 0]
        return kernel(table, q, k, v)

    return run


# ---------------------------------------------------------------------------
# Paged decode lowering (DESIGN.md §12): one launch walks the runtime
# DecodeTileSchedule — one grid step = one block of up to pages_per_step
# live KV pages of one sequence, copied from the pool in HBM by hand,
# the next row's pages in flight while this row computes
# ---------------------------------------------------------------------------

def _decode_flash_kernel(tbl_ref, layer_ref, bt_ref, *refs, page_size,
                         pages_per_step, max_blocks, pages, num_kv_heads,
                         rep, scale, kv_quant=False):
    """One grid step of the paged decode walk.

    ``tbl_ref`` rows are ``(seq, page, k_len, first, last)``
    (:class:`~repro.core.schedule.DecodeTileSchedule`): the BlockSpec
    index maps pulled query row ``seq`` into VMEM, and the body copies
    the row's live pages — block-table entries ``page`` onward of
    sequence ``seq`` (``bt_ref``, flattened), ``ceil(k_len / P)`` of
    them — of layer ``layer_ref[0]``'s pool from HBM into one of two
    VMEM buffers.  Step ``t`` starts row ``t + 1``'s copies into the
    other buffer before it waits for its own, so the next block's pages
    stream in while this one computes.  A row with ``k_len`` 0 (an empty
    slot's row, or any row past the live total) copies and computes
    nothing; the ``first``/``last`` flags still reset and drain the
    carry — the same m/l/acc discipline as the fused flash walk, batched
    over heads instead of query rows.

    The block is contracted as one ``(pps * P * hkv, hd)`` matrix:
    column ``c`` of the ``(h, pps * P * hkv)`` score tile is position
    ``c // hkv`` of KV head ``c % hkv``, and each query head keeps only
    its own KV head's columns (GQA by mask).  Both contractions are then
    plain 2-D GEMMs, which is what Mosaic lowers.  The other heads'
    columns are ``hkv``x the score work; on a v5e at 8/16 heads of 128 a
    static loop of per-KV-head dots took 1.7x as long per page.

    ``kv_quant`` (DESIGN.md §13): the pools are int8 with per-token f32
    scale rows (one per score column) copied beside each page.  The
    scales are *separable by page position*, so dequant never touches
    the KV tiles: the K scales multiply the score columns
    (``q . (k*s) == (q . k) * s``) and the V scales fold into P before
    the PV contraction (``sum_p p . (v*s) == sum_p (p*s) . v``) — both
    lane-dim row broadcasts, no 3-D elementwise dequant."""
    refs = list(refs)
    q_ref, k_hbm, v_hbm = refs[:3]
    ks_hbm, vs_hbm = (refs[3:5] if kv_quant else (None, None))
    del refs[:5 if kv_quant else 3]
    o_ref, kbuf, vbuf = refs[:3]
    ksbuf, vsbuf = (refs[3:5] if kv_quant else (None, None))
    sem, m_ref, l_ref, acc_ref = refs[5 if kv_quant else 3:]

    t = pl.program_id(0)
    slot = t % 2
    layer = layer_ref[0]
    cols = pages_per_step * page_size * num_kv_heads

    def copies(row, buf, j):
        """The copies of page ``j`` of row ``row`` into buffer ``buf``."""
        entry = tbl_ref[row, 0] * max_blocks + tbl_ref[row, 1] + j
        page = jnp.clip(bt_ref[entry], 0, pages - 1)
        out = [pltpu.make_async_copy(k_hbm.at[layer, page],
                                     kbuf.at[buf, j], sem.at[buf]),
               pltpu.make_async_copy(v_hbm.at[layer, page],
                                     vbuf.at[buf, j], sem.at[buf])]
        if kv_quant:
            out += [pltpu.make_async_copy(ks_hbm.at[page],
                                          ksbuf.at[buf, j], sem.at[buf]),
                    pltpu.make_async_copy(vs_hbm.at[page],
                                          vsbuf.at[buf, j], sem.at[buf])]
        return out

    def each_page(row, buf, act):
        """``act`` on the copies of every live page of ``row``."""
        def body(j, carry):
            for c in copies(row, buf, j):
                act(c)
            return carry
        live = (tbl_ref[row, 2] + page_size - 1) // page_size
        jax.lax.fori_loop(0, live, body, 0)

    @pl.when(t == 0)
    def _first_row():
        each_page(0, 0, lambda c: c.start())

    @pl.when(t + 1 < pl.num_programs(0))
    def _prefetch_next_row():
        each_page(t + 1, 1 - slot, lambda c: c.start())

    each_page(t, slot, lambda c: c.wait())
    k_len = tbl_ref[t, 2]

    @pl.when(tbl_ref[t, 3] == 1)
    def _init():
        _carry_init(m_ref, l_ref, acc_ref)

    @pl.when(k_len > 0)
    def _step():
        q = q_ref[0]                                   # (h, hd)
        # int8 wire values are exact in the wide dtype.
        k = kbuf[slot].astype(q.dtype).reshape(cols, -1)   # (pps*P*hkv, hd)
        v = vbuf[slot].astype(q.dtype).reshape(cols, -1)
        # Pages past the live ones hold an earlier row's values, or none
        # yet — `where`, never multiply (§IV-B).
        pos = (jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
               // num_kv_heads)
        v = jnp.where(pos < k_len, v, 0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if kv_quant:
            s = s * ksbuf[slot].reshape(1, cols)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        live = ((col // num_kv_heads < k_len)
                & (col % num_kv_heads == head // rep))
        s = jnp.where(live, s, NEG_INF)

        # Per-head online-softmax update — the m/l algebra of
        # `_online_softmax_update`, with the V scales folded into P.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if kv_quant:
            p = p * jnp.where(pos.reshape(1, cols) < k_len,
                              vsbuf[slot].reshape(1, cols), 0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(tbl_ref[t, 4] == 1)
    def _store():
        o_ref[0] = _carry_drain(l_ref, acc_ref, o_ref.dtype)


def build_decode_flash_kernel(*, schedule: DecodeTileSchedule,
                              num_heads: int, num_kv_heads: int,
                              head_dim: int, dtype=jnp.bfloat16,
                              kv_dtype=None, kv_quant: bool = False,
                              interpret: bool = False):
    """Generate ONE pallas_call executing a whole paged decode step.

    Returns ``f(table, layer, block_tables, q:(S,h,hd),
    k_pool:(L,pages,P,hkv,hd), v_pool) -> (S,h,hd)`` where ``table`` is
    the runtime ``(max_tiles, 5)`` int32 tile table
    (:meth:`DecodeTileSchedule.tables`), ``layer`` a ``(1,)`` int32
    index into the pools' leading layer dim and ``block_tables`` the
    ``(S * max_blocks,)`` int32 page ids, flattened.  All three are
    *scalar-prefetch operands*: the batch composition and the layer are
    data, so the kernel compiles once per pool geometry and neither the
    churning batch nor the layer scan retraces.  The pools stay in HBM
    (``memory_space=ANY``) and the body copies each row's live pages of
    layer ``layer[0]`` straight from the whole stack the decode step
    carries through its layer scan, so no layer's pool is ever sliced
    out or copied whole (DESIGN.md §12).  With ``kv_quant`` two more
    operands ``(pages, 1, P * hkv)`` hold the walked layer's per-column
    dequant scales.  The table stays the first operand: the benchmark's
    trace tells the kernel apart by it."""
    S, P, pps = schedule.num_seqs, schedule.page_size, schedule.pages_per_step
    h, hkv, hd = num_heads, num_kv_heads, head_dim
    kv_dtype = kv_dtype or dtype
    body = functools.partial(
        _decode_flash_kernel, page_size=P, pages_per_step=pps,
        max_blocks=schedule.max_blocks, pages=schedule.pages,
        num_kv_heads=hkv, rep=h // hkv, scale=hd ** -0.5, kv_quant=kv_quant)

    def row(t, tbl, layer, bt):
        return (tbl[t, 0], 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, h, hd), row), hbm, hbm]
    scratch = [pltpu.VMEM((2, pps, P, hkv, hd), kv_dtype),
               pltpu.VMEM((2, pps, P, hkv, hd), kv_dtype)]
    if kv_quant:
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((2, pps, 1, P * hkv), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),     # one per page buffer
        pltpu.VMEM((h, 1), jnp.float32),   # running max
        pltpu.VMEM((h, 1), jnp.float32),   # running denom
        pltpu.VMEM((h, hd), jnp.float32),  # output accumulator
    ]
    need = decode_vmem_need(pps, page_size=P, num_heads=h, num_kv_heads=hkv,
                            head_dim=hd, isz=jnp.dtype(dtype).itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the tile table, the layer, block tables
        grid=(schedule.max_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), row),
        scratch_shapes=scratch,
    )

    return pl.pallas_call(
        body,
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, h, hd), dtype),
        compiler_params=pltpu.CompilerParams(
            # one sequential dimension: the carry and the page buffers
            # thread the walk
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(need),
        ),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused scheduled backward (DESIGN.md §11): ONE launch walks the same
# causal-pruned tile table as the forward, producing dQ/dK/dV with the
# D = rowsum(dO . O) precompute fused into each q-block's first tile
# ---------------------------------------------------------------------------

def _fused_flash_bwd_kernel(tbl_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                            lse_ref, dq_ref, dk_ref, dv_ref,
                            d_ref, dqacc_ref, *, schedule, d, scale):
    """One grid step = one active (q-block, k-block) pair of the forward
    schedule.  P is recomputed from the staged LSE rows (no second online
    reduction); dK/dV accumulate fp32 across q-blocks by read-modify-write
    on the whole-staged outputs (contributions outside a tile's owned
    rows/cols are masked to zero, so clamped-window overlap adds zero);
    dQ accumulates in scratch across a q-block's k walk and drains with a
    predicated store at ``last`` tiles.  Windows over padded staged
    buffers zero their padding rows: a non-finite pad times a zero
    probability would otherwise leak into the RMW-accumulated dK/dV."""
    bq, bk, causal = schedule.bq, schedule.bk, schedule.causal
    sq, sk, sq_p, sk_p = schedule.sq, schedule.sk, schedule.sq_p, schedule.sk_p
    t = pl.program_id(1)
    q0, q_end, k0, k_end = (tbl_ref[t, 0], tbl_ref[t, 1], tbl_ref[t, 3],
                            tbl_ref[t, 4])
    qs, ks = _window_origins(tbl_ref, t, schedule)

    @pl.when(t == 0)
    def _zero_outputs():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    o_win = _rows_below(o_ref[0, pl.ds(qs, bq), :].astype(jnp.float32),
                        qs, sq, sq_p)
    do_win = _rows_below(do_ref[0, pl.ds(qs, bq), :].astype(jnp.float32),
                         qs, sq, sq_p)

    @pl.when(tbl_ref[t, 6] == 1)
    def _init():
        # D = rowsum(dO . O), computed once per q-block on its first tile
        # and carried in scratch for the rest of the k walk.
        d_ref[...] = jnp.sum(do_win * o_win, axis=1, keepdims=True)
        dqacc_ref[...] = jnp.zeros_like(dqacc_ref)

    q = _rows_below(q_ref[0, pl.ds(qs, bq), :], qs, sq, sq_p)
    k = _rows_below(k_ref[0, pl.ds(ks, bk), :], ks, sk, sk_p)
    v = _rows_below(v_ref[0, pl.ds(ks, bk), :], ks, sk, sk_p)
    lse = _rows_below(lse_ref[0, pl.ds(qs, bq), :], qs, sq, sq_p)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # Own both axes: unlike the forward (whose per-q-block carry only
    # needed the k-range predicate), the backward RMW-accumulates dK/dV
    # across q-blocks, so clamped-window rows another q-block owns must
    # contribute exactly zero.
    qpos = qs + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ks + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = (kpos >= k0) & (kpos < k_end) & (qpos >= q0) & (qpos < q_end)
    if causal:
        valid &= kpos <= qpos
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # (bq, bk) fp32

    # dV += P^T @ dO — zero rows outside [k0, k_end) make the clamped
    # k-window overlap-add a no-op.
    dv_ref[0, pl.ds(ks, bk), :] += jax.lax.dot_general(
        p, do_win, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    dp = jax.lax.dot_general(do_win, v.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[...]) * scale  # (bq, bk) fp32

    # dK += dS^T @ Q
    dk_ref[0, pl.ds(ks, bk), :] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # dQ accumulates over the q-block's k walk in scratch.
    dqacc_ref[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(tbl_ref[t, 7] == 1)
    def _store_dq():
        own = ownership_mask((bq, d), qs, 0, q0, q_end, 0, d)
        predicated_store(dq_ref, (0, pl.ds(qs, bq), pl.ds(0, d)),
                         dqacc_ref[...], own)


def build_fused_flash_bwd_kernel(*, schedule: FlashTileSchedule,
                                 batch_heads: int, d: int,
                                 dtype=jnp.bfloat16, interpret: bool = False):
    """Generate ONE pallas_call executing a whole flash backward schedule.

    Returns ``f(q, k, v, o, do, lse) -> (dq, dk, dv)`` over ``(BH, s, d)``
    operands (``lse``: ``(BH, sq)`` fp32); gradients come back fp32 (the
    ops wrapper casts).  Supergrid, tile table and predication mirror
    :func:`build_fused_flash_kernel` — the backward walks the *same*
    causal-pruned schedule, so it skips the same fully-masked k-blocks
    (DESIGN.md §11).
    """
    sq, sk = schedule.sq, schedule.sk
    bq = schedule.bq
    table = pack_table(schedule.tiles)

    body = functools.partial(_fused_flash_bwd_kernel, schedule=schedule,
                             d=d, scale=d ** -0.5)

    spec_q = pl.BlockSpec((1, schedule.sq_p, d), lambda b, t, tbl: (b, 0, 0))
    spec_k = pl.BlockSpec((1, schedule.sk_p, d), lambda b, t, tbl: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch_heads, schedule.num_tiles),
        in_specs=[spec_q, spec_k, spec_k, spec_q, spec_q,
                  pl.BlockSpec((1, schedule.sq_p, 1),
                               lambda b, t, tbl: (b, 0, 0))],
        out_specs=[spec_q, spec_k, spec_k],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # D = rowsum(dO . O)
            pltpu.VMEM((bq, d), jnp.float32),  # dQ accumulator
        ],
    )

    kernel = pl.pallas_call(
        body,
        name="flash_attention_bwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch_heads, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((batch_heads, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((batch_heads, sk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(flash_bwd_vmem_need(
                schedule.sq_p, schedule.sk_p, d,
                isz=jnp.dtype(dtype).itemsize, bq=bq, bk=schedule.bk)),
        ),
        interpret=interpret,
    )

    def run(q, k, v, o, do, lse):
        return kernel(table, q, k, v, o, do, lse[..., None])

    return run
