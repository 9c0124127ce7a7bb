"""SSD chunked-scan Pallas kernels — the paper's small-GEMM ladder in its
Mamba-2 habitat (arXiv:2405.21060 §6, "state-space duality").

Each grid step processes one (batch x chunk x head) cell entirely in
VMEM: two back-to-back small GEMMs — (Q,n)x(n,Q) then the decay-masked
(Q,Q)x(Q,p) — with the (Q,Q) score tile as the ZA-style accumulator that
never touches HBM.  Q, n, p are all in the 64-256 range: exactly the
"small odd GEMM" population the paper's engine targets (DESIGN.md §4).

Two lowerings (DESIGN.md §10):

  * **fused scan** (``build_ssd_scan_kernel``): ONE ``pallas_call`` over
    a ``(groups, chunks)`` supergrid executes the *whole* chunked scan —
    the intra-chunk ladder above plus the inter-chunk recurrence — with
    the ``(p, n)`` state carried across the sequential chunk dimension
    as VMEM accumulator scratch.  The per-chunk state tensors the XLA
    formulation materializes around its associative scan never exist.
  * **intra-chunk only** (``build_ssd_chunk_kernel``, the pre-schedule
    lowering, kept as the fallback half of the non-fused path): the diag
    ladder over a flat group grid; the inter-chunk recurrence then runs
    as separate XLA ops in ``repro.kernels.ssd_chunk.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_body(c_ref, b_ref, l_ref, x_ref, o_ref, s_ref):
    c = c_ref[0]          # (Q, n)
    b = b_ref[0]          # (Q, n)
    l = l_ref[0]          # (Q, Q) decay mask
    x = x_ref[0]          # (Q, p)
    # GEMM 1: scores = C · Bᵀ (contract the state dim; fused transpose)
    s_ref[...] = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # elementwise decay mask in-register (the predication analogue)
    w = (s_ref[...] * l.astype(jnp.float32)).astype(x.dtype)
    # GEMM 2: y = W · xdt
    o_ref[0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def build_ssd_chunk_kernel(*, groups: int, q: int, n: int, p: int,
                           dtype=jnp.float32, interpret: bool = False):
    """f(C:(G,Q,n), B:(G,Q,n), L:(G,Q,Q), xdt:(G,Q,p)) -> (G,Q,p)."""
    return pl.pallas_call(
        _ssd_chunk_body,
        name="ssd_chunk",
        grid=(groups,),
        in_specs=[
            pl.BlockSpec((1, q, n), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, q, n), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, q, q), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, q, p), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((groups, q, p), dtype),
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused carried-state scan (DESIGN.md §10): one launch for the whole scan
# ---------------------------------------------------------------------------

def _chunk_decay_row(decay_in, n):
    """The whole-chunk decay (decay-in's last row) broadcast to a
    ``(G, NC, 1, n)`` row operand: scaling the ``(p, n)`` state by it is a
    sublane broadcast, where one element picked out of the ``(Q, 1)``
    column would need a broadcast in both sublanes and lanes, which
    Mosaic does not lower."""
    return jnp.broadcast_to(decay_in[..., -1:, None],
                            decay_in.shape[:2] + (1, n))


def _ssd_scan_body(c_ref, b_ref, l_ref, x_ref, di_ref, do_ref, cd_ref,
                   s0_ref, y_ref, sf_ref, *rest, q, chunks):
    """One grid step = one (group, chunk) cell; the chunk dimension is
    sequential, so ``state_ref`` (the (p, n) SSM state, fp32) carries
    across it as accumulator scratch — the inter-chunk recurrence *is*
    the tile walk, not a separate dispatch.  With ``return_states`` the
    state *entering* each chunk is also drained per cell (the residual
    the backward walk replays, DESIGN.md §11)."""
    states_ref = rest[0] if len(rest) == 2 else None
    state_ref = rest[-1]
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    if states_ref is not None:
        states_ref[0, 0] = state_ref[...]

    c = c_ref[0, 0]          # (Q, n)
    b = b_ref[0, 0]          # (Q, n)
    l = l_ref[0, 0]          # (Q, Q) decay mask
    x = x_ref[0, 0]          # (Q, p)
    di = di_ref[0, 0]        # (Q, 1) decay into each row from chunk start
    do = do_ref[0, 0]        # (Q, 1) decay from each row to chunk end
    state = state_ref[...]   # (p, n) state *entering* this chunk

    # inter-chunk contribution: y_off = (C · S_prevᵀ) ⊙ decay_in
    y_off = jax.lax.dot_general(
        c.astype(jnp.float32), state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * di
    # intra-chunk ladder (identical math to _ssd_chunk_body)
    s = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    w = (s * l.astype(jnp.float32)).astype(x.dtype)
    y_diag = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S ← S · exp(da_tot) + Bᵀ · (xdt ⊙ decay_out); the
    # whole-chunk decay is decay_in's last element (da_cs[-1] == da_tot).
    xw = (x.astype(jnp.float32) * do).astype(x.dtype)
    bx = jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = state * cd_ref[0, 0] + bx

    @pl.when(ci == chunks - 1)
    def _final():
        sf_ref[0] = state_ref[...]


def build_ssd_scan_kernel(*, groups: int, chunks: int, q: int, n: int,
                          p: int, dtype=jnp.float32, interpret: bool = False,
                          return_states: bool = False):
    """Generate ONE pallas_call executing a whole chunked SSD scan.

    Returns ``f(C, B, L, xdt, decay_in, decay_out, s0) -> (y, s_final)``
    over ``C/B: (G, NC, Q, n)``, ``L: (G, NC, Q, Q)``,
    ``xdt: (G, NC, Q, p)``, ``decay_in/decay_out: (G, NC, Q)``,
    ``s0: (G, p, n)`` fp32 — yielding ``y: (G, NC, Q, p)`` and the final
    state ``(G, p, n)`` fp32.  The supergrid is ``(groups, chunks)`` with
    the chunk dimension sequential (the carried-state walk).

    ``return_states`` appends a third output, the fp32 state *entering*
    each chunk, ``(G, NC, p, n)`` — the residual the reverse-walk
    backward replays (DESIGN.md §11).
    """
    body = functools.partial(_ssd_scan_body, q=q, chunks=chunks)
    out_specs = [
        pl.BlockSpec((1, 1, q, p), lambda g, c: (g, c, 0, 0)),
        pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((groups, chunks, q, p), dtype),
        jax.ShapeDtypeStruct((groups, p, n), jnp.float32),
    ]
    if return_states:
        out_specs.append(pl.BlockSpec((1, 1, p, n), lambda g, c: (g, c, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((groups, chunks, p, n), jnp.float32))
    kernel = pl.pallas_call(
        body,
        name="ssd_chunk",
        grid=(groups, chunks),
        in_specs=[
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, q, q), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, n), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )

    def run(c, b, l, xdt, decay_in, decay_out, s0):
        # Decays ride as (Q, 1) columns: a row-broadcast Mosaic lowers.
        return kernel(c, b, l, xdt, decay_in[..., None],
                      decay_out[..., None], _chunk_decay_row(decay_in, n), s0)

    return run


# ---------------------------------------------------------------------------
# Fused carried-state backward (DESIGN.md §11): one reverse-walk launch
# ---------------------------------------------------------------------------

def _ssd_scan_bwd_body(c_ref, b_ref, l_ref, x_ref, di_ref, do_ref, cd_ref,
                       states_ref, dy_ref, dsf_ref, dc_ref, db_ref, dl_ref,
                       dx_ref, ddi_ref, ddo_ref, ds0_ref, ds_ref, *,
                       q, chunks):
    """One grid step = one (group, chunk) cell walked in *reverse* chunk
    order (the BlockSpec index maps flip the chunk coordinate); the
    ``(p, n)`` state cotangent carries backward through the walk as
    accumulator scratch, exactly mirroring the forward's carried state.
    Every per-cell quantity the chain rule needs (scores, decay-weighted
    windows) is recomputed in-register from the staged operands — only
    the carried state itself rides in from the forward as a residual."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        ds_ref[...] = dsf_ref[0]

    c = c_ref[0, 0].astype(jnp.float32)      # (Q, n)
    b = b_ref[0, 0].astype(jnp.float32)      # (Q, n)
    l = l_ref[0, 0].astype(jnp.float32)      # (Q, Q)
    x = x_ref[0, 0].astype(jnp.float32)      # (Q, p)
    di = di_ref[0, 0]                        # (Q, 1) fp32
    do = do_ref[0, 0]                        # (Q, 1) fp32
    s_in = states_ref[0, 0]                  # (p, n) state entering chunk
    dy = dy_ref[0, 0].astype(jnp.float32)    # (Q, p)
    ds_out = ds_ref[...]                     # (p, n) cotangent of S_out

    # state update S_out = S_in * di[Q-1] + Bᵀ(x ⊙ do) backward: the
    # carried cotangent splits into the decay leg and the Bx leg.
    ds_in = ds_out * cd_ref[0, 0]
    ddi_last = jnp.sum(s_in * ds_out)        # scalar -> ddi[Q-1]
    dxw = jax.lax.dot_general(b, ds_out, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q, p)
    xw = x * do
    db = jax.lax.dot_general(xw, ds_out, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, n)
    dx = dxw * do
    ddo = jnp.sum(dxw * x, axis=1, keepdims=True)                  # (Q, 1)

    # intra-chunk ladder backward: recompute scores/W, then walk
    # y = (scores ⊙ L) · x backward.
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * l
    dw = jax.lax.dot_general(dy, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    dx += jax.lax.dot_general(w, dy, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dscores = dw * l
    dl = dw * scores
    dc = jax.lax.dot_general(dscores, b, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    db += jax.lax.dot_general(dscores, c, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

    # inter-chunk offset y_off = (C · S_inᵀ) ⊙ di backward.
    a = dy * di
    y_off_raw = jax.lax.dot_general(c, s_in, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    dc += jax.lax.dot_general(a, s_in, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds_in += jax.lax.dot_general(a, c, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ddi = jnp.sum(dy * y_off_raw, axis=1, keepdims=True)           # (Q, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    ddi += jnp.where(row == q - 1, ddi_last, 0.0)

    dc_ref[0, 0] = dc.astype(dc_ref.dtype)
    db_ref[0, 0] = db.astype(db_ref.dtype)
    dl_ref[0, 0] = dl.astype(dl_ref.dtype)
    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    ddi_ref[0, 0] = ddi
    ddo_ref[0, 0] = ddo
    ds_ref[...] = ds_in

    @pl.when(ci == chunks - 1)
    def _final():
        ds0_ref[0] = ds_ref[...]


def build_ssd_scan_bwd_kernel(*, groups: int, chunks: int, q: int, n: int,
                              p: int, dtype=jnp.float32,
                              interpret: bool = False):
    """Generate ONE reverse-walk pallas_call for the chunked-scan backward.

    Returns ``f(C, B, L, xdt, decay_in, decay_out, states, dY, dSf) ->
    (dC, dB, dL, dxdt, d_decay_in, d_decay_out, ds0)`` — cell shapes as
    the forward, ``states: (G, NC, p, n)`` fp32 (the per-chunk entering
    states the forward drained), gradients fp32.  The supergrid is
    ``(groups, chunks)`` with the chunk coordinate *flipped* in every
    index map, so the sequential dimension walks chunks last-to-first and
    the state cotangent carries in scratch (DESIGN.md §11).
    """
    last = chunks - 1
    body = functools.partial(_ssd_scan_bwd_body, q=q, chunks=chunks)
    kernel = pl.pallas_call(
        body,
        name="ssd_chunk_bwd",
        grid=(groups, chunks),
        in_specs=[
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, q), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, 1, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, q), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, p), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda g, c: (g, last - c, 0, 0)),
            pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((groups, chunks, q, n), jnp.float32),
            jax.ShapeDtypeStruct((groups, chunks, q, n), jnp.float32),
            jax.ShapeDtypeStruct((groups, chunks, q, q), jnp.float32),
            jax.ShapeDtypeStruct((groups, chunks, q, p), jnp.float32),
            jax.ShapeDtypeStruct((groups, chunks, q, 1), jnp.float32),
            jax.ShapeDtypeStruct((groups, chunks, q, 1), jnp.float32),
            jax.ShapeDtypeStruct((groups, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )

    def run(c, b, l, xdt, decay_in, decay_out, states, dy, dsf):
        dc, db, dl, dx, ddi, ddo, ds0 = kernel(
            c, b, l, xdt, decay_in[..., None], decay_out[..., None],
            _chunk_decay_row(decay_in, n), states, dy, dsf)
        return dc, db, dl, dx, ddi[..., 0], ddo[..., 0], ds0

    return run
