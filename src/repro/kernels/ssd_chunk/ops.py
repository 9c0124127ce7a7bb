"""SSD chunked-scan family: engine-dispatched small-GEMM ladder + scan.

Two public surfaces over one engine family (DESIGN.md §10):

  * :func:`ssd_chunk_diag` — the intra-chunk (diagonal-block) ladder on
    a flat ``(G, Q, ·)`` group batch (``desc.chunks == 0``);
  * :func:`ssd_chunk_scan` — the whole chunked scan on a
    ``(G, chunks, Q, ·)`` layout, returning outputs *and* the final SSM
    state.  Resolved by ``engine.resolve_fused`` exactly as for dense
    GEMM: the fused lowering is ONE ``pallas_call`` with the ``(p, n)``
    state carried across the sequential chunk grid dimension as
    accumulator scratch; the fallback runs the diag kernel plus the XLA
    associative-scan inter-chunk recurrence (the pre-schedule
    formulation, kept for VMEM-oversized cells and as the autotuner's
    alternative).  Both report traced launch counts through
    ``engine.count_launches`` → ``engine.stats()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.blocking import SsdChunkPlan, plan_ssd, plan_ssd_bwd, \
    ssd_bwd_fused_legal
from repro.core.config import get_config, resolve_interpret
from repro.core.descriptor import SsdChunkBwdDescriptor, SsdChunkDescriptor
from repro.core.schedule import plan_launches
from repro.kernels.ssd_chunk.kernel import (build_ssd_chunk_kernel,
                                            build_ssd_scan_bwd_kernel,
                                            build_ssd_scan_kernel)
from repro.kernels.ssd_chunk.ref import ref_ssd_chunk_scan


def _execute_diag(desc: SsdChunkDescriptor, groups: int, c_mat, b_mat,
                  l_mat, xdt, interpret: bool) -> jax.Array:
    """Build (and cache) the intra-chunk ladder kernel and run it on a
    flat ``(groups, Q, ·)`` batch."""
    key = (desc.family, "diag", groups, desc.q, desc.n, desc.p,
           desc.dtype, interpret)
    kernel = engine.build_cached(key, lambda: build_ssd_chunk_kernel(
        groups=groups, q=desc.q, n=desc.n, p=desc.p,
        dtype=xdt.dtype, interpret=interpret))
    return kernel(c_mat, b_mat, l_mat, xdt)


def _execute_scan_fallback(desc: SsdChunkDescriptor, c, b, l, xdt,
                           decay_in, decay_out, s0, interpret: bool):
    """Non-fused scan: diag kernel for y_diag, XLA ops for the
    inter-chunk recurrence (associative scan over per-chunk states)."""
    g, nc, q, n = c.shape
    p = xdt.shape[-1]
    flat = (g * nc, q)
    y_diag = _execute_diag(
        desc, g * nc, c.reshape(*flat, n), b.reshape(*flat, n),
        l.reshape(*flat, q), xdt.reshape(*flat, p),
        interpret).reshape(g, nc, q, p)

    # per-chunk state contributions: bx[g,c] = Bᵀ · (xdt ⊙ decay_out)
    xw = (xdt.astype(jnp.float32)
          * decay_out[..., None]).astype(xdt.dtype)
    bx = jnp.einsum("gcqn,gcqp->gcpn", b, xw,
                    preferred_element_type=jnp.float32)
    dec = decay_in[..., -1]  # whole-chunk decay: da_cs[-1] == da_tot

    def combine(left, right):
        dl, sl = left
        dr, sr = right
        return dl * dr, sl * dr[..., None, None] + sr

    dcum, s_incl = jax.lax.associative_scan(
        combine, (dec.astype(jnp.float32), bx.astype(jnp.float32)), axis=1)
    s_incl = s_incl + dcum[..., None, None] * s0[:, None]
    s_prev = jnp.concatenate([s0[:, None], s_incl[:, :-1]], axis=1)

    y_off = jnp.einsum("gcqn,gcpn->gcqp", c.astype(jnp.float32), s_prev,
                       preferred_element_type=jnp.float32)
    y_off = y_off * decay_in[..., None]
    y = (y_diag.astype(jnp.float32) + y_off).astype(xdt.dtype)
    return y, s_incl[:, -1]


def _execute_scan_fused(desc: SsdChunkDescriptor, c, b, l, xdt,
                        decay_in, decay_out, s0, interpret: bool):
    """Single carried-state launch over the (groups, chunks) supergrid."""
    key = desc.cache_key() + ("fused", interpret)
    kernel = engine.build_cached(key, lambda: build_ssd_scan_kernel(
        groups=desc.groups, chunks=desc.chunks, q=desc.q, n=desc.n,
        p=desc.p, dtype=xdt.dtype, interpret=interpret))
    return kernel(c, b, l, xdt, decay_in, decay_out, s0)


def execute(desc: SsdChunkDescriptor, plan: SsdChunkPlan, c_mat, b_mat,
            l_mat, xdt, *rest, interpret: bool = False):
    """Engine executor: run one planned SSD dispatch (either form)."""
    if not desc.chunks:
        engine.count_launches("ssd_chunk", 1)
        return _execute_diag(desc, desc.groups, c_mat, b_mat, l_mat, xdt,
                             interpret)
    decay_in, decay_out, s0 = rest
    fused = engine.resolve_fused(plan)
    engine.count_launches("ssd_chunk", plan_launches(plan, fused),
                          fused=fused)
    if fused:
        return _execute_scan_fused(desc, c_mat, b_mat, l_mat, xdt,
                                   decay_in, decay_out, s0, interpret)
    return _execute_scan_fallback(desc, c_mat, b_mat, l_mat, xdt,
                                  decay_in, decay_out, s0, interpret)


engine.register_family("ssd_chunk", planner=plan_ssd, execute=execute)


# ---------------------------------------------------------------------------
# Backward family (DESIGN.md §11): ONE reverse-walk pallas_call carrying
# the (p, n) state cotangent as accumulator scratch
# ---------------------------------------------------------------------------

def execute_bwd(desc: SsdChunkBwdDescriptor, plan: SsdChunkPlan, c, b, l,
                xdt, decay_in, decay_out, states, dy, dsf, *,
                interpret: bool = False):
    """Engine executor: run one planned SSD chunked-scan backward.

    ``states`` is the forward's per-chunk entering-state residual
    ``(G, NC, p, n)`` fp32; ``dy``/``dsf`` the output cotangents.  Single
    lowering — the reverse carried-state walk; illegal descriptors never
    reach the engine (the custom VJP falls back to reference autodiff
    first).
    """
    engine.count_launches("ssd_chunk_bwd", 1)
    key = desc.cache_key() + ("fused", interpret)
    kernel = engine.build_cached(key, lambda: build_ssd_scan_bwd_kernel(
        groups=desc.groups, chunks=desc.chunks, q=desc.q, n=desc.n,
        p=desc.p, dtype=xdt.dtype, interpret=interpret))
    return kernel(c, b, l, xdt, decay_in, decay_out, states, dy, dsf)


engine.register_family("ssd_chunk_bwd", planner=plan_ssd_bwd,
                       execute=execute_bwd)


def _scan_dispatch(c, b, l, xdt, decay_in, decay_out, s0):
    """The engine-dispatched scan (primal path)."""
    desc = SsdChunkDescriptor.from_scan_operands(c, xdt)
    return engine.dispatch(desc, c, b, l, xdt, decay_in, decay_out, s0)


@jax.custom_vjp
def _ssd_vjp(c, b, l, xdt, decay_in, decay_out, s0):
    """Differentiable chunked SSD scan (custom VJP, DESIGN.md §11):
    forward = the engine-dispatched kernel; backward = the single
    reverse-walk launch carrying the state cotangent when legal,
    reference-path autodiff otherwise."""
    return _scan_dispatch(c, b, l, xdt, decay_in, decay_out, s0)


def _ssd_vjp_fwd(c, b, l, xdt, decay_in, decay_out, s0):
    cfg = get_config()
    desc = SsdChunkDescriptor.from_scan_operands(c, xdt)
    bdesc = SsdChunkBwdDescriptor.from_forward(desc)
    fused_ok = (cfg.fused != "off"
                and ssd_bwd_fused_legal(bdesc, cfg.machine_model))
    if fused_ok:
        # The backward replays the per-chunk entering states, so the
        # forward must run fused too (the states drain from its walk).
        fused_ok = engine.resolve_fused(engine.plan_for(desc))
    if not fused_ok:
        out = _scan_dispatch(c, b, l, xdt, decay_in, decay_out, s0)
        return out, {"ref": (c, b, l, xdt, decay_in, decay_out, s0)}
    # Forward with the entering states drained for the reverse walk —
    # same schedule, same carried-state math as the primal fused kernel.
    interpret = resolve_interpret(cfg.interpret)
    key = desc.cache_key() + ("fused_states", interpret)
    kernel = engine.build_cached(key, lambda: build_ssd_scan_kernel(
        groups=desc.groups, chunks=desc.chunks, q=desc.q, n=desc.n,
        p=desc.p, dtype=xdt.dtype, interpret=interpret, return_states=True))
    engine.count_launches("ssd_chunk", 1, fused=True)
    y, sf, states = kernel(c, b, l, xdt, decay_in, decay_out, s0)
    return (y, sf), {"fused": (c, b, l, xdt, decay_in, decay_out, states)}


def _ssd_vjp_bwd(res, g):
    dy, dsf = g
    if "fused" in res:
        c, b, l, xdt, decay_in, decay_out, states = res["fused"]
        bdesc = SsdChunkBwdDescriptor.from_forward(
            SsdChunkDescriptor.from_scan_operands(c, xdt))
        dc, db, dl, dx, ddi, ddo, ds0 = engine.dispatch(
            bdesc, c, b, l, xdt, decay_in, decay_out, states,
            dy.astype(jnp.float32), dsf.astype(jnp.float32))
    else:
        c, b, l, xdt, decay_in, decay_out, s0 = res["ref"]
        _, vjp = jax.vjp(ref_ssd_chunk_scan, c, b, l, xdt,
                         decay_in, decay_out, s0)
        dc, db, dl, dx, ddi, ddo, ds0 = vjp(
            (dy.astype(xdt.dtype), dsf.astype(jnp.float32)))
    return (dc.astype(c.dtype), db.astype(b.dtype), dl.astype(l.dtype),
            dx.astype(xdt.dtype), ddi.astype(decay_in.dtype),
            ddo.astype(decay_out.dtype), ds0.astype(jnp.float32))


_ssd_vjp.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssd_chunk_diag(c_mat, b_mat, l_mat, xdt):
    """Batched intra-chunk SSD: (G,Q,n)x2, (G,Q,Q), (G,Q,p) -> (G,Q,p)."""
    desc = SsdChunkDescriptor.from_operands(c_mat, xdt)
    return engine.dispatch(desc, c_mat, b_mat, l_mat, xdt)


def ssd_chunk_scan(c_mat, b_mat, l_mat, xdt, decay_in, decay_out, s0):
    """Whole chunked SSD scan via the engine (DESIGN.md §10).

    ``c_mat``/``b_mat``: (G, NC, Q, n); ``l_mat``: (G, NC, Q, Q);
    ``xdt``: (G, NC, Q, p); ``decay_in``/``decay_out``: (G, NC, Q) fp32
    (``exp(da_cs)`` and ``exp(da_tot - da_cs)``); ``s0``: (G, p, n) fp32
    initial state.  Returns ``(y: (G, NC, Q, p), s_final: (G, p, n))``
    with the inter-chunk recurrence carried inside the kernel when the
    plan is fused.  Differentiable: training flows through the custom
    VJP onto the reverse carried-state walk (DESIGN.md §11).
    """
    return _ssd_vjp(c_mat, b_mat, l_mat, xdt, decay_in, decay_out, s0)
