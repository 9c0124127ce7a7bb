"""Mamba-2 SSD (state-space duality) layer, chunked algorithm
(arXiv:2405.21060 §6).

The chunked SSD forward is a ladder of *small batched GEMMs* —
(Q×n)·(n×Q), (Q×n)·(n×p), (n×Q)·(Q×p) with Q=chunk, n=state, p=headdim all
in the 64–256 range — i.e. exactly the small-GEMM population the paper's
engine targets (DESIGN.md §4).  On TPU the inner contractions route through
the engine; here they are einsums so the XLA dry-run path shards cleanly.

Layer structure (Mamba-2 block):

    in_proj -> [z | x | B | C | dt];  conv1d+silu over [x|B|C];
    SSD(x, dt, A, B, C) + D·x;  RMSNorm(y ⊙ silu(z));  out_proj

Decode carries (conv tail, S[h,p,n]) — O(1) state in sequence length,
which is what makes mamba2 a legal ``long_500k`` architecture.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.config import get_config
from repro.models import common


class SSMState(NamedTuple):
    conv: jax.Array  # (b, cw-1, conv_dim)
    s: jax.Array     # (b, h, p, n) fp32


def ssd_dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    h = d_in // cfg.ssm_head_dim
    return d_in, h, cfg.ssm_ngroups, cfg.ssm_state


def ssd_init(rng, cfg):
    d = cfg.d_model
    d_in, h, g, n = ssd_dims(cfg)
    conv_dim = d_in + 2 * g * n
    proj_dim = 2 * d_in + 2 * g * n + h
    ri, ro, rc, rd = common.split_rngs(rng, 4)
    dt = jnp.exp(jax.random.uniform(rd, (h,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        "in_proj": common.linear_init(ri, d, proj_dim),
        "out_proj": common.linear_init(ro, d_in, d),
        "conv_w": common.normal_init(rc, (cfg.conv1d_width, conv_dim), 0.02),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # inverse-softplus init
        "norm": common.rmsnorm_init(d_in),
    }


def _segsum(x):
    """log-decay lower-triangular matrix: out[..., i, j] = sum_{j<k<=i} x[k]."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, out, -jnp.inf)


def _ssd_chunked(x, dt, a, b_mat, c_mat, chunk, s0=None):
    """Chunked SSD scan.

    x: (b, s, h, p); dt: (b, s, h) (post-softplus); a: (h,) negative;
    b_mat/c_mat: (b, s, g, n); s0: optional initial state (b, h, p, n).
    Returns y: (b, s, h, p), final state (b, h, p, n).
    """
    bsz, s_orig, h, p = x.shape
    g, n = b_mat.shape[-2], b_mat.shape[-1]
    pad = (-s_orig) % chunk
    if pad:
        # dt = 0 on padded steps => decay 1 and zero input: state-exact.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s = s_orig + pad
    nc = s // chunk
    rep = h // g

    # reshape into chunks
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b_mat.reshape(bsz, nc, chunk, g, n)
    cc = c_mat.reshape(bsz, nc, chunk, g, n)

    da = dtc * a[None, None, None, :]              # (b, nc, Q, h) log-decay
    da_cs = jnp.cumsum(da, axis=2)                  # within-chunk cumsum
    da_tot = da_cs[:, :, -1]                        # (b, nc, h)

    # ---- intra-chunk (quadratic within chunk: small GEMM ladder) --------
    L = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))  # (b, nc, h, Q, Q)
    xdt = xc * dtc[..., None]                        # (b, nc, Q, h, p)
    if get_config().backend == "pallas":
        # Engine routing (DESIGN.md §10): the whole chunked scan — the
        # intra-chunk ladder AND the inter-chunk recurrence — is ONE
        # dispatch of the ssd_chunk family's scan form, with each
        # (batch, head) pair a group and the (p, n) state carried across
        # the chunk walk inside the kernel; the associative-scan +
        # einsum composition below never materializes on this path.
        # Differentiable: training pulls gradients through the family's
        # custom VJP, whose backward is ONE reverse-walk launch carrying
        # the state cotangent in scratch (DESIGN.md §11).
        from repro.kernels.ssd_chunk import ssd_chunk_scan
        gdim = bsz * h
        cg = jnp.repeat(cc, rep, axis=3).transpose(0, 3, 1, 2, 4) \
            .reshape(gdim, nc, chunk, n)
        bg = jnp.repeat(bc, rep, axis=3).transpose(0, 3, 1, 2, 4) \
            .reshape(gdim, nc, chunk, n)
        lg = L.transpose(0, 2, 1, 3, 4).reshape(gdim, nc, chunk, chunk)
        xg = xdt.transpose(0, 3, 1, 2, 4).reshape(gdim, nc, chunk, p)
        di = jnp.exp(da_cs).transpose(0, 3, 1, 2).reshape(gdim, nc, chunk)
        do = jnp.exp(da_tot[:, :, None] - da_cs) \
            .transpose(0, 3, 1, 2).reshape(gdim, nc, chunk)
        s0g = (jnp.zeros((gdim, p, n), jnp.float32) if s0 is None
               else s0.astype(jnp.float32).reshape(gdim, p, n))
        yg, s_fin = ssd_chunk_scan(cg, bg, lg, xg, di, do, s0g)
        y = yg.reshape(bsz, h, nc, chunk, p).transpose(0, 2, 3, 1, 4) \
            .reshape(bsz, s, h, p)
        return y[:, :s_orig], s_fin.reshape(bsz, h, p, n)
    # scores: C_i · B_j over state dim, broadcast groups->heads
    cb = jnp.einsum("bnqgd,bnkgd->bngqk", cc, bc)   # (b, nc, g, Q, Q)
    cb = jnp.repeat(cb, rep, axis=2)                 # (b, nc, h, Q, Q)
    w = cb * L
    y_diag = jnp.einsum("bnhqk,bnkhp->bnqhp", w.astype(x.dtype), xdt)

    # ---- chunk states ----------------------------------------------------
    decay_out = jnp.exp(da_tot[..., None] - da_cs.transpose(0, 1, 3, 2))  # (b,nc,h,Q)
    bfull = jnp.repeat(bc, rep, axis=3)  # (b, nc, Q, h, n) groups -> heads
    bx = jnp.einsum("bnqhd,bnqhp->bnhpd", bfull,
                    (xdt * decay_out.transpose(0, 1, 3, 2)[..., None]).astype(x.dtype))

    # ---- inter-chunk recurrence (associative over chunks) ----------------
    dec = jnp.exp(da_tot)  # (b, nc, h) decay applied across each chunk

    def combine(left, right):
        dl, sl = left
        dr, sr = right
        return dl * dr, sl * dr[..., None, None] + sr

    dcum, s_incl = jax.lax.associative_scan(combine, (dec.astype(jnp.float32),
                                                      bx.astype(jnp.float32)), axis=1)
    if s0 is not None:
        # fold the initial state into every chunk's inclusive state
        s_incl = s_incl + dcum[..., None, None] * s0[:, None]
    # state *entering* chunk i = inclusive state of chunk i-1
    first = jnp.zeros_like(s_incl[:, :1]) if s0 is None else s0[:, None]
    s_prev = jnp.concatenate([first, s_incl[:, :-1]], axis=1)  # (b,nc,h,p,n)

    # ---- inter-chunk contribution ----------------------------------------
    decay_in = jnp.exp(da_cs)  # (b, nc, Q, h)
    cfull = jnp.repeat(cc, rep, axis=3)  # (b, nc, Q, h, n)
    y_off = jnp.einsum("bnqhd,bnhpd->bnqhp", cfull,
                       s_prev.astype(x.dtype)) * decay_in[..., None].astype(x.dtype)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y[:, :s_orig], s_incl[:, -1]  # final state (b, h, p, n)


def ssd_apply(params, cfg, x, *, state: Optional[SSMState] = None):
    """x: (b, s, d) -> (y, new_state)."""
    dt_ = jnp.dtype(cfg.dtype)
    bsz, s, _ = x.shape
    d_in, h, g, n = ssd_dims(cfg)
    p = cfg.ssm_head_dim

    zxbcdt = common.linear(params["in_proj"], x, compute_dtype=dt_)
    z, xs, bb, cc, dt_raw = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + g * n, 2 * d_in + 2 * g * n], axis=-1)

    conv_in = jnp.concatenate([xs, bb, cc], axis=-1)
    tail = state.conv if state is not None else None
    cw = params["conv_w"].shape[0]
    if tail is None:
        tail = jnp.zeros((bsz, cw - 1, conv_in.shape[-1]), conv_in.dtype)
    xp = jnp.concatenate([tail.astype(conv_in.dtype), conv_in], axis=1)
    conv_out = sum(xp[:, i:i + s] * params["conv_w"][i].astype(conv_in.dtype)
                   for i in range(cw))
    conv_out = jax.nn.silu(conv_out + params["conv_b"].astype(conv_in.dtype))
    new_tail = xp[:, -(cw - 1):]

    xs, bb, cc = jnp.split(conv_out, [d_in, d_in + g * n], axis=-1)
    xs = xs.reshape(bsz, s, h, p)
    bb = bb.reshape(bsz, s, g, n)
    cc = cc.reshape(bsz, s, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])  # (h,) negative

    if s == 1 and state is not None:
        # ---- decode: single recurrent step -------------------------------
        da = jnp.exp(dt[:, 0] * a[None, :])  # (b, h)
        bx = jnp.einsum("bgd,bhp->bhpd",
                        bb[:, 0].astype(jnp.float32),
                        (xs[:, 0] * dt[:, 0, :, None].astype(xs.dtype)).astype(jnp.float32))
        s_new = state.s * da[..., None, None] + bx
        cfull = jnp.repeat(cc[:, 0], h // g, axis=1)  # (b, h, n)
        y = jnp.einsum("bhd,bhpd->bhp", cfull.astype(jnp.float32), s_new)
        y = y[:, None].astype(dt_)  # (b, 1, h, p)
        final_state = s_new
    else:
        s0 = state.s if state is not None else None
        y, final_state = _ssd_chunked(xs, dt, a, bb, cc, cfg.ssm_chunk, s0)

    y = y + xs * params["D"].astype(dt_)[None, None, :, None]
    y = y.reshape(bsz, s, d_in)
    y = common.rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = common.linear(params["out_proj"], y, compute_dtype=dt_)
    new_state = SSMState(conv=new_tail, s=final_state.astype(jnp.float32))
    return out, new_state


def init_ssm_state(batch, cfg) -> SSMState:
    d_in, h, g, n = ssd_dims(cfg)
    conv_dim = d_in + 2 * g * n
    return SSMState(
        conv=jnp.zeros((batch, cfg.conv1d_width - 1, conv_dim),
                       jnp.dtype(cfg.dtype)),
        s=jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
    )
