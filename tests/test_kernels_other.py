"""Transpose / grouped-GEMM / flash-attention kernels vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels.transpose import transpose, ref_transpose
from repro.kernels.grouped_gemm import grouped_gemm, ref_grouped_gemm
from repro.kernels.flash_attention import flash_attention, ref_attention

RNG = np.random.default_rng(7)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("rows,cols,bt", [
    (256, 512, 128), (100, 300, 64), (7, 1000, 256), (128, 128, 128),
    (1, 5, 8),
])
def test_transpose(rows, cols, bt):
    x = rand((rows, cols))
    np.testing.assert_array_equal(transpose(x, bt=bt), ref_transpose(x))


def test_transpose_batched():
    x = rand((3, 64, 96))
    np.testing.assert_array_equal(transpose(x, bt=32), ref_transpose(x))


def test_transpose_batched_is_single_launch():
    """Batch walks as a grid dimension (DESIGN.md §9): a batched transpose
    is ONE pallas_call, visible to the launch counter — not B vmap'd
    launches it can't see."""
    from repro.core import engine
    engine.reset_stats()
    x = rand((7, 40, 56))
    out = transpose(x, bt=32)
    np.testing.assert_array_equal(out, ref_transpose(x))
    assert engine.stats()["transpose"]["launches"] == 1


@pytest.mark.parametrize("sizes,bm", [
    ([37, 0, 201, 70], 32), ([128, 64, 0, 64], 64), ([5, 3, 2, 1], 8),
    ([300], 128), ([0, 0, 17], 16),
])
def test_grouped_gemm(sizes, bm):
    sizes_a = jnp.array(sizes, jnp.int32)
    e, kdim, n = len(sizes), 96, 160
    t = int(sizes_a.sum()) + 4
    x, w = rand((t, kdim)), rand((e, kdim, n))
    out = grouped_gemm(x, w, sizes_a, bm=bm, bk=64, bn=64)
    ref = ref_grouped_gemm(x, w, sizes_a)
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


def _check_grouped_gemm(sizes):
    sizes_a = jnp.array(sizes, jnp.int32)
    e, kdim, n = len(sizes), 32, 48
    t = max(1, int(sizes_a.sum()))
    x, w = rand((t, kdim)), rand((e, kdim, n))
    out = grouped_gemm(x, w, sizes_a, bm=16, bk=32, bn=48)
    ref = ref_grouped_gemm(x, w, sizes_a)
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.lists(st.integers(0, 60), min_size=1, max_size=5))
    def test_grouped_gemm_property(sizes):
        _check_grouped_gemm(sizes)
else:
    # Deterministic fallback: empty / single / ragged / all-empty groups.
    @pytest.mark.parametrize("sizes", [[0], [1], [60], [0, 0, 0],
                                       [17, 0, 42, 3], [60, 60, 60, 60, 60]])
    def test_grouped_gemm_property(sizes):
        _check_grouped_gemm(sizes)


# ---------------------------------------------------------------------------
# Grouped GEMM scheduled single-launch path (DESIGN.md §9): the fused
# lowering must be bit-identical to the pad/scatter lowering (same bk
# chunking, same fp32 accumulation order — masking instead of padding)
# and match the oracle across every ragged case.
# ---------------------------------------------------------------------------

# (group_sizes, extra rows past sum) — zero-size experts, sum < T, a
# single expert owning all rows, and M/K/N-tail-inducing shapes.
GROUPED_RAGGED_CASES = [
    ([37, 0, 201, 70], 4),
    ([0, 0, 0], 5),        # all experts empty: output all zeros
    ([300], 0),            # one expert owns every row
    ([5, 3, 2, 1], 0),
    ([0, 0, 17], 10),
    ([60, 60, 60], 33),    # sum < T with aligned groups
]


def _grouped_case(sizes, t_extra, kdim=100, n=70):
    sizes_a = jnp.array(sizes, jnp.int32)
    t = max(1, int(sizes_a.sum()) + t_extra)
    x = rand((t, kdim))
    w = rand((len(sizes), kdim, n))
    return sizes_a, x, w


@pytest.mark.parametrize("sizes,t_extra", GROUPED_RAGGED_CASES)
def test_grouped_fused_matches_padscatter_bitwise(sizes, t_extra):
    sizes_a, x, w = _grouped_case(sizes, t_extra)
    # bm=16/bk=64/bn=32 force M, K and N tails on every case above
    kw = dict(bm=16, bk=64, bn=32)
    fused = grouped_gemm(x, w, sizes_a, fused=True, **kw)
    padded = grouped_gemm(x, w, sizes_a, fused=False, **kw)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(padded))
    ref = ref_grouped_gemm(x, w, sizes_a)
    np.testing.assert_allclose(fused, ref, atol=1e-3, rtol=1e-3)


def test_grouped_fused_matches_ref_bitwise_single_k_panel():
    """With one K panel the fused kernel sums the same 96 products per
    output as the oracle einsum, in one fp32 dot: they may differ only in
    summation order (XLA's CPU dot splits K its own way), so they agree
    to ~sqrt(K) fp32 ulps of the largest output (4x margin)."""
    sizes_a, x, w = _grouped_case([37, 0, 201, 70], 4, kdim=96, n=160)
    out = np.asarray(grouped_gemm(x, w, sizes_a, fused=True))
    ref = np.asarray(ref_grouped_gemm(x, w, sizes_a))
    tol = 4 * np.sqrt(96) * np.finfo(np.float32).eps * np.abs(ref).max()
    np.testing.assert_allclose(out, ref, rtol=0, atol=tol)


@pytest.mark.parametrize("epilogue", ["bias", "gelu", "silu", "relu",
                                      "bias_gelu", "bias_silu"])
def test_grouped_epilogues_fused_vs_padscatter(epilogue):
    """Per-expert bias + activation epilogues lower identically on both
    paths (shared kernels/epilogue.py on the fp32 accumulator)."""
    sizes_a, x, w = _grouped_case([13, 0, 40, 7], 5)
    bias = rand((4, 70)) if "bias" in epilogue else None
    kw = dict(bm=16, bk=64, bn=32, epilogue=epilogue, bias=bias)
    fused = grouped_gemm(x, w, sizes_a, fused=True, **kw)
    padded = grouped_gemm(x, w, sizes_a, fused=False, **kw)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(padded))
    # against the oracle: epilogue applied per-expert on valid rows only
    ref = ref_grouped_gemm(x, w, sizes_a)
    if "bias" in epilogue:
        offsets = np.concatenate([[0], np.cumsum(np.asarray(sizes_a))])
        expert = np.clip(np.searchsorted(offsets, np.arange(x.shape[0]),
                                         side="right") - 1, 0, 3)
        ref = ref + np.asarray(bias)[expert]
    if epilogue in ("gelu", "bias_gelu"):
        ref = jax.nn.gelu(ref)
    elif epilogue in ("silu", "bias_silu"):
        ref = jax.nn.silu(ref)
    elif epilogue == "relu":
        ref = jnp.maximum(ref, 0)
    total = int(np.asarray(sizes_a).sum())
    valid = (np.arange(x.shape[0]) < total)[:, None]
    ref = jnp.where(valid, ref, 0.0)
    np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)


def test_grouped_bias_epilogue_requires_bias():
    sizes_a, x, w = _grouped_case([8, 8], 0, kdim=16, n=16)
    with pytest.raises(ValueError, match="bias"):
        grouped_gemm(x, w, sizes_a, epilogue="bias")


def test_grouped_multi_expert_dispatch_is_single_launch():
    """Acceptance (DESIGN.md §9): a multi-expert ragged dispatch executes
    as exactly ONE pallas_call when fused, with no pad/scatter host ops —
    mirroring tests/test_kernels_gemm.py's GEMM assertion."""
    from repro.core import engine
    engine.reset_stats()
    sizes_a, x, w = _grouped_case([37, 0, 201, 70], 4)
    fused = grouped_gemm(x, w, sizes_a, fused=True)
    assert engine.stats()["grouped_gemm"]["launches"] == 1
    padded = grouped_gemm(x, w, sizes_a, fused=False)
    # the pad/scatter lowering is also one launch — it pays in scatter/
    # gather traffic, not dispatches
    assert engine.stats()["grouped_gemm"]["launches"] == 2
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(padded))


def test_grouped_fused_under_jit():
    """group_sizes is runtime data: the scheduled path must trace (tables
    are jnp ops on the traced operand, static shapes throughout)."""
    sizes_a, x, w = _grouped_case([13, 0, 40, 7], 5)
    f = jax.jit(lambda x, w, s: grouped_gemm(x, w, s, fused=True))
    np.testing.assert_allclose(f(x, w, sizes_a),
                               ref_grouped_gemm(x, w, sizes_a),
                               atol=1e-3, rtol=1e-3)


def test_grouped_plan_defaults_to_fused():
    """The analytical planner takes the paper's one-kernel stance when
    the staged operands fit VMEM."""
    from repro.core import (GroupedGemmDescriptor, grouped_fused_legal,
                            plan_grouped)
    d = GroupedGemmDescriptor(t=256, k=96, n=160, num_experts=4)
    assert grouped_fused_legal(d)
    assert plan_grouped(d).fused
    huge = GroupedGemmDescriptor(t=1 << 20, k=4096, n=4096, num_experts=64)
    assert not grouped_fused_legal(huge)
    assert not plan_grouped(huge).fused


@pytest.mark.parametrize("b,s,h,d,causal,bq,bk", [
    (2, 256, 4, 64, True, 128, 128),
    (1, 384, 2, 128, True, 128, 128),
    (2, 128, 3, 64, False, 64, 64),
    (1, 96, 1, 64, True, 64, 64),  # ragged seq vs block
])
def test_flash_attention(b, s, h, d, causal, bq, bk):
    q, k, v = rand((b, s, h, d)), rand((b, s, h, d)), rand((b, s, h, d))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_flash_attention_bf16():
    q = rand((2, 128, 2, 64), jnp.bfloat16)
    k = rand((2, 128, 2, 64), jnp.bfloat16)
    v = rand((2, 128, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)


# ---------------------------------------------------------------------------
# Flash attention scheduled single-launch path (DESIGN.md §10): the fused
# causal-aware tile-table lowering must be bit-identical to the dense-grid
# pre-schedule lowering (same per-tile online-softmax math; dropped causal
# tiles were exact no-ops) and match the oracle.
# ---------------------------------------------------------------------------

# (b, h, sq, sk, d, bq, bk) — sq/sk/d tails vs the block sizes, ragged
# sq != sk (non-causal), multi-head batches folded into the supergrid.
FLASH_PARITY_CASES = [
    (2, 4, 256, 256, 64, 128, 128),   # aligned, multi-head
    (1, 2, 96, 96, 64, 64, 64),       # sq/sk tails (96 % 64)
    (2, 3, 100, 100, 48, 64, 32),     # ragged everything incl. d=48
    (1, 1, 130, 70, 32, 64, 32),      # sq != sk
    (3, 2, 33, 257, 16, 32, 128),     # long-k, tiny blocks
]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,sq,sk,d,bq,bk", FLASH_PARITY_CASES)
def test_flash_fused_matches_dense_grid_bitwise(b, h, sq, sk, d, bq, bk,
                                                causal, dtype):
    q = rand((b, sq, h, d), dtype)
    k = rand((b, sk, h, d), dtype)
    v = rand((b, sk, h, d), dtype)
    kw = dict(causal=causal, block_q=bq, block_k=bk)
    fused = flash_attention(q, k, v, fused=True, **kw)
    dense = flash_attention(q, k, v, fused=False, **kw)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(dense))
    if causal and sq != sk:
        # the kernels' causal diagonal is start-aligned (kpos <= qpos);
        # the oracle end-aligns it — only the lowerings are comparable
        return
    ref = ref_attention(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_fused_causal_is_single_launch_fewer_tiles():
    """Acceptance (DESIGN.md §10): a causal dispatch with fused legal is
    exactly ONE pallas_call and walks fewer tiles than the dense (q, k)
    grid — the masked k-blocks never enter the table."""
    from repro.core import FlashDescriptor, FlashPlan, engine, plan_flash
    desc = FlashDescriptor(batch_heads=4, sq=512, sk=512, d=64, causal=True)
    assert plan_flash(desc).fused  # the planner takes the one-kernel stance
    # pin 128x128 blocks: a 4x4 (q, k) grid whose upper triangle the
    # table drops — 10 tiles instead of 16
    plan = FlashPlan(desc, 128, 128, fused=True)
    sched = plan.tile_schedule()
    assert sched.dense_tiles == 16 and sched.num_tiles == 10
    engine.reset_stats()
    q = rand((2, 512, 2, 64))
    out = flash_attention(q, q, q, causal=True, block_q=128, block_k=128)
    assert engine.stats()["flash_attention"]["launches"] == 1
    np.testing.assert_allclose(out, ref_attention(q, q, q, causal=True),
                               atol=2e-3, rtol=2e-3)
    # the dense-grid fallback is also one pallas_call — it pays in grid
    # steps for masked tiles, not dispatches
    flash_attention(q, q, q, causal=True, block_q=128, block_k=128,
                    fused=False)
    assert engine.stats()["flash_attention"]["launches"] == 2


def test_flash_plan_defaults_to_fused():
    """Fused whenever one batch-head slice of q/k/v + out stages in VMEM;
    VMEM-oversized problems fall back to the dense grid."""
    from repro.core import FlashDescriptor, flash_fused_legal, plan_flash
    d = FlashDescriptor(batch_heads=8, sq=2048, sk=2048, d=64)
    assert flash_fused_legal(d)
    assert plan_flash(d).fused
    huge = FlashDescriptor(batch_heads=8, sq=1 << 20, sk=1 << 20, d=128)
    assert not flash_fused_legal(huge)
    assert not plan_flash(huge).fused


# ---------------------------------------------------------------------------
# SSD intra-chunk kernel (the small-GEMM ladder in its Mamba-2 habitat)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,q,n,p", [(6, 64, 32, 64), (2, 128, 128, 64),
                                     (1, 32, 16, 16)])
def test_ssd_chunk_kernel(g, q, n, p):
    from repro.kernels.ssd_chunk import ssd_chunk_diag, ref_ssd_chunk_diag
    c = rand((g, q, n))
    b = rand((g, q, n))
    x = rand((g, q, p))
    l = jnp.tril(jnp.exp(rand((g, q, q)) * 0.1))
    out = ssd_chunk_diag(c, b, l, x)
    ref = ref_ssd_chunk_diag(c, b, l, x)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_ssd_chunk_matches_model_ladder():
    """The kernel reproduces the y_diag term of the model's chunked SSD."""
    from repro.kernels.ssd_chunk import ssd_chunk_diag
    from repro.models.ssd import _segsum
    b_, nc, q, h, p, n = 1, 2, 8, 2, 4, 3
    x = rand((b_, nc, q, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b_, nc, q, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = rand((b_, nc, q, 1, n))
    C = rand((b_, nc, q, 1, n))
    da = dt * a[None, None, None, :]
    L = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))  # (b, nc, h, q, q)
    xdt = x * dt[..., None]
    # flatten (b, nc, h) into kernel groups
    cg = jnp.broadcast_to(C.transpose(0, 1, 3, 2, 4), (b_, nc, h, q, n)) \
        .reshape(-1, q, n)
    bg = jnp.broadcast_to(B.transpose(0, 1, 3, 2, 4), (b_, nc, h, q, n)) \
        .reshape(-1, q, n)
    lg = L.reshape(-1, q, q)
    xg = xdt.transpose(0, 1, 3, 2, 4).reshape(-1, q, p)
    y_kernel = ssd_chunk_diag(cg, bg, lg, xg).reshape(b_, nc, h, q, p)

    cb = jnp.einsum("bnqgd,bnkgd->bngqk", C, B)
    cb = jnp.repeat(cb, h, axis=2)
    w = cb * L
    y_ref = jnp.einsum("bnhqk,bnkhp->bnqhp", w.astype(x.dtype), xdt)
    np.testing.assert_allclose(y_kernel.transpose(0, 1, 3, 2, 4), y_ref,
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# SSD carried-state scan (DESIGN.md §10): the fused single-launch lowering
# (state carried across the sequential chunk grid dimension) vs the diag
# kernel + XLA associative-scan fallback vs the sequential oracle.
# ---------------------------------------------------------------------------

def _ssd_scan_case(g, nc, q, n, p, seed=11):
    r = np.random.default_rng(seed)
    arr = lambda s: jnp.asarray(r.standard_normal(s), jnp.float32)
    c, b = arr((g, nc, q, n)), arr((g, nc, q, n))
    l = jnp.tril(jnp.exp(arr((g, nc, q, q)) * 0.1))
    x = arr((g, nc, q, p))
    # physical decays: da negative, so decay_in = exp(da_cs) in (0, 1]
    # with decay_in[-1] the whole-chunk decay the state update reads
    da_cs = -jnp.cumsum(jnp.abs(arr((g, nc, q))) * 0.1, axis=-1)
    di = jnp.exp(da_cs)
    do = jnp.exp(da_cs[..., -1:] - da_cs)
    s0 = arr((g, p, n))
    return c, b, l, x, di, do, s0


@pytest.mark.parametrize("g,nc,q,n,p", [
    (2, 3, 16, 8, 12),    # odd little everything
    (1, 1, 8, 4, 4),      # single chunk: recurrence degenerates to s0
    (4, 7, 32, 16, 8),    # longer carried-state walk
])
def test_ssd_scan_fused_matches_fallback(g, nc, q, n, p):
    from repro.core import engine
    from repro.kernels.ssd_chunk import ssd_chunk_scan, ref_ssd_chunk_scan
    ops = _ssd_scan_case(g, nc, q, n, p)
    engine.reset_stats()
    from repro.core.config import use
    y_f, s_f = ssd_chunk_scan(*ops)
    # fused: the whole scan — intra ladder AND inter-chunk recurrence —
    # is exactly ONE pallas_call
    assert engine.stats()["ssd_chunk"]["launches"] == 1
    with use(fused="off"):
        y_m, s_m = ssd_chunk_scan(*ops)
    np.testing.assert_allclose(y_f, y_m, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_f, s_m, atol=2e-3, rtol=2e-3)
    y_r, s_r = ref_ssd_chunk_scan(*ops)
    np.testing.assert_allclose(y_f, y_r, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_f, s_r, atol=2e-3, rtol=2e-3)


def test_ssd_scan_carried_state_tail():
    """Carried-state tails: a scan split in two with the intermediate
    state handed across the seam equals the unsplit scan — the property
    decode warm-starts (s0 != 0) rely on."""
    from repro.kernels.ssd_chunk import ssd_chunk_scan
    c, b, l, x, di, do, s0 = _ssd_scan_case(2, 4, 16, 8, 12)
    y_full, s_full = ssd_chunk_scan(c, b, l, x, di, do, s0)
    cut = 2
    y1, s_mid = ssd_chunk_scan(c[:, :cut], b[:, :cut], l[:, :cut],
                               x[:, :cut], di[:, :cut], do[:, :cut], s0)
    y2, s_end = ssd_chunk_scan(c[:, cut:], b[:, cut:], l[:, cut:],
                               x[:, cut:], di[:, cut:], do[:, cut:], s_mid)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y_full,
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_end, s_full, atol=2e-3, rtol=2e-3)


def test_ssd_scan_under_jit():
    """The scan form must trace: static shapes, carried scratch, two
    outputs."""
    from repro.kernels.ssd_chunk import ssd_chunk_scan, ref_ssd_chunk_scan
    ops = _ssd_scan_case(2, 3, 8, 4, 4)
    y_j, s_j = jax.jit(ssd_chunk_scan)(*ops)
    y_r, s_r = ref_ssd_chunk_scan(*ops)
    np.testing.assert_allclose(y_j, y_r, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_j, s_r, atol=2e-3, rtol=2e-3)


def test_ssd_model_routes_through_scan():
    """models/ssd.py under the pallas backend: one ssd_chunk launch for
    the whole chunked forward, bit-for-bit state/output parity with the
    XLA formulation within tolerance."""
    from repro.core import engine
    from repro.core.config import use
    from repro.models.ssd import _ssd_chunked
    r = np.random.default_rng(3)
    b, s, h, p, g, n, chunk = 2, 20, 4, 8, 2, 6, 8  # ragged s: pad to 24
    x = jnp.asarray(r.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = -jnp.asarray(r.uniform(0.5, 1.5, (h,)), jnp.float32)
    B = jnp.asarray(r.standard_normal((b, s, g, n)), jnp.float32)
    C = jnp.asarray(r.standard_normal((b, s, g, n)), jnp.float32)
    s0 = jnp.asarray(r.standard_normal((b, h, p, n)), jnp.float32)
    y_x, f_x = _ssd_chunked(x, dt, a, B, C, chunk, s0)
    engine.reset_stats()
    with use(backend="pallas"):
        y_p, f_p = _ssd_chunked(x, dt, a, B, C, chunk, s0)
    assert engine.stats()["ssd_chunk"]["launches"] == 1
    np.testing.assert_allclose(y_x, y_p, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(f_x, f_p, atol=2e-3, rtol=2e-3)
