"""Pallas GEMM kernel vs pure-jnp oracle: shape/dtype/layout sweeps,
plus the fused-vs-multi-launch parity matrix (DESIGN.md §8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GemmDescriptor, engine, plan_gemm, backend, matmul
from repro.kernels.gemm import gemm, ref_gemm

RNG = np.random.default_rng(42)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def tol_for(dtype):
    return 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-4


SHAPES = [
    (128, 128, 128),   # single aligned block
    (256, 256, 512),
    (80, 80, 512),     # paper Fig 7 shape
    (1, 128, 512),     # single-row GEMV-ish
    (7, 33, 100),      # fully ragged
    (513, 129, 257),   # off-by-one everywhere
    (512, 512, 64),    # shallow K
    (64, 1024, 128),
]


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("layout", ["nn", "nt"])
def test_gemm_matches_oracle(m, n, k, layout):
    a = rand((m, k))
    b = rand((k, n) if layout == "nn" else (n, k))
    out = gemm(a, b, layout=layout)
    ref = ref_gemm(a, b, layout=layout)
    np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_dtypes(dtype):
    a, b = rand((96, 160), dtype), rand((160, 224), dtype)
    out = gemm(a, b)
    ref = ref_gemm(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol_for(dtype), rtol=tol_for(dtype))


@pytest.mark.parametrize("edge", ["mask", "pad"])
def test_edge_strategies_agree(edge):
    """Predication (mask) vs copy-based padding — identical results (§IV-B)."""
    a, b = rand((70, 90)), rand((90, 110))
    out = gemm(a, b, edge=edge)
    np.testing.assert_allclose(out, ref_gemm(a, b), atol=1e-4, rtol=1e-4)


def test_accumulate_beta1():
    """C += A@B semantics (the paper's GEMM form)."""
    a, b, c = rand((100, 64)), rand((64, 72)), rand((100, 72))
    out = gemm(a, b, c=c)
    np.testing.assert_allclose(out, ref_gemm(a, b, c=c), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("epilogue", ["bias", "gelu", "silu", "relu",
                                      "bias_gelu", "bias_silu"])
def test_epilogues(epilogue):
    a, b = rand((64, 96)), rand((96, 128))
    bias = rand((128,)) if "bias" in epilogue else None
    out = gemm(a, b, epilogue=epilogue, bias=bias)
    ref = ref_gemm(a, b, epilogue=epilogue, bias=bias)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_batched():
    a, b = rand((3, 40, 50)), rand((3, 50, 60))
    out = gemm(a, b)
    ref = ref_gemm(a, b)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_region_plan_execution_matches_fig7():
    """An 640x640 heterogeneous plan executes region-by-region and still
    produces the exact product."""
    d = GemmDescriptor(m=640, n=640, k=512)
    plan = plan_gemm(d, force_block=(256, 256))
    assert len(plan.regions) >= 3  # interior + strips (+ corner)
    a, b = rand((640, 512)), rand((512, 640))
    out = gemm(a, b, plan=plan)
    np.testing.assert_allclose(out, ref_gemm(a, b), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# Fused single-launch execution (DESIGN.md §8): the fused path computes
# the same products as the multi-launch path, masking instead of
# stitching; only the order the fp32 accumulator sums them may differ.
# ---------------------------------------------------------------------------

PARITY_SHAPES = [
    (128, 128, 128),   # fully aligned
    (80, 80, 512),     # paper Fig 7 shape
    (70, 90, 130),     # M/N/K tails everywhere
    (128, 128, 100),   # K tail only
    (7, 33, 100),      # sub-register-tile
    (513, 129, 257),   # off-by-one everywhere
]


def assert_same_sum(fused, multi, k):
    """The two lowerings sum the same k products in fp32 but in different
    orders (K-panel widths and XLA's dot splitting differ), so they agree
    to the roundoff of a length-k sum: ~sqrt(k) fp32 ulps of the largest
    output, with a 4x margin, plus one rounding of the output dtype."""
    assert fused.dtype == multi.dtype and fused.shape == multi.shape
    f = np.asarray(fused, np.float32)
    m = np.asarray(multi, np.float32)
    scale = float(np.abs(m).max())
    tol = (4 * np.sqrt(k) * np.finfo(np.float32).eps
           + float(jnp.finfo(fused.dtype).eps)) * scale
    np.testing.assert_allclose(f, m, rtol=0, atol=tol)


@pytest.mark.parametrize("m,n,k", PARITY_SHAPES)
@pytest.mark.parametrize("layout", ["nn", "nt"])
def test_fused_matches_multilaunch_bitwise(m, n, k, layout):
    a = rand((m, k))
    b = rand((k, n) if layout == "nn" else (n, k))
    fused = gemm(a, b, layout=layout, fused=True)
    multi = gemm(a, b, layout=layout, fused=False)
    assert_same_sum(fused, multi, k)
    np.testing.assert_allclose(fused, ref_gemm(a, b, layout=layout),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("epilogue", [None, "bias", "gelu", "silu", "relu",
                                      "bias_gelu", "bias_silu"])
@pytest.mark.parametrize("accumulate", [False, True])
def test_fused_parity_epilogues(epilogue, accumulate):
    m, n, k = 70, 90, 130  # tails on every dim
    a, b = rand((m, k)), rand((k, n))
    c = rand((m, n)) if accumulate else None
    bias = rand((n,)) if epilogue and "bias" in epilogue else None
    fused = gemm(a, b, c=c, epilogue=epilogue, bias=bias, fused=True)
    multi = gemm(a, b, c=c, epilogue=epilogue, bias=bias, fused=False)
    assert_same_sum(fused, multi, k)
    ref = ref_gemm(a, b, c=c, epilogue=epilogue, bias=bias)
    np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("layout", ["nn", "nt"])
@pytest.mark.parametrize("accumulate", [False, True])
def test_fused_parity_batched(layout, accumulate):
    """desc.batch rides as a leading grid dimension, not a vmap."""
    nb, m, n, k = 3, 40, 70, 50
    a = rand((nb, m, k))
    b = rand((nb, k, n) if layout == "nn" else (nb, n, k))
    c = rand((nb, m, n)) if accumulate else None
    fused = gemm(a, b, c=c, layout=layout, fused=True)
    multi = gemm(a, b, c=c, layout=layout, fused=False)
    assert_same_sum(fused, multi, k)
    np.testing.assert_allclose(fused, ref_gemm(a, b, c=c, layout=layout),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_parity_dtypes(dtype):
    a, b = rand((96, 160), dtype), rand((160, 224), dtype)
    assert_same_sum(gemm(a, b, fused=True), gemm(a, b, fused=False), 160)


def test_multiregion_plan_is_single_launch():
    """Acceptance: a multi-region descriptor resolves to exactly ONE
    pallas_call on the fused path (engine.stats launch counter), and the
    result matches the multi-launch lowering.  Since the
    fused-ranking fix (DESIGN.md §14) the planner itself prices the
    stitched fused walk against per-region launches and comes out
    ``fused=False`` on this cover — the measured fused/multi speedup here
    is < 1 — so the fused path is exercised by forcing the bit."""
    engine.reset_stats()
    d = GemmDescriptor(m=640, n=640, k=512)
    plan = plan_gemm(d, force_block=(256, 256))
    assert len(plan.regions) >= 3 and not plan.fused
    a, b = rand((640, 512)), rand((512, 640))
    fused = gemm(a, b, plan=plan, fused=True)
    assert engine.stats()["gemm"]["launches"] == 1
    multi = gemm(a, b, plan=plan, fused=False)
    assert engine.stats()["gemm"]["launches"] == 1 + len(plan.regions)
    assert_same_sum(fused, multi, 512)


def test_fused_schedule_matches_plan_regions():
    """The flattened schedule covers C exactly once and its windows stay
    inside the operand buffers (clamped two-step load/store)."""
    d = GemmDescriptor(m=513, n=129, k=257)
    sched = plan_gemm(d, force_block=(256, 128)).tile_schedule()
    sched.validate()
    assert sched.bk <= d.k
    assert sched.num_tiles >= len(plan_gemm(d, force_block=(256, 128)).regions)


def test_dispatcher_backends_agree():
    a, b = rand((64, 64)), rand((64, 64))
    with backend("xla"):
        x1 = matmul(a, b)
    with backend("pallas"):
        x2 = matmul(a, b)
    np.testing.assert_allclose(x1, x2, atol=1e-4, rtol=1e-4)


def test_jit_cache_hits():
    from repro.core import GLOBAL_KERNEL_CACHE
    GLOBAL_KERNEL_CACHE.clear()
    a, b = rand((32, 32)), rand((32, 32))
    gemm(a, b)
    h0, m0, _ = GLOBAL_KERNEL_CACHE.stats()
    gemm(a, b)  # same descriptor -> cache hit, no rebuild
    h1, m1, _ = GLOBAL_KERNEL_CACHE.stats()
    assert m1 == m0 and h1 > h0


def test_gradients_flow_through_xla_backend():
    a, b = rand((32, 48)), rand((48, 16))

    def f(a, b):
        with backend("xla"):
            return jnp.sum(matmul(a, b) ** 2)

    ga, gb = jax.grad(f, argnums=(0, 1))(a, b)
    ga_ref, gb_ref = jax.grad(
        lambda a, b: jnp.sum((a @ b) ** 2), argnums=(0, 1))(a, b)
    np.testing.assert_allclose(ga, ga_ref, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(gb, gb_ref, atol=1e-3, rtol=1e-3)
