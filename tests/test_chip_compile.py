"""Each kernel family compiled for a described TPU v5e at real widths.

Interpret mode cannot show what Mosaic refuses (slices it cannot prove
aligned, layouts it cannot relayout, VMEM it cannot hold), so these
tests compile every family's kernel for a ``v5e:2x2`` topology that is
described, not attached, at qwen3-0.6b widths (mamba2-130m for the SSD
scan).  Nothing runs.  Each compiled program must hold a Mosaic kernel
(``tpu_custom_call``): an interpreted kernel lowers to plain HLO.

The topology is described inside a module fixture — only the worker
that runs this file loads the TPU compiler — and the tests skip where it
cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import GemmDescriptor, engine, plan_gemm, use
from repro.kernels.flash_attention import (flash_attention,
                                           paged_decode_attention)
from repro.kernels.gemm import gemm
from repro.kernels.grouped_gemm import grouped_gemm
from repro.kernels.ssd_chunk import ssd_chunk_scan
from repro.kernels.transpose import transpose

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for_chip(one_chip, fn, *shapes, fused="on"):
    """Compile ``fn`` for the described chip with compiled kernels and
    the fused lowerings; returns the compiled program's HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    with use(backend="pallas", interpret=False, fused=fused):
        return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_gemm(one_chip):
    # qwen3-0.6b MLP up-projection over a 256-token prefill.
    text = compile_for_chip(one_chip, gemm, ((256, 1024), BF16),
                            ((1024, 3072), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,fused", [(5120, True), (8192, False)])
def test_gemm_vmem_boundary(one_chip, m, fused):
    # The up-projection over an m-token prefill, either side of the fused
    # lowering's VMEM limit.  At 5120 rows the planner calls it fused and
    # the kernel asks Mosaic for 96 MiB, which must compile; at 8192 rows
    # it would need 140 MiB, so the planner takes the multi-launch
    # lowering instead of a fused kernel Mosaic refuses.
    desc = GemmDescriptor(m=m, n=3072, k=1024, in_dtype="bfloat16",
                          out_dtype="bfloat16")
    assert plan_gemm(desc).fused is fused
    engine.reset_stats()
    text = compile_for_chip(one_chip, gemm, ((m, 1024), BF16),
                            ((1024, 3072), BF16), fused="auto")
    assert "tpu_custom_call" in text
    stats = engine.stats()["gemm"]
    assert stats["launches"] > 0
    assert (stats["launches_fused"] > 0) is fused


def test_fused_gemm_single_row_k_tail(one_chip):
    # One decode row through the down-projection: a K tail (3072 over
    # 2048-wide panels) masked on a single-row bf16 operand.
    text = compile_for_chip(one_chip, gemm, ((1, 3072), BF16),
                            ((3072, 1024), BF16))
    assert "tpu_custom_call" in text


def test_causal_flash_attention(one_chip):
    qkv = ((1, 2048, 16, 128), BF16)
    text = compile_for_chip(
        one_chip, lambda q, k, v: flash_attention(q, k, v, causal=True),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_flash_decode(one_chip):
    # 8 decode slots, 16 query / 8 KV heads of 128, 1024 pages of 16.
    pool = ((1024, 16, 8, 128), BF16)
    text = compile_for_chip(one_chip, paged_decode_attention,
                            ((8, 16, 128), BF16), pool, pool,
                            ((8, 64), I32), ((8,), I32))
    assert "tpu_custom_call" in text


def test_fused_grouped_gemm(one_chip):
    text = compile_for_chip(one_chip, grouped_gemm, ((1024, 1024), BF16),
                            ((8, 1024, 768), BF16), ((8,), I32))
    assert "tpu_custom_call" in text


def test_ssd_chunk_scan(one_chip):
    # mamba2-130m: 24 heads of 64, state 128, chunk 256, 4 chunks.
    g, nc, q, n, p = 24, 4, 256, 128, 64
    text = compile_for_chip(
        one_chip, ssd_chunk_scan, ((g, nc, q, n), BF16),
        ((g, nc, q, n), BF16), ((g, nc, q, q), BF16), ((g, nc, q, p), BF16),
        ((g, nc, q), F32), ((g, nc, q), F32), ((g, p, n), F32))
    assert "tpu_custom_call" in text


def test_transpose(one_chip):
    text = compile_for_chip(one_chip, transpose, ((1024, 3072), BF16))
    assert "tpu_custom_call" in text
