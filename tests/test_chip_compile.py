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
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import GemmDescriptor, engine, plan_gemm, use
from repro.kernels.flash_attention import (flash_attention,
                                           paged_decode_attention)
from repro.kernels.gemm import gemm
from repro.kernels.grouped_gemm import grouped_gemm
from repro.kernels.ssd_chunk import ssd_chunk_scan
from repro.kernels.transpose import transpose
from repro.models.attention import PageSpec
from repro.runtime.pages import init_serving_cache
from repro.runtime.steps import make_paged_serve_step, param_shapes

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


# One HLO instruction: its name, result type and opcode.
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([\w\-]+)\(")


def test_paged_serve_step_keeps_pool_in_place(one_chip):
    # The continuous-batching decode step at qwen3-0.6b widths over three
    # scanned layers, 8 slots and the chat cell's 1,792 pages of 16 (a
    # stack of pools too large for VMEM, as every served pool is: a
    # smaller one may be staged in VMEM around the loop and copied back),
    # compiled with the donation the engine jits it with.  The stacked
    # pools ride the layer scan as its carry and are written in place,
    # so the program holds no copy, slice or update-slice of a whole
    # stack or of one layer's pool, and the donated pools alias the
    # step's output.
    layers, pages, slots = 3, 1792, 8
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=layers)
    spec = PageSpec(num_pages=pages, page_size=16, max_blocks=64)

    def chip(x):
        dt = jnp.bfloat16 if jnp.issubdtype(x.dtype, jnp.floating) \
            else x.dtype
        return jax.ShapeDtypeStruct(x.shape, dt, sharding=one_chip)

    params = jax.tree.map(chip, param_shapes(cfg))
    cache = jax.tree.map(chip, jax.eval_shape(
        lambda: init_serving_cache(cfg, slots, spec)))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    with use(backend="pallas", interpret=False):
        text = jax.jit(make_paged_serve_step(cfg), donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((slots, 1), jnp.int32,
                                                sharding=one_chip),
            rows, rows.update(dtype=jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text

    dims = (f"[{layers},{pages},16,8,128]", f"[{pages},16,8,128]")
    moved, pool_params = [], []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, result, op = m.groups()
        if not any(d + "{" in result for d in dims):
            continue
        if op == "parameter" and name.startswith("cache"):
            pool_params.append(int(re.search(r"parameter\((\d+)\)",
                                             line).group(1)))
        if re.search(r"copy|dynamic-slice|dynamic-update-slice",
                     f"{name} {op}"):
            moved.append(line.strip()[:160])
    assert not moved, "pool-sized copies or slices:\n" + "\n".join(moved)
    assert len(pool_params) == 2  # the stacked K and V pools
    alias = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    for n in pool_params:
        assert f": ({n}, {{}}" in alias, (n, alias)


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


def _granite_period():
    """granite-4.0-h-small at published widths cut as its benchmark cell
    serves it: one period of 10 layers (the attention mixer at index 5),
    9 of the 72 experts held."""
    return dataclasses.replace(get_config("granite-4.0-h-small"),
                               num_layers=10, experts_held=9)


def test_granite_paged_decode_step(one_chip):
    # The continuous-batching decode step of the cut Granite model over
    # 48 slots and 1,792 pages of 16: paged flash_decode for the GQA
    # layer, the recurrent SSD step for the nine Mamba-2 layers, and the
    # held experts through grouped_gemm with runtime group sizes.
    from repro.launch.serve import load_params
    cfg, slots = _granite_period(), 48
    spec = PageSpec(num_pages=1792, page_size=16, max_blocks=112)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: load_params(cfg)))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_serving_cache(cfg, slots, spec)))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    engine.reset_stats()
    with use(backend="pallas", interpret=False):
        text = jax.jit(make_paged_serve_step(cfg), donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((slots, 1), jnp.int32,
                                                sharding=one_chip),
            rows, rows.update(dtype=jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text
    stats = engine.stats()
    assert stats["grouped_gemm"]["launches"] >= 3
    assert stats["flash_decode"]["launches"] >= 1


def test_granite_prefill(one_chip):
    # One prompt of 320 tokens (two chunks of 256, the second ragged):
    # ssd_chunk over 128 head groups, causal flash attention at the
    # published score scale, and the held experts through grouped_gemm.
    from repro.launch.serve import load_params
    from repro.runtime.steps import make_prefill_step
    cfg, length = _granite_period(), 320
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: load_params(cfg)))
    tokens = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)
    engine.reset_stats()
    with use(backend="pallas", interpret=False):
        text = jax.jit(make_prefill_step(cfg, length)).lower(
            params, {"tokens": tokens}).compile().as_text()
    assert "tpu_custom_call" in text
    stats = engine.stats()
    for fam in ("ssd_chunk", "grouped_gemm", "flash_attention"):
        assert stats[fam]["launches"] >= 1, fam
