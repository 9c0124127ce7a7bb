"""Every Pallas kernel carries its engine family's name.

A profiler trace names each device operation by its HLO instruction, and
a named ``pallas_call`` gives its custom call that name, so a trace can
find a family's kernels however their operands change.  Each case builds
one lowering of a family (forward, fused or not, and backward) and reads
the names of the ``pallas_call`` equations in its jaxpr.  Nothing runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.core import use
from repro.kernels.flash_attention import (flash_attention,
                                           paged_decode_attention)
from repro.kernels.gemm import gemm
from repro.kernels.grouped_gemm import grouped_gemm
from repro.kernels.ssd_chunk import ssd_chunk_scan
from repro.kernels.transpose import transpose

F32, I32 = jnp.float32, jnp.int32
QKV = ((1, 64, 2, 32), F32)
POOL = ((16, 8, 2, 32), F32)
GROUPED = (((64, 64), F32), ((4, 64, 128), F32), ((4,), I32))
G, NC, Q, N, P = 2, 2, 16, 8, 8
SSD = (((G, NC, Q, N), F32), ((G, NC, Q, N), F32), ((G, NC, Q, Q), F32),
       ((G, NC, Q, P), F32), ((G, NC, Q), F32), ((G, NC, Q), F32),
       ((G, P, N), F32))


def _causal(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _flash_grad(q, k, v):
    return jax.grad(lambda q: _causal(q, k, v).sum())(q)


def _grouped_grad(x, w, g):
    return jax.grad(lambda x, w: grouped_gemm(x, w, g).sum(),
                    argnums=(0, 1))(x, w)


def _ssd_grad(*args):
    return jax.grad(lambda *a: ssd_chunk_scan(*a)[0].sum())(*args)


CASES = {
    "gemm-fused": ("on", gemm, (((64, 64), F32), ((64, 128), F32)),
                   {"gemm"}),
    "gemm-multi": ("off", gemm, (((64, 64), F32), ((64, 128), F32)),
                   {"gemm"}),
    "flash_attention-fused": ("on", _causal, (QKV,) * 3,
                              {"flash_attention"}),
    "flash_attention-dense": ("off", _causal, (QKV,) * 3,
                              {"flash_attention"}),
    "flash_attention_bwd": ("auto", _flash_grad, (QKV,) * 3,
                            {"flash_attention", "flash_attention_bwd"}),
    "flash_decode": ("auto", paged_decode_attention,
                     (((2, 4, 32), F32), POOL, POOL, ((2, 4), I32),
                      ((2,), I32)), {"flash_decode"}),
    "grouped_gemm-fused": ("on", grouped_gemm, GROUPED, {"grouped_gemm"}),
    "grouped_gemm-padded": ("off", grouped_gemm, GROUPED,
                            {"grouped_gemm"}),
    "grouped_gemm_bwd": ("auto", _grouped_grad, GROUPED,
                         {"grouped_gemm", "grouped_gemm_bwd"}),
    "ssd_chunk-scan": ("on", ssd_chunk_scan, SSD, {"ssd_chunk"}),
    "ssd_chunk-chunks": ("off", ssd_chunk_scan, SSD, {"ssd_chunk"}),
    "ssd_chunk_bwd": ("auto", _ssd_grad, SSD, {"ssd_chunk", "ssd_chunk_bwd"}),
    "transpose": ("auto", transpose, (((64, 128), F32),), {"transpose"}),
}


def _kernel_names(jaxpr):
    """The name of every ``pallas_call`` in ``jaxpr`` and its sub-jaxprs."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    names += _kernel_names(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    names += _kernel_names(sub)
    return names


@pytest.mark.parametrize("case", CASES)
def test_kernel_carries_its_family_name(case):
    fused, fn, shapes, want = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    with use(backend="pallas", fused=fused):
        # A fresh function each time: make_jaxpr caches traces by function,
        # and the ambient lowering policy is not part of that key.
        names = _kernel_names(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)
    assert names and set(names) == want
