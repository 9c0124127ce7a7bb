"""Optimizers, schedules, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw, scalable_adamw, warmup_cosine
from repro.optim.adamw import clip_by_global_norm, global_norm
from repro.optim.compression import (error_feedback_compress,
                                     compressed_psum, _quantize_int8,
                                     _dequantize_int8)


def quadratic_loss(params):
    return sum(jnp.sum(jnp.square(p - 3.0)) for p in jax.tree.leaves(params))


@pytest.mark.parametrize("make_opt", [
    lambda: adamw(0.1),
    lambda: scalable_adamw(0.1),
    lambda: scalable_adamw(0.1, use_momentum=False),
])
def test_optimizer_converges_on_quadratic(make_opt):
    opt = make_opt()
    params = {"w": jnp.zeros((256, 256)), "b": jnp.zeros((256,))}
    state = opt.init(params)
    loss0 = float(quadratic_loss(params))
    for step in range(60):
        grads = jax.grad(quadratic_loss)(params)
        params, state, _ = opt.update(grads, state, params,
                                      jnp.asarray(step))
    assert float(quadratic_loss(params)) < 0.2 * loss0


def test_scalable_adamw_factored_state_is_small():
    opt = scalable_adamw(1e-3, use_momentum=False)
    params = {"w": jnp.zeros((512, 1024))}
    state = opt.init(params)
    v = state["v"]["w"]
    assert set(v) == {"r", "c"}
    assert v["r"].shape == (512,) and v["c"].shape == (1024,)
    n_state = sum(x.size for x in jax.tree.leaves(state))
    assert n_state < 0.01 * params["w"].size


def test_clip_preserves_dtype_and_norm():
    grads = {"a": jnp.full((8,), 100.0, jnp.bfloat16)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert clipped["a"].dtype == jnp.bfloat16
    assert abs(float(global_norm(clipped)) - 1.0) < 0.05


def test_warmup_cosine_shape():
    lr = warmup_cosine(1e-3, 100, 1000)
    assert float(lr(jnp.asarray(0))) < 1e-4
    assert abs(float(lr(jnp.asarray(100))) - 1e-3) < 1e-4
    assert float(lr(jnp.asarray(999))) < 2.1e-4


def test_int8_quantization_roundtrip_bound():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                    jnp.float32)
    q, scale = _quantize_int8(x)
    deq = _dequantize_int8(q, scale, x.shape)
    # block-symmetric int8: error bounded by scale/2 per block
    err = np.abs(np.asarray(deq - x))
    bound = np.repeat(np.asarray(scale)[:, 0], 256)[:1000] * 0.51
    assert (err <= bound + 1e-6).all()


def test_error_feedback_residual_corrects():
    """Error feedback: sum of applied grads converges to sum of true grads
    (residual stays bounded)."""
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(512),
                          jnp.float32)}
    res = None
    applied = jnp.zeros(512)
    for _ in range(20):
        out, res = error_feedback_compress(g, res)
        applied = applied + out["w"]
    total_true = 20 * g["w"]
    rel = float(jnp.linalg.norm(applied - total_true) /
                jnp.linalg.norm(total_true))
    assert rel < 0.02


def test_compressed_psum_single_device():
    from jax.sharding import Mesh
    import jax
    mesh_devices = np.array(jax.devices()[:1])
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(mesh_devices, ("pod",))
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, 16)),
                    jnp.float32)

    def f(x):
        return compressed_psum(x, "pod")

    out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
    np.testing.assert_allclose(out, x, atol=0.05, rtol=0.05)
