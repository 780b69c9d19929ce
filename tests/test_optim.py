"""Optimizer substrate: AdamW semantics, schedule, clipping, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.optim.compress import (
    quantize_int8, dequantize_int8, init_error_feedback)


def test_adamw_converges_on_quadratic():
    ocfg = optim.AdamWConfig(lr_peak=0.1, lr_min=0.01, warmup_steps=5,
                             total_steps=200, weight_decay=0.0)
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = optim.init(params, ocfg)
    for _ in range(200):
        grads = {"w": 2 * (params["w"] - target)}
        params, state = optim.update(grads, state, ocfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_grad_clip_bounds_update():
    ocfg = optim.AdamWConfig(lr_peak=1e-2, warmup_steps=0, total_steps=10,
                             clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = optim.init(params, ocfg)
    huge = {"w": jnp.full(4, 1e9)}
    p2, _ = optim.update(huge, state, ocfg, jnp.float32)
    # effective |g| after clip is <= 1, so |delta| <= lr * O(1/sqrt eps-ish)
    assert float(jnp.max(jnp.abs(p2["w"]))) < 1.0


def test_cosine_schedule_shape():
    ocfg = optim.AdamWConfig(lr_peak=1.0, lr_min=0.1, warmup_steps=10,
                             total_steps=100)
    lrs = [float(optim.cosine_lr(ocfg, jnp.int32(s))) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0 + 1e-6
    assert abs(lrs[10] - 1.0) < 0.05
    assert lrs[-1] < 0.2
    assert all(b <= a + 1e-6 for a, b in zip(lrs[10:], lrs[11:]))


def test_bf16_moments_halve_memory():
    params = {"w": jnp.zeros((128, 128))}
    s32 = optim.init(params, optim.AdamWConfig(moments_dtype="float32"))
    s16 = optim.init(params, optim.AdamWConfig(moments_dtype="bfloat16"))
    assert s16.m["w"].dtype == jnp.bfloat16
    assert s32.m["w"].dtype == jnp.float32


def test_int8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_error_feedback_removes_bias():
    """With EF, the *accumulated* applied signal tracks the true sum."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(256,)).astype(np.float32) * 1e-3)
    ef = {"g": jnp.zeros(256)}
    applied = jnp.zeros(256)
    for _ in range(50):
        target = g + ef["g"]
        q, s = quantize_int8(target)
        deq = dequantize_int8(q, s)
        ef = {"g": target - deq}
        applied = applied + deq
    np.testing.assert_allclose(np.asarray(applied), np.asarray(50 * g),
                               atol=float(s) * 1.5)


def test_compressed_psum_under_shard_map():
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compressed_psum_mean
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(dp=jax.device_count(), tp=1)
    grads = {"w": jnp.arange(8, dtype=jnp.float32)}
    ef = init_error_feedback(grads)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
             check_vma=False)
    def f(g, e):
        return compressed_psum_mean(g, e, "data")

    red, ef2 = f(grads, ef)
    np.testing.assert_allclose(np.asarray(red["w"]), np.arange(8), atol=0.05)
