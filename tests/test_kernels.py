"""Per-kernel shape/dtype sweeps of the model-plane Pallas kernels
(interpret mode) against their pure-jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("shape,causal,window,dt,tol", [
    ((1, 4, 2, 128, 128, 64), True, None, jnp.float32, 2e-5),
    ((2, 8, 8, 64, 64, 32), True, None, jnp.bfloat16, 2e-2),
    ((1, 4, 1, 128, 256, 64), True, None, jnp.float32, 2e-5),   # GQA prefill
    ((1, 2, 2, 96, 96, 64), True, 32, jnp.float32, 2e-5),        # window
    ((1, 2, 2, 64, 64, 128), False, None, jnp.float32, 2e-5),
    ((1, 4, 2, 1, 128, 64), True, None, jnp.float32, 2e-5),      # decode q
    ((1, 1, 1, 256, 256, 64), True, 128, jnp.bfloat16, 2e-2),
])
def test_flash_attention(shape, causal, window, dt, tol):
    b, hq, hkv, t, s, d = shape
    q = jnp.asarray(RNG.normal(size=(b, hq, t, d)), dt)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dt)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dt)
    got = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,t,dk,dv", [
    (1, 2, 64, 16, 16), (2, 1, 128, 32, 64), (1, 1, 256, 64, 64),
])
def test_rwkv6_kernel(b, h, t, dk, dv):
    r = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, t, dv)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.3, 0.99, size=(b, h, t, dk)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, dk)), jnp.float32)
    np.testing.assert_allclose(ops.rwkv6(r, k, v, w, u),
                               ref.rwkv6(r, k, v, w, u),
                               rtol=3e-4, atol=3e-4)


def test_rwkv6_chunked_matches_scan():
    b, h, t, dk, dv = 2, 3, 128, 32, 48
    r = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, t, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, t, dv)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.5, 0.995, size=(b, h, t, dk)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, dk)), jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(b, h, dk, dv)) * 0.1, jnp.float32)
    want, sw = ref.rwkv6(r, k, v, w, u, state=s0, return_state=True)
    got, sg = ref.rwkv6_chunked(r, k, v, w, u, chunk=32, state=s0,
                                return_state=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(sg, sw, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("g,t,d", [(2, 64, 8), (1, 500, 16), (3, 256, 128)])
def test_linear_scan_kernel(g, t, d):
    a = jnp.asarray(RNG.uniform(0.2, 0.99, size=(g, t, d)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(g, t, d)), jnp.float32)
    np.testing.assert_allclose(ops.linear_scan(a, b), ref.linear_scan(a, b),
                               rtol=1e-4, atol=1e-4)


def test_linear_scan_chunked_and_grad():
    import jax

    g, t, d = 2, 512, 16
    a = jnp.asarray(RNG.uniform(0.2, 0.99, size=(g, t, d)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(g, t, d)), jnp.float32)
    np.testing.assert_allclose(ref.linear_scan_chunked(a, b, chunk=128),
                               ref.linear_scan(a, b), rtol=1e-4, atol=1e-4)
    # chunk-checkpointed version must be differentiable
    f = lambda a_, b_: ref.linear_scan_chunked(a_, b_, chunk=128).sum()
    ga, gb = jax.grad(f, argnums=(0, 1))(a, b)
    assert np.isfinite(np.asarray(ga)).all() and np.isfinite(np.asarray(gb)).all()
