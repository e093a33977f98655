"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize(
    "b,h,t,hd,window",
    [
        (2, 4, 256, 64, None),
        (1, 2, 512, 64, None),
        (2, 2, 256, 128, None),
        (1, 4, 256, 64, 64),
        (1, 1, 128, 32, 32),
    ],
)
def test_flash_attention_sweep(b, h, t, hd, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(b * 100 + t), 3)
    q = _rand(k1, (b, h, t, hd), dtype)
    k = _rand(k2, (b, h, t, hd), dtype)
    v = _rand(k3, (b, h, t, hd), dtype)
    scale = hd**-0.5
    out = ops.flash_attention(q, k, v, scale=scale, window=window, interpret=True)
    exp = ref.flash_attention_ref(
        q.reshape(b * h, t, hd), k.reshape(b * h, t, hd), v.reshape(b * h, t, hd),
        scale=scale, window=window,
    ).reshape(b, h, t, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("s,valid_upto", [(512, 511), (1024, 700), (2048, 1)])
def test_decode_attention_sweep(s, valid_upto, dtype):
    b, h, hd = 2, 4, 64
    k1, k2, k3 = jax.random.split(jax.random.key(s), 3)
    q = _rand(k1, (b, h, 1, hd), dtype)
    k = _rand(k2, (b, h, s, hd), dtype)
    v = _rand(k3, (b, h, s, hd), dtype)
    valid = (jnp.arange(s) <= valid_upto).astype(jnp.int32)
    out = ops.decode_attention(q, k, v, valid, scale=hd**-0.5, interpret=True)
    exp = ref.decode_attention_ref(
        q.reshape(b * h, 1, hd), k.reshape(b * h, s, hd), v.reshape(b * h, s, hd),
        jnp.broadcast_to(valid[None], (b * h, s)), scale=hd**-0.5,
    ).reshape(b, h, 1, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize(
    "t,h,p,g,n,chunk",
    [
        (256, 4, 64, 1, 32, 64),
        (128, 2, 32, 2, 16, 32),
        (512, 4, 64, 1, 64, 128),
    ],
)
def test_ssd_scan_sweep(t, h, p, g, n, chunk, dtype):
    b = 2
    keys = jax.random.split(jax.random.key(t + h), 5)
    x = _rand(keys[0], (b, t, h, p), dtype)
    dt = jax.nn.softplus(_rand(keys[1], (b, t, h), jnp.float32)) * 0.1
    a = -jnp.exp(jax.random.normal(keys[2], (h,)))
    bm = _rand(keys[3], (b, t, g, n), dtype)
    cm = _rand(keys[4], (b, t, g, n), dtype)
    out = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    rep = h // g
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, t, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, t, 1)
    ar = jnp.broadcast_to(a[None], (b, h)).reshape(b * h, 1)
    bmr = jnp.repeat(bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, t, n)
    cmr = jnp.repeat(cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, t, n)
    exp = (
        ref.ssd_scan_ref(xr, dtr, ar, bmr, cmr)
        .reshape(b, h, t, p)
        .transpose(0, 2, 1, 3)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-3, rtol=3e-2,
    )


def test_ssd_kernel_matches_model_chunked_path():
    """The pure-jnp model SSD (mamba2.ssd_chunked) agrees with the kernel."""
    from repro.models.mamba2 import ssd_chunked

    b, t, h, p, g, n = 1, 128, 2, 32, 1, 16
    keys = jax.random.split(jax.random.key(0), 5)
    x = _rand(keys[0], (b, t, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(keys[1], (b, t, h), jnp.float32)) * 0.1
    a = -jnp.exp(jax.random.normal(keys[2], (h,)))
    bm = _rand(keys[3], (b, t, g, n), jnp.float32)
    cm = _rand(keys[4], (b, t, g, n), jnp.float32)
    y_model, _ = ssd_chunked(x, dt, a, bm, cm, chunk=32)
    y_kernel = ops.ssd_scan(x, dt, a, bm, cm, chunk=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_model, np.float32), np.asarray(y_kernel, np.float32),
        atol=1e-3, rtol=1e-3,
    )


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention", "ssd_scan"])
def test_kernels_refuse_cpu_unless_interpret_asked(kernel):
    """No silent interpreter: off a TPU a kernel raises unless asked."""
    k = jax.random.key(0)
    if kernel == "flash_attention":
        q = _rand(k, (1, 1, 128, 64), jnp.float32)
        call = lambda **kw: ops.flash_attention(q, q, q, scale=0.125, **kw)
    elif kernel == "decode_attention":
        q = _rand(k, (1, 1, 1, 64), jnp.float32)
        kv = _rand(k, (1, 1, 128, 64), jnp.float32)
        valid = jnp.ones((128,), jnp.int32)
        call = lambda **kw: ops.decode_attention(q, kv, kv, valid, scale=0.125, **kw)
    else:
        x = _rand(k, (1, 64, 1, 32), jnp.float32)
        dt = jnp.full((1, 64, 1), 0.1)
        bc = _rand(k, (1, 64, 1, 16), jnp.float32)
        call = lambda **kw: ops.ssd_scan(x, dt, -jnp.ones((1,)), bc, bc, chunk=64, **kw)
    with pytest.raises(RuntimeError, match="interpret=True"):
        call()
    assert np.isfinite(np.asarray(call(interpret=True), np.float32)).all()
