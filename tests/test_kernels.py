"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_chunk_scan import mamba_chunk_scan
from repro.kernels.rmsnorm import rmsnorm

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd,causal,window,cap", [
    (2, 256, 4, 2, 64, True, None, None),
    (1, 256, 8, 8, 128, True, None, 50.0),
    (2, 512, 4, 1, 64, True, 128, None),
    (1, 128, 4, 4, 32, False, None, None),
    (1, 384, 6, 2, 64, True, 256, 30.0),
])
def test_flash_attention(rng, dtype, b, s, h, kv, hd, causal, window, cap):
    q = _rand(rng, (b, s, h, hd), dtype)
    k = _rand(rng, (b, s, kv, hd), dtype)
    v = _rand(rng, (b, s, kv, hd), dtype)
    scale = 1.0 / np.sqrt(hd)
    want = ref.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=cap, scale=scale)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,kv,hd,window,cap", [
    (2, 256, 8, 2, 64, None, None),
    (1, 512, 4, 4, 128, 128, None),
    (3, 256, 16, 8, 64, None, 30.0),
    (2, 384, 8, 1, 32, 64, None),
])
def test_decode_attention(rng, dtype, b, t, h, kv, hd, window, cap):
    q = _rand(rng, (b, 1, h, hd), dtype)
    k = _rand(rng, (b, t, kv, hd), dtype)
    v = _rand(rng, (b, t, kv, hd), dtype)
    lengths = jnp.asarray(rng.integers(1, t, size=(b,)), jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    want = ref.decode_attention(q, k, v, lengths=lengths, window=window,
                                softcap=cap, scale=scale)
    got = decode_attention(q, k, v, lengths=lengths, window=window,
                           softcap=cap, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


def _repeat_then_attend(q, k, v, lengths, window, softcap, scale):
    """Decode attention as plain MHA over K/V repeated to every query head."""
    g = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k).astype(jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    t = jnp.arange(k.shape[1])[None, :]
    ok = t < lengths[:, None]
    if window:
        ok &= t >= lengths[:, None] - window
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqt,bthd->bqhd", p, v)


# XLA's CPU dot kernels sum the grouped and the repeated contractions in
# different orders: float32 agrees to an ulp or two, bfloat16 to one ulp
REPEAT_TOL = {jnp.float32: dict(rtol=1e-6, atol=1e-6),
              jnp.bfloat16: dict(rtol=2 ** -7, atol=2 ** -7)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cap", [None, 30.0])
@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("g", [1, 2, 4, 7])
def test_ref_decode_attention_matches_repeat_formula(rng, g, window, cap,
                                                     dtype):
    b, t, kv, hd = 4, 192, 2, 64
    h = kv * g
    q = _rand(rng, (b, 1, h, hd), dtype)
    k = _rand(rng, (b, t, kv, hd), dtype)
    v = _rand(rng, (b, t, kv, hd), dtype)
    lengths = jnp.asarray([1, t, 37, 150], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    want = _repeat_then_attend(q, k, v, lengths, window, cap, scale)
    got = ref.decode_attention(q, k, v, lengths=lengths, window=window,
                               softcap=cap, scale=scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **REPEAT_TOL[dtype])


def test_ref_decode_attention_does_not_repeat_kv():
    """No tensor of the lowered decode attention carries the cache's time
    axis with all H query heads, flat or as KV x G: each group reads its
    KV head in place."""
    b, t, h, kv, hd = 2, 256, 56, 8, 128
    g = h // kv
    spec = jax.ShapeDtypeStruct
    text = jax.jit(lambda q, k, v, n: ref.decode_attention(
        q, k, v, lengths=n, scale=hd ** -0.5)).lower(
        spec((b, 1, h, hd), jnp.bfloat16), spec((b, t, kv, hd), jnp.bfloat16),
        spec((b, t, kv, hd), jnp.bfloat16), spec((b,), jnp.int32)).as_text()
    shapes = [tuple(map(int, m.rstrip("x").split("x")))
              for m in re.findall(r"tensor<((?:\d+x)+)", text)]
    assert any(s == (b, t, kv, hd) for s in shapes)      # the cache itself
    for s in shapes:
        if t in s and hd in s:
            assert h not in s and not (kv in s and g in s), s


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 37, 256), (2, 128), (1, 8, 8, 512)])
@pytest.mark.parametrize("zero_centered", [True, False])
def test_rmsnorm(rng, dtype, shape, zero_centered):
    x = _rand(rng, shape, dtype)
    s = _rand(rng, (shape[-1],), dtype) * 0.1
    want = ref.rmsnorm(x, s, zero_centered=zero_centered)
    got = rmsnorm(x, s, zero_centered=zero_centered, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("b,s,nh,hd,ns,chunk", [
    (2, 128, 3, 32, 16, 32),
    (1, 256, 2, 64, 32, 64),
    (1, 64, 4, 16, 8, 64),   # single chunk
])
def test_mamba_chunk_scan(rng, b, s, nh, hd, ns, chunk):
    x = _rand(rng, (b, s, nh, hd), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, nh))) * 0.1 + 0.01,
                     jnp.float32)
    a = -jnp.asarray(np.abs(rng.standard_normal(nh)) + 0.1, jnp.float32)
    bm = _rand(rng, (b, s, ns), jnp.float32)
    cm = _rand(rng, (b, s, ns), jnp.float32)
    d = _rand(rng, (nh,), jnp.float32)
    want_y, want_h = ref.mamba_chunk_scan(x, dt, a, bm, cm, d)
    got_y, got_h = mamba_chunk_scan(x, dt, a, bm, cm, d, chunk=chunk,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=2e-4, atol=2e-4)


def test_mamba_chunk_scan_with_initial_state(rng):
    b, s, nh, hd, ns = 1, 128, 2, 16, 8
    x = _rand(rng, (b, s, nh, hd), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, nh))) * 0.1 + 0.01)
    a = -jnp.asarray(np.abs(rng.standard_normal(nh)) + 0.1)
    bm = _rand(rng, (b, s, ns), jnp.float32)
    cm = _rand(rng, (b, s, ns), jnp.float32)
    d = _rand(rng, (nh,), jnp.float32)
    # split in two halves: h from first half feeds second half
    y1, h1 = ref.mamba_chunk_scan(x[:, :64], dt[:, :64], a, bm[:, :64],
                                  cm[:, :64], d)
    y2k, h2k = mamba_chunk_scan(x[:, 64:], dt[:, 64:], a, bm[:, 64:],
                                cm[:, 64:], d, chunk=32, h0=h1,
                                interpret=True)
    y_full, h_full = ref.mamba_chunk_scan(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(y2k), np.asarray(y_full[:, 64:]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2k), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)


def test_model_chunked_paths_match_oracles(rng):
    """The model-side chunked SSD/mLSTM implementations (associative scan)
    agree with the sequential/stabilised oracles."""
    from repro.models import mamba2 as m2
    b, s, nh, hd, ns = 2, 96, 2, 16, 8
    x = _rand(rng, (b, s, nh, hd), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, nh))) * 0.1 + 0.01)
    a = -jnp.asarray(np.abs(rng.standard_normal(nh)) + 0.1)
    bm = _rand(rng, (b, s, ns), jnp.float32)
    cm = _rand(rng, (b, s, ns), jnp.float32)
    d = _rand(rng, (nh,), jnp.float32)
    h0 = jnp.zeros((b, nh, hd, ns), jnp.float32)
    y_model, h_model = m2._ssd_chunked(x, dt, a, bm, cm, d, h0, 32)
    y_ref, h_ref = ref.mamba_chunk_scan(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_ref),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(h_model), np.asarray(h_ref),
                               rtol=5e-4, atol=5e-4)

    from repro.models import xlstm as xl
    q = _rand(rng, (b, s, nh, hd), jnp.float32)
    k = _rand(rng, (b, s, nh, hd), jnp.float32)
    v = _rand(rng, (b, s, nh, hd), jnp.float32)
    ig = _rand(rng, (b, s, nh), jnp.float32) * 2
    fg = _rand(rng, (b, s, nh), jnp.float32) * 2 + 2
    c0 = jnp.zeros((b, nh, hd, hd))
    n0 = jnp.zeros((b, nh, hd))
    y_model, _, _ = xl._mlstm_chunked(q, k, v, ig, fg, c0, n0, 32)
    y_ref = ref.mlstm_chunkwise(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
