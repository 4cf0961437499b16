"""Pallas TPU decode attention (GQA flash-decoding).

One new token per sequence against a (B, T, KV, hd) cache.  Grid:
(batch, kv_heads, kv_blocks); each program attends the whole G-head query
group (G x hd tile — MXU-friendly since G*hd is a multiple of 128 for the
assigned archs) against one KV block, carrying online-softmax state in
VMEM scratch.  Valid lengths arrive via scalar prefetch (SMEM), masking
both the tail beyond ``lengths`` and, for sliding-window layers, the
prefix before ``lengths - window``.

Blocks are head-major, as in :mod:`repro.kernels.flash_attention`: the
wrapper views the cache as ``(B,KV,T,hd)`` and the query as
``(B,KV,G,hd)``, so each block's last two dimensions are a
(time, head-dim) or (group, head-dim) tile the TPU compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale, window, softcap, blk_k, kv_blocks):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]
    k_start = ki * blk_k
    live = k_start < length
    if window is not None and window > 0:
        live &= k_start + blk_k > length - window

    @pl.when(live)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None and window > 0:
            mask &= kpos >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...]
                                / jnp.maximum(l_scr[...], 1e-30)[:, None]
                                ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "blk_k",
                              "interpret"))
def decode_attention(q, k, v, *, lengths, window=None, softcap=None,
                     scale=1.0, blk_k=128, interpret=False):
    """q: (B,1,H,hd); k,v: (B,T,KV,hd); lengths: (B,) -> (B,1,H,hd)."""
    b, one, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    blk_k = min(blk_k, t)
    assert t % blk_k == 0
    nk = t // blk_k
    qg = q.reshape(b, kv, g, hd)

    kernel = functools.partial(_dec_kernel, scale=scale, window=window,
                               softcap=softcap, blk_k=blk_k, kv_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, ci, ki, lens: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, ci, ki, lens: (bi, ci, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, ci, ki, lens: (bi, ci, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, ci, ki, lens: (bi, ci, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), qg, k.swapaxes(1, 2),
      v.swapaxes(1, 2))
    return out.reshape(b, 1, h, hd)
