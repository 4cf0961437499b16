"""Pallas TPU flash attention (forward): blocked online-softmax with
explicit VMEM tiling.

Grid: (batch, q_heads, q_blocks, kv_blocks) — kv innermost ("arbitrary"
semantics) carrying running (m, l, acc) in VMEM scratch; fully-masked kv
blocks (beyond the causal frontier / outside the sliding window) are
skipped with ``pl.when`` so the work matches a real flash kernel.  GQA is
expressed in the K/V index maps (kv head = q head // group), so no
expanded K/V ever materialises.

Blocks are head-major: the wrapper moves heads ahead of the sequence
(``(B,H,S,hd)``) so every block is ``(1, 1, blk, hd)`` and its last two
dimensions are a (sequence, head-dim) tile, which is what the TPU
compiler's tiling rules accept.  A ``(1, blk, 1, hd)`` block over the
model's ``(B,S,H,hd)`` layout puts a size-1 head block second-minor and
is refused.  Checked against kernels/ref.py in interpret mode
(tests/test_kernels.py) and compiled for v5e (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int | None,
               softcap: float | None, blk_q: int, blk_k: int,
               kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k

    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (blk_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None and window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new
        l_scr[...] = l_new

    # skip blocks fully outside the causal / window support
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + blk_q - 1
    if window is not None and window > 0:
        live &= k_start + blk_k - 1 >= q_start - window + 1

    @pl.when(live)
    def _run():
        _block()

    @pl.when(ki == kv_blocks - 1)
    def _emit():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "q_offset", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=1.0, q_offset=0, blk_q=128, blk_k=128,
                    interpret=False):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd) -> (B,S,H,hd)."""
    assert q_offset == 0, "pallas path expects full-sequence queries"
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    assert s % blk_q == 0 and t % blk_k == 0, (s, t, blk_q, blk_k)
    nq, nk = s // blk_q, t // blk_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, kv_blocks=nk)

    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),      # running max m
            pltpu.VMEM((blk_q,), jnp.float32),      # running sum l
            pltpu.VMEM((blk_q, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
