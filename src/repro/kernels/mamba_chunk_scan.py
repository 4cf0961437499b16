"""Pallas TPU Mamba-2 SSD chunk scan.

Grid: (batch, heads, chunks) with the chunk dim sequential ("arbitrary"),
carrying the (HD, NS) state in VMEM scratch across chunks.  Within a chunk
everything is dense MXU work: the (Q, Q) decay-masked score block, the
state outer-product update, and the inter-chunk contribution — the TPU
reshaping of Mamba-2's GPU kernel (DESIGN.md hardware-adaptation notes).

Blocks are head-major (``x`` as ``(B,NH,S,HD)``, ``dt`` as one row per
chunk, ``(B,NH,1,S)``) so every block's last two dimensions form a tile
the TPU compiler accepts; the per-head scalars ``a`` and ``d`` live whole
in SMEM.  The within-chunk cumulative sum is a masked (Q, Q) reduction,
computed once as a column and once as a row, so no vector is transposed
inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hf_ref, h_scr, *, q: int, n_chunks: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, HD)
    dt_row = dt_ref[0, 0].astype(jnp.float32)        # (1, Q)
    a = a_ref[hi]                                    # scalar
    bm = b_ref[0].astype(jnp.float32)                # (Q, NS)
    cm = c_ref[0].astype(jnp.float32)                # (Q, NS)
    dsk = d_ref[hi]                                  # scalar

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = t_idx >= u_idx                             # u <= t
    dt_col = jnp.sum(jnp.where(t_idx == u_idx, dt_row, 0.0), axis=1,
                     keepdims=True)                  # (Q, 1)
    # F_t = sum_{u<=t} dt_u a  (<= 0), as a column and as a row
    fcum_col = jnp.sum(jnp.where(tri, dt_row * a, 0.0), axis=1,
                       keepdims=True)                # (Q, 1)
    fcum_row = jnp.sum(jnp.where(t_idx <= u_idx, dt_col * a, 0.0), axis=0,
                       keepdims=True)                # (1, Q)
    ftot = jnp.sum(dt_row * a, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk: w[t,u] = (C_t.B_u) exp(F_t - F_u) dt_u, u <= t
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    w = jnp.where(tri, jnp.exp(fcum_col - fcum_row), 0.0) * cb * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q,HD)

    # inter-chunk contribution from the carried state
    h = h_scr[...]                                   # (HD, NS)
    y = y + jnp.exp(fcum_col) * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y = y + dsk * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h' = exp(F_Q) h + sum_u exp(F_Q - F_u) dt_u x_u (x) B_u
    decay_u = jnp.exp(ftot - fcum_col) * dt_col      # (Q, 1)
    delta = jax.lax.dot_general(x * decay_u, bm,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_scr[...] = jnp.exp(ftot) * h + delta

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hf_ref[0, 0] = h_scr[...].astype(hf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_chunk_scan(x, dt, a, b, c, d, *, chunk=256, h0=None,
                     interpret=False):
    """Matches kernels.ref.mamba_chunk_scan semantics.

    x: (B,S,NH,HD)  dt: (B,S,NH)  a,d: (NH,)  b,c: (B,S,NS)
    Returns (y (B,S,NH,HD), h_final (B,NH,HD,NS))."""
    bs, s, nh, hd = x.shape
    ns = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    if h0 is None:
        h0 = jnp.zeros((bs, nh, hd, ns), jnp.float32)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_ssd_kernel, q=q, n_chunks=nc)
    y, hf = pl.pallas_call(
        kernel,
        grid=(bs, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda bi, hi, ci: (bi, hi, 0, ci)),
            smem,
            pl.BlockSpec((1, q, ns), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, ns), lambda bi, hi, ci: (bi, ci, 0)),
            smem,
            pl.BlockSpec((1, 1, hd, ns), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, hd, ns), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, nh, s, hd), x.dtype),
            jax.ShapeDtypeStruct((bs, nh, hd, ns), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ns), jnp.float32)],
        interpret=interpret,
    )(x.swapaxes(1, 2), jnp.moveaxis(dt, 1, 2)[:, :, None, :],
      a.astype(jnp.float32), b, c, d.astype(jnp.float32), h0)
    return y.swapaxes(1, 2), hf
