"""Attention variants: GQA (full / sliding-window / soft-capped), DeepSeek
MLA, and gated cross-attention (VLM image layers).

Three execution modes share one code path:
  * train:   full sequence, causal mask, no cache.
  * prefill: full sequence, causal mask, writes the KV cache.
  * decode:  q_len == 1 against a pre-filled cache at ``pos``.

Caches are plain dicts of arrays.  A group of layers keeps each slot's
caches stacked over its repeats, ``(R, B, T, ...)``, in the carry of its
layer scan (:func:`repro.models.blocks.apply_group`): GQA and MLA take that
stack and their layer's index, write their new rows into it in place and
read their layer's slice back, so a step moves only the rows it writes
and the cache its attention reads.  Cross-attention takes its layer's own
cache and returns it whole.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import common
from repro.parallel.annotate import hint
from repro.models.common import dense_init, rmsnorm, rmsnorm_init, take_keys
from repro.models.config import LayerSpec, ModelConfig

Params = Any


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    dt = cfg.compute_dtype
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = take_keys(key, 4)
    p = {
        "wq": dense_init(k1, d, (h * hd,), dt),
        "wk": dense_init(k2, d, (kv * hd,), dt),
        "wv": dense_init(k3, d, (kv * hd,), dt),
        "wo": dense_init(k4, h * hd, (d,), dt),
    }
    if spec.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dt)
        p["k_norm"] = rmsnorm_init(hd, dt)
    return p


def init_attn_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                    max_len: int, dtype) -> Params:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (batch, max_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attn_scale(cfg: ModelConfig) -> float:
    if cfg.attn_scale > 0:
        return cfg.attn_scale
    return 1.0 / math.sqrt(cfg.head_dim)


def apply_attn(params: Params, cfg: ModelConfig, spec: LayerSpec,
               x: jax.Array, positions: jax.Array,
               cache: Params | None = None,
               layer: jax.Array | int | None = None
               ) -> tuple[jax.Array, Params | None]:
    """x: (B, S, D); positions: (B, S) absolute positions.

    ``cache`` is the stack of the group's caches, ``(R, B, T, KV, hd)``
    leaves, of which this layer is index ``layer``; the stack is returned
    with this layer's rows written.  When it is given and S > 1 this is
    prefill (rows [0, S) written); when S == 1 it is a decode step at
    ``positions[:, 0]``.
    """
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    wq = hint(params["wq"], "wt_d", "heads_out")
    wk = hint(params["wk"], "wt_d", "kv_out")
    wv = hint(params["wv"], "wt_d", "kv_out")
    # the fence keeps attention's head-major layout out of the dots, so they
    # read the weights in the layout they are stored in, not a transposed copy
    q, k, v = jax.lax.optimization_barrier(
        tuple(jnp.einsum("bsd,dn->bsn", x, w) for w in (wq, wk, wv)))
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    q = hint(q, "batch", "attn_seq", "heads", None)
    k = hint(k, "batch", "seq", "kv_heads", None)
    v = hint(v, "batch", "seq", "kv_heads", None)
    if spec.qk_norm:
        q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, eps=cfg.norm_eps)
    q = common.apply_rope(q, positions, theta=cfg.rope_theta)
    k = common.apply_rope(k, positions, theta=cfg.rope_theta)

    scale = _attn_scale(cfg)
    softcap = cfg.attn_softcap or None
    window = spec.window or None

    if cache is None:
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  softcap=softcap, scale=scale)
        out = hint(out, "batch", "attn_seq", "heads", None)
        return out.reshape(b, s, h * hd) @ hint(params["wo"], "heads_out", "wt_d"), None

    if s > 1:  # prefill
        new_cache = {"k": _write_prefix(cache["k"], layer, k),
                     "v": _write_prefix(cache["v"], layer, v)}
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  softcap=softcap, scale=scale)
        out = hint(out, "batch", "attn_seq", "heads", None)
        return out.reshape(b, s, h * hd) @ hint(params["wo"], "heads_out", "wt_d"), new_cache

    # decode: write (k, v) at pos then attend to the whole cache with a
    # validity mask (<= pos, > pos - window).
    pos = positions[:, 0]  # (B,)
    new_cache = {"k": _write_rows(cache["k"], layer, k[:, 0], pos),
                 "v": _write_rows(cache["v"], layer, v[:, 0], pos)}
    out = ops.decode_attention(q, _layer_of(new_cache["k"], layer, k.dtype),
                               _layer_of(new_cache["v"], layer, v.dtype),
                               lengths=pos + 1, window=window,
                               softcap=softcap, scale=scale)
    out = hint(out, "batch", "attn_seq", "heads", None)
    return out.reshape(b, s, h * hd) @ hint(params["wo"], "heads_out", "wt_d"), new_cache


def _write_rows(stack: jax.Array, layer, val: jax.Array,
                pos: jax.Array) -> jax.Array:
    """stack: (R, B, T, ...), val: (B, ...), pos: (B,) -> stack with
    val[b] at [layer, b, pos[b]]."""
    b = val.shape[0]
    return stack.at[layer, jnp.arange(b), pos].set(val.astype(stack.dtype))


def _write_prefix(stack: jax.Array, layer, val: jax.Array) -> jax.Array:
    """stack: (R, B, T, ...), val: (B, S, ...) -> stack with val at
    [layer, :, :S]."""
    return jax.lax.dynamic_update_slice(
        stack, val[None].astype(stack.dtype), (layer,) + (0,) * val.ndim)


def _layer_of(stack: jax.Array, layer, dtype) -> jax.Array:
    """The layer's slice (B, T, ...) of a stacked cache, in ``dtype``."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0,
                                        keepdims=False).astype(dtype)


# ---------------------------------------------------------------------------
# DeepSeek-V3 Multi-head Latent Attention (MLA)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    dt = cfg.compute_dtype
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = m.nope_head_dim + m.rope_head_dim
    ks = take_keys(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, (m.q_lora_rank,), dt),
        "q_norm": rmsnorm_init(m.q_lora_rank, dt),
        "wq_b": dense_init(ks[1], m.q_lora_rank, (h * qd,), dt),
        "wkv_a": dense_init(ks[2], d, (m.kv_lora_rank + m.rope_head_dim,), dt),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dt),
        "wk_b": dense_init(ks[3], m.kv_lora_rank, (h * m.nope_head_dim,), dt),
        "wv_b": dense_init(ks[4], m.kv_lora_rank, (h * m.v_head_dim,), dt),
        "wo": dense_init(ks[5], h * m.v_head_dim, (d,), dt),
    }


def init_mla_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                   max_len: int, dtype) -> Params:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
    }


def _mla_attend_block(cfg: ModelConfig, q_nope, q_rope, ckv, krope,
                      wk_b, wv_b, mask, absorbed: bool) -> jax.Array:
    """One dense block of latent attention.

    q_nope: (B,S,H,dn)  q_rope: (B,S,H,dr)  ckv: (B,T,r)  krope: (B,T,dr)
    mask: broadcastable-to-(B,S,T) boolean (True = attend).

    ``absorbed``: beyond-paper optimization — fold wk_b/wv_b into the query /
    output side so the per-token work stays in latent space (no T x H x dn
    expansion).  Baseline expands K/V per head (DeepSeek's naive form).
    """
    m = cfg.mla
    h = cfg.num_heads
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if absorbed:
        wk = wk_b.reshape(m.kv_lora_rank, h, m.nope_head_dim)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk)
        scores = jnp.einsum("bshr,btr->bhst", q_lat, ckv)
        scores = scores + jnp.einsum("bshr,btr->bhst", q_rope, krope)
        scores = scores.astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
        ctx = jnp.einsum("bhst,btr->bshr", p, ckv)  # latent context
        wv = wv_b.reshape(m.kv_lora_rank, h, m.v_head_dim)
        return jnp.einsum("bshr,rhv->bshv", ctx, wv)
    k_nope = jnp.einsum("btr,rn->btn", ckv, wk_b).reshape(
        *ckv.shape[:2], h, m.nope_head_dim)
    value = jnp.einsum("btr,rn->btn", ckv, wv_b).reshape(
        *ckv.shape[:2], h, m.v_head_dim)
    scores = jnp.einsum("bshn,bthn->bhst", q_nope, k_nope)
    scores = scores + jnp.einsum("bshr,btr->bhst", q_rope, krope)
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(value.dtype)
    return jnp.einsum("bhst,bthv->bshv", p, value)


_MLA_BLOCK_THRESHOLD = 8192
_MLA_Q_BLOCK = 1024


def _mla_attend_causal(cfg: ModelConfig, q_nope, q_rope, ckv, krope,
                       wk_b, wv_b, absorbed: bool) -> jax.Array:
    """Causal latent attention; blocks over queries past the threshold so
    the (S,T) score tensor never materialises at 32k+ (see kernels/ref.py
    BLOCK_THRESHOLD rationale)."""
    s, t = q_nope.shape[1], ckv.shape[1]
    if s <= _MLA_BLOCK_THRESHOLD:
        mask = (jnp.arange(s)[:, None] >= jnp.arange(t)[None, :])[None]
        return _mla_attend_block(cfg, q_nope, q_rope, ckv, krope, wk_b,
                                 wv_b, mask, absorbed)
    assert s % _MLA_Q_BLOCK == 0
    outs = []
    for i in range(s // _MLA_Q_BLOCK):
        qs = i * _MLA_Q_BLOCK
        hi = min(t, qs + _MLA_Q_BLOCK)
        mask = ((jnp.arange(_MLA_Q_BLOCK)[:, None] + qs)
                >= jnp.arange(hi)[None, :])[None]
        outs.append(_mla_attend_block(
            cfg, q_nope[:, qs:qs + _MLA_Q_BLOCK],
            q_rope[:, qs:qs + _MLA_Q_BLOCK],
            ckv[:, :hi], krope[:, :hi], wk_b, wv_b, mask, absorbed))
    return jnp.concatenate(outs, axis=1)


def apply_mla(params: Params, cfg: ModelConfig, spec: LayerSpec,
              x: jax.Array, positions: jax.Array,
              cache: Params | None = None,
              layer: jax.Array | int | None = None, *,
              absorbed: bool = False) -> tuple[jax.Array, Params | None]:
    """As :func:`apply_attn`, with the latent cache (``ckv``, ``krope``)."""
    b, s, _ = x.shape
    m = cfg.mla
    h = cfg.num_heads
    q = jnp.einsum("bsd,dr->bsr", x, hint(params["wq_a"], "wt_d", None))
    q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)
    q = jnp.einsum("bsr,rn->bsn", q,
                   hint(params["wq_b"], None, "heads_out")).reshape(
        b, s, h, m.nope_head_dim + m.rope_head_dim)
    q = hint(q, "batch", "attn_seq", "heads", None)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = common.apply_rope(q_rope, positions, theta=cfg.rope_theta)

    kv = jnp.einsum("bsd,dr->bsr", x, hint(params["wkv_a"], "wt_d", None))
    ckv, krope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(params["kv_norm"], ckv, eps=cfg.norm_eps)
    krope = common.apply_rope(krope[:, :, None], positions,
                              theta=cfg.rope_theta)[:, :, 0]

    if cache is None:
        out = _mla_attend_causal(cfg, q_nope, q_rope, ckv, krope,
                                 hint(params["wk_b"], None, "heads_out"), hint(params["wv_b"], None, "heads_out"), absorbed)
        return out.reshape(b, s, -1) @ hint(params["wo"], "heads_out", "wt_d"), None

    if s > 1:  # prefill
        new_cache = {"ckv": _write_prefix(cache["ckv"], layer, ckv),
                     "krope": _write_prefix(cache["krope"], layer, krope)}
        out = _mla_attend_causal(cfg, q_nope, q_rope, ckv, krope,
                                 hint(params["wk_b"], None, "heads_out"), hint(params["wv_b"], None, "heads_out"), absorbed)
        return out.reshape(b, s, -1) @ hint(params["wo"], "heads_out", "wt_d"), new_cache

    pos = positions[:, 0]
    new_cache = {
        "ckv": _write_rows(cache["ckv"], layer, ckv[:, 0], pos),
        "krope": _write_rows(cache["krope"], layer, krope[:, 0], pos),
    }
    t = new_cache["ckv"].shape[2]
    mask = jnp.arange(t)[None, None, :] <= pos[:, None, None]  # (B,1,T)
    out = _mla_attend_block(cfg, q_nope, q_rope,
                            _layer_of(new_cache["ckv"], layer, ckv.dtype),
                            _layer_of(new_cache["krope"], layer, krope.dtype),
                            params["wk_b"], params["wv_b"], mask, absorbed)
    return out.reshape(b, s, -1) @ hint(params["wo"], "heads_out", "wt_d"), new_cache


# ---------------------------------------------------------------------------
# Gated cross-attention (VLM image layers; frontend is a stub per spec)
# ---------------------------------------------------------------------------

def init_cross_attn(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    dt = cfg.compute_dtype
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = take_keys(key, 5)
    return {
        "wq": dense_init(ks[0], d, (h * hd,), dt),
        "wk": dense_init(ks[1], cfg.vision_dim, (kv * hd,), dt),
        "wv": dense_init(ks[2], cfg.vision_dim, (kv * hd,), dt),
        "wo": dense_init(ks[3], h * hd, (d,), dt),
        "gate": jnp.zeros((), dt),
        "q_norm": rmsnorm_init(hd, dt),
        "k_norm": rmsnorm_init(hd, dt),
    }


def init_cross_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype) -> Params:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (batch, cfg.num_image_tokens, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "filled": jnp.zeros((), jnp.int32)}


def apply_cross_attn(params: Params, cfg: ModelConfig, spec: LayerSpec,
                     x: jax.Array, image_embeds: jax.Array | None,
                     cache: Params | None = None
                     ) -> tuple[jax.Array, Params | None]:
    """x: (B,S,D); image_embeds: (B, N_img, vision_dim) or None in decode
    (then K/V come from the cache filled at prefill)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dn->bsn",
                   x, hint(params["wq"], "wt_d", "heads_out")
                   ).reshape(b, s, h, hd)
    q = hint(q, "batch", "attn_seq", "heads", None)
    q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)

    if image_embeds is not None:
        k = jnp.einsum("bnd,dm->bnm", image_embeds,
                       hint(params["wk"], "wt_d", "kv_out")).reshape(
            b, -1, kv, hd)
        k = rmsnorm(params["k_norm"], k, eps=cfg.norm_eps)
        v = jnp.einsum("bnd,dm->bnm", image_embeds,
                       hint(params["wv"], "wt_d", "kv_out")).reshape(
            b, -1, kv, hd)
        new_cache = None
        if cache is not None:
            new_cache = {"k": k.astype(cache["k"].dtype),
                         "v": v.astype(cache["v"].dtype),
                         "filled": jnp.ones((), jnp.int32)}
    else:
        assert cache is not None, "decode cross-attn needs a filled cache"
        k, v = cache["k"], cache["v"]
        new_cache = cache

    out = ops.flash_attention(q, k, v, causal=False, window=None,
                              softcap=None, scale=1.0 / math.sqrt(hd))
    out = hint(out, "batch", "attn_seq", "heads", None)
    out = out.reshape(b, s, h * hd) @ hint(params["wo"], "heads_out", "wt_d")
    gate = jnp.tanh(params["gate"].astype(jnp.float32)).astype(out.dtype)
    return out * gate, new_cache
