"""Top-level LM: embeddings -> layer groups -> final norm -> head(s).

Exposes the three execution paths the shape cells exercise:
  * ``forward``      - training forward (full sequence, no cache)
  * ``prefill``      - fill caches for a prompt, return last-token logits
  * ``decode_step``  - one token against the cache

MusicGen-style multi-codebook streams (tokens (B,S,K)) and VLM image-embed
stubs (``image_embeds`` forwarded to cross-attention layers) are handled
here so every assigned arch shares one code path.

The embedding, the layer stack, the final norm and the head run under
``jax.named_scope`` names (``embed``, ``layers``, ``final_norm``,
``head``), as each layer's parts do (``blocks.apply_layer``), so a device
trace can be read by model part.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.common import soft_cap, take_keys, rmsnorm, rmsnorm_init
from repro.models.common import embed_init, dense_init
from repro.models.config import ModelConfig
from repro.parallel.annotate import hint

Params = Any


def init_params(key, cfg: ModelConfig) -> Params:
    dt = cfg.compute_dtype
    keys = take_keys(key, len(cfg.groups) + 2)
    if cfg.num_codebooks:
        ek = jax.random.split(keys[0], cfg.num_codebooks)
        embed = jax.vmap(
            lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt))(ek)
    else:
        embed = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)
    p = {
        "embed": embed,
        "groups": [blocks.init_group(k, cfg, g)
                   for g, k in zip(cfg.groups, keys[1:-1])],
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            hk = jax.random.split(keys[-1], cfg.num_codebooks)
            p["head"] = jax.vmap(
                lambda k: dense_init(k, cfg.d_model, (cfg.vocab_size,), dt)
            )(hk)
        else:
            p["head"] = dense_init(keys[-1], cfg.d_model, (cfg.vocab_size,),
                                   dt)
    return p


def abstract_params(cfg: ModelConfig) -> Params:
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(functools.partial(init_params, cfg=cfg), key)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Params:
    dtype = dtype or cfg.compute_dtype
    return [blocks.init_group_cache(cfg, g, batch, max_len, dtype)
            for g in cfg.groups]


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_len, dtype))


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        if cfg.num_codebooks:
            # tokens: (B, S, K) -> sum of per-codebook embeddings
            embs = jax.vmap(lambda e, t: jnp.take(e, t, axis=0))(
                params["embed"], jnp.moveaxis(tokens, -1, 0))  # (K,B,S,D)
            x = jnp.sum(embs, axis=0)
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return hint(x, "batch", "seq", "embed")


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("head"):
        if cfg.num_codebooks:
            w = params.get("head", params["embed"])  # (K,V,D) if tied
            if "head" in params:
                logits = jnp.einsum("bsd,kdv->bskv", x, w)
            else:
                logits = jnp.einsum("bsd,kvd->bskv", x, w)
        else:
            if "head" in params:
                logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
            else:
                logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        axes = (("batch", "seq", None, "vocab") if cfg.num_codebooks
                else ("batch", "seq", "vocab"))
        logits = hint(logits, *axes)
        return soft_cap(logits, cfg.final_softcap or None)


def _run(params: Params, cfg: ModelConfig, x: jax.Array, ctx: dict,
         caches: list | None):
    aux = dict(blocks.ZERO_AUX)
    new_caches = [] if caches is not None else None
    # the layer loop's own work (slicing each layer's stacked weights, and
    # the slices and writes of the caches that a mixer rewrites whole)
    # falls under ``layers`` alone; attention's rows are written in place
    # under its own scope (blocks.apply_group)
    with jax.named_scope("layers"):
        for gi, gspec in enumerate(cfg.groups):
            c = None if caches is None else caches[gi]
            x, nc, ga = blocks.apply_group(params["groups"][gi], cfg, gspec,
                                           x, ctx, c)
            if new_caches is not None:
                new_caches.append(nc)
            aux = {k: aux[k] + ga[k] for k in aux}
    with jax.named_scope("final_norm"):
        x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    return x, new_caches, aux


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            image_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, dict]:
    """Training forward. tokens: (B,S) or (B,S,K). Returns (logits, aux)."""
    b, s = tokens.shape[:2]
    x = _embed(params, cfg, tokens)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
           "image_embeds": image_embeds}
    x, _, aux = _run(params, cfg, x, ctx, None)
    return _head(params, cfg, x), aux


def forward_loss(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 labels: jax.Array, image_embeds: jax.Array | None = None
                 ) -> tuple[jax.Array, dict]:
    """Training forward + token cross-entropy, sharding-friendly.

    The gold logit is computed by gathering the label's head row and dotting
    with the hidden state — O(B*S*D) — instead of take_along_axis over the
    vocab-sharded (B,S,V) logits (which would force GSPMD to replicate
    them).  Only the logsumexp reduction touches the full logits tensor.
    """
    b, s = tokens.shape[:2]
    x = _embed(params, cfg, tokens)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
           "image_embeds": image_embeds}
    x, _, aux = _run(params, cfg, x, ctx, None)

    logits = _head(params, cfg, x)  # (B,S,V) or (B,S,K,V)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)

    if cfg.num_codebooks:
        w = params.get("head")
        wv = (jnp.swapaxes(w, 1, 2) if w is not None
              else params["embed"])                        # (K,V,D)
        rows = jax.vmap(lambda e, t: jnp.take(e, t, axis=0),
                        in_axes=(0, 2))(wv, labels)        # (K,B,S,D)
        gold = jnp.einsum("bsd,kbsd->bsk", x.astype(jnp.float32),
                          rows.astype(jnp.float32))
    else:
        w = params.get("head")
        wv = jnp.swapaxes(w, 0, 1) if w is not None else params["embed"]
        rows = jnp.take(wv, labels, axis=0)                # (B,S,D)
        gold = jnp.sum(x.astype(jnp.float32)
                       * rows.astype(jnp.float32), axis=-1)
    if cfg.final_softcap:
        gold = soft_cap(gold, cfg.final_softcap)
    loss = jnp.mean(lse - gold)
    return loss, aux


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            cache: list, image_embeds: jax.Array | None = None,
            mla_absorbed: bool = False) -> tuple[jax.Array, list]:
    """Fill the cache with a prompt; returns (last-token logits, cache)."""
    b, s = tokens.shape[:2]
    x = _embed(params, cfg, tokens)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
           "image_embeds": image_embeds, "mla_absorbed": mla_absorbed}
    x, new_caches, _ = _run(params, cfg, x, ctx, cache)
    return _head(params, cfg, x[:, -1:]), new_caches


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: list, pos: jax.Array,
                mla_absorbed: bool = False) -> tuple[jax.Array, list]:
    """tokens: (B,1) or (B,1,K); pos: (B,) absolute position of the token."""
    b = tokens.shape[0]
    x = _embed(params, cfg, tokens)
    ctx = {"positions": pos[:, None], "image_embeds": None,
           "mla_absorbed": mla_absorbed}
    x, new_caches, _ = _run(params, cfg, x, ctx, cache)
    return _head(params, cfg, x), new_caches
