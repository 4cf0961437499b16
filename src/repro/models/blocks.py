"""Layer composition and the scan-over-layers group machinery.

One *layer* = (pre-norm -> mixer block -> residual) + optional
(pre-norm -> MLP/MoE -> residual), with gemma2-style post-norms when
``spec.post_norms``; the two halves run under ``jax.named_scope`` names,
the mixer's kind (``attn``, ``mamba2``, ...) and ``mlp`` or ``moe``.  A
*group* scans a repeating pattern of layers with stacked parameters; weight-shared slots (zamba2's shared attention) are
closed over instead of scanned.  With a cache, each slot's caches stay
stacked over the repeats in the scan's carry, and each layer updates its
index of the stack in place.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, mamba2, xlstm
from repro.models.common import rmsnorm, rmsnorm_init, take_keys
from repro.models.config import GroupSpec, LayerSpec, ModelConfig
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.moe import apply_moe, init_moe

Params = Any

_MIXER_INIT = {
    "attn": attention.init_attn,
    "mla": attention.init_mla,
    "cross_attn": attention.init_cross_attn,
    "mamba2": mamba2.init_mamba2,
    "mlstm": xlstm.init_mlstm,
    "slstm": xlstm.init_slstm,
}

_CACHE_INIT = {
    "attn": attention.init_attn_cache,
    "mla": attention.init_mla_cache,
    "cross_attn": attention.init_cross_cache,
    "mamba2": mamba2.init_mamba_cache,
    "mlstm": xlstm.init_mlstm_cache,
    "slstm": xlstm.init_slstm_cache,
}

#: mixers whose cache has a time axis: they write only their new rows into
#: the stack of the group's caches, in place (``attention._write_rows``).
#: The others rewrite their whole state each step and return it, and
#: :func:`apply_layer` writes it into the stack.
_ROWS_IN_PLACE = frozenset({"attn", "mla"})

ZERO_AUX = {"moe_aux_loss": jnp.zeros((), jnp.float32),
            "moe_dropped": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    k1, k2 = take_keys(key, 2)
    dt = cfg.compute_dtype
    p: dict = {}
    if spec.kind != "none":
        p["pre_norm"] = rmsnorm_init(cfg.d_model, dt)
        p["mixer"] = _MIXER_INIT[spec.kind](k1, cfg, spec)
        if spec.post_norms:
            p["post_norm"] = rmsnorm_init(cfg.d_model, dt)
    if spec.mlp != "none":
        p["pre_mlp_norm"] = rmsnorm_init(cfg.d_model, dt)
        p["mlp"] = (init_moe(k2, cfg) if spec.mlp == "moe"
                    else init_mlp(k2, cfg))
        if spec.post_norms:
            p["post_mlp_norm"] = rmsnorm_init(cfg.d_model, dt)
    return p


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype) -> Params:
    if spec.kind == "none":
        return {}
    return _CACHE_INIT[spec.kind](cfg, spec, batch, max_len, dtype)


def apply_layer(params: Params, cfg: ModelConfig, spec: LayerSpec,
                x: jax.Array, ctx: dict, cache: Params | None,
                layer: jax.Array | int
                ) -> tuple[jax.Array, Params | None, dict]:
    """One layer.  ``cache`` is the stack of its slot's caches over the
    group's repeats, of which this layer is index ``layer``; the stack is
    returned updated there."""
    aux = dict(ZERO_AUX)
    stack = cache
    if cache is not None and spec.kind not in _ROWS_IN_PLACE:
        cache = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), stack)
    if spec.kind != "none":
        with jax.named_scope(spec.kind):
            h = rmsnorm(params["pre_norm"], x, eps=cfg.norm_eps)
            if spec.kind == "attn":
                h, new_cache = attention.apply_attn(
                    params["mixer"], cfg, spec, h, ctx["positions"], cache,
                    layer)
            elif spec.kind == "mla":
                h, new_cache = attention.apply_mla(
                    params["mixer"], cfg, spec, h, ctx["positions"], cache,
                    layer, absorbed=ctx.get("mla_absorbed", False))
            elif spec.kind == "cross_attn":
                h, new_cache = attention.apply_cross_attn(
                    params["mixer"], cfg, spec, h, ctx.get("image_embeds"),
                    cache)
            elif spec.kind == "mamba2":
                h, new_cache = mamba2.apply_mamba2(params["mixer"], cfg,
                                                   spec, h, cache)
            elif spec.kind == "mlstm":
                h, new_cache = xlstm.apply_mlstm(params["mixer"], cfg, spec,
                                                 h, cache)
            elif spec.kind == "slstm":
                h, new_cache = xlstm.apply_slstm(params["mixer"], cfg, spec,
                                                 h, cache)
            else:  # pragma: no cover
                raise ValueError(spec.kind)
            if spec.post_norms:
                h = rmsnorm(params["post_norm"], h, eps=cfg.norm_eps)
            x = x + h
        if cache is not None and spec.kind not in _ROWS_IN_PLACE:
            new_cache = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), layer, 0), stack, new_cache)
    else:
        new_cache = stack

    if spec.mlp != "none":
        with jax.named_scope("moe" if spec.mlp == "moe" else "mlp"):
            h = rmsnorm(params["pre_mlp_norm"], x, eps=cfg.norm_eps)
            if spec.mlp == "moe":
                h, moe_aux = apply_moe(params["mlp"], cfg, h)
                aux = {k: moe_aux[k].astype(jnp.float32) for k in aux}
            else:
                h = apply_mlp(params["mlp"], cfg, h)
            if spec.post_norms:
                h = rmsnorm(params["post_mlp_norm"], h, eps=cfg.norm_eps)
            x = x + h
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Groups (scan over repeats)
# ---------------------------------------------------------------------------

def init_group(key, cfg: ModelConfig, gspec: GroupSpec) -> Params:
    slot_params = []
    keys = take_keys(key, len(gspec.pattern))
    for spec, k in zip(gspec.pattern, keys):
        if spec.shared:
            slot_params.append(init_layer(k, cfg, spec))
        else:
            ks = jax.random.split(k, gspec.repeat)
            slot_params.append(
                jax.vmap(lambda kk: init_layer(kk, cfg, spec))(ks))
    return {"slots": tuple(slot_params)}


def init_group_cache(cfg: ModelConfig, gspec: GroupSpec, batch: int,
                     max_len: int, dtype) -> Params:
    slots = []
    for spec in gspec.pattern:
        one = init_layer_cache(cfg, spec, batch, max_len, dtype)
        slots.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (gspec.repeat, *a.shape)).copy()
            if hasattr(a, "shape") else a, one))
    return {"slots": tuple(slots)}


def apply_group(params: Params, cfg: ModelConfig, gspec: GroupSpec,
                x: jax.Array, ctx: dict, cache: Params | None
                ) -> tuple[jax.Array, Params | None, dict]:
    """The group's layers, repeat by repeat.  The caches are carried, not
    scanned: each slot's stack ``(R, ...)`` passes through every repeat,
    which updates its own index, so the stacks are updated in place and no
    layer's cache is copied out of them or back."""
    pattern = gspec.pattern
    scanned_params = tuple(p for spec, p in zip(pattern, params["slots"])
                           if not spec.shared)
    shared_params = tuple(p for spec, p in zip(pattern, params["slots"])
                          if spec.shared)

    def body(carry, per_repeat):
        xc, aux_acc, caches = carry
        sl_params, r = per_repeat
        it_sc, it_sh = iter(sl_params), iter(shared_params)
        new_caches = []
        for i, spec in enumerate(pattern):
            p = next(it_sh) if spec.shared else next(it_sc)
            c = caches[i] if (caches is not None and caches[i]) else None
            xc, nc, aux = apply_layer(p, cfg, spec, xc, ctx, c, r)
            new_caches.append({} if nc is None else nc)
            aux_acc = {k: aux_acc[k] + aux[k] for k in aux_acc}
        if caches is not None:
            caches = tuple(new_caches)
        return (xc, aux_acc, caches), None

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.checkpoint_dots
                  if cfg.remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    carry = (x, dict(ZERO_AUX), None if cache is None else cache["slots"])
    carry, _ = jax.lax.scan(body, carry,
                            (scanned_params, jnp.arange(gspec.repeat)))
    x, aux, slots = carry
    return x, None if cache is None else {"slots": slots}, aux
