"""Gated-linear-unit MLPs (SwiGLU / GeGLU)."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import ACTIVATIONS, dense_init, take_keys
from repro.models.config import ModelConfig
from repro.parallel.annotate import hint

Params = Any


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None,
             gated: bool | None = None) -> Params:
    dt = cfg.compute_dtype
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    gated = cfg.gated_mlp if gated is None else gated
    k1, k2, k3 = take_keys(key, 3)
    p = {
        "wi": dense_init(k1, d, (f,), dt),   # gate (or sole up) proj
        "wo": dense_init(k3, f, (d,), dt),   # down proj
    }
    if gated:
        p["wu"] = dense_init(k2, d, (f,), dt)  # up proj
    return p


def apply_mlp(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = ACTIVATIONS[cfg.activation]
    # weight hints = just-in-time FSDP gather (strip any 'data' shard) +
    # keep the TP dim explicit so GSPMD never replicates the F dim
    wo = hint(params["wo"], "ffn", "wt_d")
    wi = hint(params["wi"], "wt_d", "ffn")
    h = act(jnp.einsum("bsd,df->bsf", x, wi))
    if "wu" in params:
        h = h * jnp.einsum("bsd,df->bsf", x,
                           hint(params["wu"], "wt_d", "ffn"))
    h = hint(h, "batch", "seq", "ffn")
    return jnp.einsum("bsf,fd->bsd", h, wo)
