"""Logical-axis sharding hints.

Model code calls ``hint(x, 'batch', 'seq', 'heads', None)`` at key points;
inside a ``logical_rules`` context each logical name maps to a mesh axis (or
tuple of axes, or None) and the hint becomes a
``jax.lax.with_sharding_constraint``.  Outside any context it is a no-op,
so single-device smoke tests and kernels never see mesh machinery.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


def current_rules():
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def logical_rules(mesh: Mesh, rules: dict[str, Any]):
    prev = getattr(_STATE, "rules", None)
    _STATE.rules = (mesh, dict(rules))
    try:
        yield
    finally:
        _STATE.rules = prev


def hint(x: jax.Array, *axes) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (None = replicated
    on that dim).  No-op outside a ``logical_rules`` context."""
    ctx = current_rules()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = []
    used: set = set()
    for a in axes:
        m = rules.get(a) if a is not None else None
        # drop axes already consumed by an earlier dim (GSPMD disallows reuse)
        if m is not None:
            flat = m if isinstance(m, tuple) else (m,)
            if any(f in used for f in flat):
                m = None
            else:
                used.update(flat)
        # divisibility guard
        if m is not None:
            flat = m if isinstance(m, tuple) else (m,)
            size = 1
            for f in flat:
                size *= mesh.shape[f]
            dim = len(spec)
            if x.shape[dim] % size != 0:
                m = None
        spec.append(m)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def make_rules(cfg, mesh: Mesh, batch: int) -> dict[str, Any]:
    """Default logical->mesh mapping for a model config on a mesh."""
    from repro.parallel.sharding import batch_axes  # lazy: avoid cycle
    tp = mesh.shape["model"] if "model" in mesh.axis_names else 1
    b_axes = batch_axes(mesh, batch)
    rules: dict[str, Any] = {
        "batch": b_axes if b_axes else None,
        "seq": None,
        "heads": "model" if cfg.num_heads % tp == 0 else None,
        "kv_heads": "model" if cfg.num_kv_heads % tp == 0 else None,
        # sequence-parallel attention fallback: when the head count does
        # not divide TP, shard the query sequence dim instead (bounds the
        # (B,H,S,T) score tensor; the hint's divisibility guard makes this
        # a no-op for decode's S=1)
        "attn_seq": "model" if cfg.num_heads % tp != 0 else None,
        "ffn": "model",
        "vocab": "model",
        "embed": None,
        # weight-side logical axes: hints on weights at their use sites act
        # as just-in-time FSDP all-gathers (wt_d strips the 'data' shard)
        "wt_d": None,
        "heads_out": "model" if cfg.num_heads % tp == 0 else None,
        "kv_out": "model" if cfg.num_kv_heads % tp == 0 else None,
    }
    if cfg.moe is not None:
        e = cfg.moe.num_experts
        dp = mesh.shape.get("data", 1)
        if e % (tp * dp) == 0 and tp * dp > 1:
            # EP over model and data jointly
            rules["experts"] = ("model", "data")
            rules["expert_ffn"] = None
            rules["moe_groups"] = None
        elif e % tp == 0 and tp > 1:
            # EP over model; expert weights FSDP-gathered over data at use
            rules["experts"] = "model"
            rules["expert_ffn"] = None
            rules["moe_groups"] = b_axes if b_axes else None
        else:
            rules["experts"] = None
            rules["expert_ffn"] = "model"
            rules["moe_groups"] = b_axes if b_axes else None
    if cfg.mamba is not None:
        d_inner = cfg.mamba.expand * cfg.d_model
        nh = d_inner // cfg.mamba.head_dim
        rules["mamba_heads"] = "model" if nh % tp == 0 else None
        rules["d_inner"] = "model" if d_inner % tp == 0 else None
    return rules
