"""The ``stack`` family: one repeating pattern of attention (RoPE) or
single-group Mamba-2 mixers, each with a GLU MLP or none, as the program's
``GroupSpec`` runs it.

A configuration file without a ``"family"`` key is of this family.  This
module turns it into the program's ``ModelConfig`` and makes the weights,
in the program's parameter layout, from a key.  The weights are the
benchmark's own: the family's plain reference (``reference.py`` beside this
file) reads the same arrays by the same names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# configuration -> the program's ModelConfig
# ---------------------------------------------------------------------------

def repeat_count(conf: dict) -> int:
    n, p = conf["num_hidden_layers"], len(conf["block_pattern"])
    if n % p:
        raise ValueError(f"{n} layers is not a whole number of "
                         f"{p}-layer periods")
    return n // p


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import (GroupSpec, LayerSpec, MambaConfig,
                                     ModelConfig)
    pattern = tuple(LayerSpec(kind=b["kind"], mlp=b["mlp"],
                              shared=b.get("shared", False))
                    for b in conf["block_pattern"])
    mamba = None
    if any(b["kind"] == "mamba2" for b in conf["block_pattern"]):
        mamba = MambaConfig(d_state=conf["mamba_d_state"],
                            d_conv=conf["mamba_d_conv"],
                            expand=conf["mamba_expand"],
                            head_dim=conf["mamba_headdim"],
                            chunk=conf["chunk_size"])
    return ModelConfig(
        name=conf["name"],
        groups=(GroupSpec(pattern=pattern, repeat=repeat_count(conf)),),
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"],
        mamba=mamba,
        tie_embeddings=conf["tie_word_embeddings"],
        activation=conf["hidden_act"],
        norm_eps=conf["rms_norm_eps"],
        dtype=conf["torch_dtype"],
        remat="none",
    )


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------

def _normal(key, shape, std, dtype):
    if len(shape) > 2:
        # one layer at a time, so that the random bits of only one layer's
        # matrix are held at once
        return jax.lax.map(lambda k: _normal(k, shape[1:], std, dtype),
                           jax.random.split(key, shape[0]))
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def _norm(key, lead, dim, dtype):
    # zero-centred RMSNorm weight: the program applies (1 + scale)
    return {"scale": _normal(key, (*lead, dim), 0.1, dtype)}


def _attn(key, conf, lead, dtype):
    d, h, kv, hd = (conf["hidden_size"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    k = jax.random.split(key, 4)
    return {"wq": _normal(k[0], (*lead, d, h * hd), d ** -0.5, dtype),
            "wk": _normal(k[1], (*lead, d, kv * hd), d ** -0.5, dtype),
            "wv": _normal(k[2], (*lead, d, kv * hd), d ** -0.5, dtype),
            "wo": _normal(k[3], (*lead, h * hd, d), (h * hd) ** -0.5, dtype)}


def _glu(key, conf, lead, dtype):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    k = jax.random.split(key, 3)
    return {"wi": _normal(k[0], (*lead, d, f), d ** -0.5, dtype),
            "wu": _normal(k[1], (*lead, d, f), d ** -0.5, dtype),
            "wo": _normal(k[2], (*lead, f, d), f ** -0.5, dtype)}


def mamba_dims(conf: dict) -> tuple[int, int, int, int]:
    d_inner = conf["mamba_expand"] * conf["hidden_size"]
    return (d_inner, d_inner // conf["mamba_headdim"], conf["mamba_d_state"],
            conf["mamba_d_conv"])


def _mamba(key, conf, lead, dtype):
    d = conf["hidden_size"]
    d_inner, nh, ns, kc = mamba_dims(conf)
    conv_dim = d_inner + 2 * ns
    k = jax.random.split(key, 7)
    bound = kc ** -0.5
    # Mamba-2's initial values: A = -[1, 16], dt log-uniform, D = 1
    a = jax.random.uniform(k[3], (*lead, nh), jnp.float32, 1.0, 16.0)
    lo, hi = math.log(conf["time_step_min"]), math.log(conf["time_step_max"])
    dt = jnp.exp(jax.random.uniform(k[4], (*lead, nh), jnp.float32, lo, hi))
    dt = jnp.maximum(dt, conf["time_step_floor"])
    return {
        "in_proj": _normal(k[0], (*lead, d, 2 * d_inner + 2 * ns + nh),
                           d ** -0.5, dtype),
        "conv_w": jax.random.uniform(k[1], (*lead, kc, conv_dim), dtype,
                                     -bound, bound),
        "conv_b": jax.random.uniform(k[2], (*lead, conv_dim), dtype,
                                     -bound, bound),
        "a_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "d_skip": jnp.ones((*lead, nh), jnp.float32),
        "norm": _norm(k[5], lead, d_inner, dtype),
        "out_proj": _normal(k[6], (*lead, d_inner, d), d_inner ** -0.5,
                            dtype),
    }


_MIXERS = {"attn": _attn, "mamba2": _mamba}


def _layer(key, conf, block, lead, dtype):
    d = conf["hidden_size"]
    k = jax.random.split(key, 4)
    p = {"pre_norm": _norm(k[0], lead, d, dtype),
         "mixer": _MIXERS[block["kind"]](k[1], conf, lead, dtype)}
    if block["mlp"] == "glu":
        p["pre_mlp_norm"] = _norm(k[2], lead, d, dtype)
        p["mlp"] = _glu(k[3], conf, lead, dtype)
    return p


def make_weights(key, conf: dict) -> dict:
    """All weights of ``conf`` in the program's layout: scanned slots carry
    a leading axis over the period's repeats, shared slots do not."""
    dtype = jnp.dtype(conf["torch_dtype"])
    d, v = conf["hidden_size"], conf["vocab_size"]
    reps = repeat_count(conf)
    pattern = conf["block_pattern"]
    k = jax.random.split(key, len(pattern) + 3)
    slots = tuple(
        _layer(k[3 + i], conf, b, () if b.get("shared") else (reps,), dtype)
        for i, b in enumerate(pattern))
    w = {"embed": _normal(k[0], (v, d), 0.02, dtype),
         "groups": [{"slots": slots}],
         "final_norm": _norm(k[1], (), d, dtype)}
    if not conf["tie_word_embeddings"]:
        w["head"] = _normal(k[2], (d, v), d ** -0.5, dtype)
    return w
