"""The plain reference of the ``stack`` family: the configuration's forward
pass in float32.

Straightforward ``jax.numpy`` over the benchmark's own weights
(``program.py`` beside this file makes them; they are read here by name),
with no cache, no batching of requests into slots and no kernels.  It
imports nothing of the program.
Matrix products run at ``highest`` precision, so a float32 product is
float32 on the TPU too.

It runs once the measured window has closed, one layer at a time, so that
only one layer's weights are held in float32 at once.

``quant="fp8"`` is the control: the same forward pass with the inputs of
every weight matrix product rounded to float8 (e4m3, a scale per row of
activations and per output column of weights).  It is what a lower
precision than the configuration's bfloat16 would give, and the output
check must tell it apart from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256          # query rows per attention block
HEAD_ROWS = 1024       # positions per block of the output head


def _fp8(a, axis):
    """Round ``a`` to float8 e4m3 with one scale along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    # clipped, so that rounding of the division never leaves e4m3's range
    q = jnp.clip(a / scale, -448.0, 448.0).astype(jnp.float8_e4m3fn)
    return q.astype(F32) * scale


def _mm(x, w, quant):
    """x: (..., k) activations, w: (k, n) weights."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(scale, x, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(F32))


def _act(name, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu":     # the tanh form, as the configuration states
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    raise ValueError(name)


def _rope(x, theta):
    """x: (B, T, heads, hd); rotate-half rotary embedding at 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]   # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, conf, quant):
    b, t, _ = x.shape
    h, kv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    q = _rope(_mm(x, p["wq"], quant).reshape(b, t, h, hd), conf["rope_theta"])
    k = _rope(_mm(x, p["wk"], quant).reshape(b, t, kv, hd),
              conf["rope_theta"])
    v = _mm(x, p["wv"], quant).reshape(b, t, kv, hd)
    # query head i reads key/value head i // (h // kv)
    q = q.reshape(b, t, kv, h // kv, hd)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k,
                       precision="highest") / math.sqrt(hd)
        causal = (jnp.arange(lo, lo + qb.shape[1])[:, None]
                  >= jnp.arange(t)[None, :])
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v,
                               precision="highest"))
    o = jnp.concatenate(outs, axis=1).reshape(b, t, h * hd)
    return _mm(o, p["wo"], quant)


def _mamba2(p, x, conf, quant):
    b, t, _ = x.shape
    d_inner = conf["mamba_expand"] * conf["hidden_size"]
    hd, ns, kc = conf["mamba_headdim"], conf["mamba_d_state"], \
        conf["mamba_d_conv"]
    nh = d_inner // hd
    proj = _mm(x, p["in_proj"], quant)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * ns]
    dt = jax.nn.softplus(proj[..., 2 * d_inner + 2 * ns:]
                         + p["dt_bias"].astype(F32))              # (B,T,nh)
    # causal depthwise convolution over time, then SiLU
    w = p["conv_w"].astype(F32)                                   # (kc, C)
    xp = jnp.pad(xbc, [(0, 0), (kc - 1, 0), (0, 0)])
    conv = sum(xp[:, i:i + t] * w[i] for i in range(kc))
    conv = conv + p["conv_b"].astype(F32)
    conv = conv * jax.nn.sigmoid(conv)
    xs = conv[..., :d_inner].reshape(b, t, nh, hd)
    bm = conv[..., d_inner:d_inner + ns]
    cm = conv[..., d_inner + ns:]
    a = -jnp.exp(p["a_log"].astype(F32))                          # (nh,)

    # h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * a)[:, :, None, None]
             + (dtt[:, :, None, None] * xt[..., None] * bt[:, None, None, :]))
        return h, jnp.einsum("bhdn,bn->bhd", h, ct, precision="highest")

    h0 = jnp.zeros((b, nh, hd, ns), F32)
    _, ys = jax.lax.scan(step, h0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                    bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + p["d_skip"].astype(F32)[:, None] * xs
    y = y.reshape(b, t, d_inner) * (z * jax.nn.sigmoid(z))
    y = _rmsnorm(p["norm"]["scale"], y, conf["rms_norm_eps"])
    return _mm(y, p["out_proj"], quant)


def _glu(p, x, conf, quant):
    g = _act(conf["hidden_act"], _mm(x, p["wi"], quant))
    return _mm(g * _mm(x, p["wu"], quant), p["wo"], quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(p, x, conf, kind, quant):
    eps = conf["rms_norm_eps"]
    mixer = _attention if kind["kind"] == "attn" else _mamba2
    x = x + mixer(p["mixer"], _rmsnorm(p["pre_norm"]["scale"], x, eps),
                  conf, quant)
    if kind["mlp"] == "glu":
        x = x + _glu(p["mlp"], _rmsnorm(p["pre_mlp_norm"]["scale"], x, eps),
                     conf, quant)
    return x


class _Static(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(conf):
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "mamba_expand", "hidden_size", "mamba_headdim",
            "mamba_d_state", "mamba_d_conv", "hidden_act", "rms_norm_eps")
    return _Static({k: conf[k] for k in keep if k in conf})


def hidden(w, conf, tokens, quant=None):
    """Final normed hidden states (B, T, D) in float32 for ``tokens``."""
    sconf = _static(conf)
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    reps = conf["num_hidden_layers"] // len(conf["block_pattern"])
    slots = w["groups"][0]["slots"]
    for r in range(reps):
        for block, sp in zip(conf["block_pattern"], slots):
            p = sp if block.get("shared") else jax.tree.map(
                lambda a: a[r], sp)
            x = _layer(p, x, sconf, _Static(block), quant)
    return _rmsnorm(w["final_norm"]["scale"], x, conf["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_block(head, x, targets, tied, quant):
    wt = head.T if tied else head
    logits = _mm(x, wt, quant)                                   # (N, V)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def head_stats(w, conf, x, targets, quant=None):
    """For each position: the best logit, the logit of ``targets`` there
    and the top token.  x: (B, T, D) float32, targets: (B, T)."""
    b, t, d = x.shape
    tied = conf["tie_word_embeddings"]
    head = w["embed"] if tied else w["head"]
    xs, ts = x.reshape(b * t, d), targets.reshape(b * t)
    outs = [_head_block(head, xs[i:i + HEAD_ROWS], ts[i:i + HEAD_ROWS],
                        tied, quant)
            for i in range(0, b * t, HEAD_ROWS)]
    return tuple(jnp.concatenate(o).reshape(b, t) for o in zip(*outs))
