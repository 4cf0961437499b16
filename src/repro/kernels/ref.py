"""Pure-jnp reference oracles for every kernel in :mod:`repro.kernels`.

These are the semantics contract: Pallas kernels must match these within
tolerance (tests sweep shapes/dtypes against them), and on non-TPU backends
the ops layer executes these directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(sq: int, st: int, *, causal: bool, window: int | None,
          q_pos0: int = 0, kv_pos0: int = 0) -> jax.Array:
    """(sq, st) boolean attend-mask with absolute position offsets."""
    qi = jnp.arange(sq)[:, None] + q_pos0
    ti = jnp.arange(st)[None, :] + kv_pos0
    m = jnp.ones((sq, st), bool)
    if causal:
        m &= qi >= ti
    if window is not None and window > 0:
        m &= qi - ti < window
    return m


def _expand_kv(k: jax.Array, h: int) -> jax.Array:
    """(B,T,KV,hd) -> (B,T,H,hd) for the prefill path (``_attend_dense``),
    whose cost is its score matrix, not this copy.  Broadcast-expand keeps
    the head dim a real tensor dim so GSPMD can shard it even when
    KV < TP degree.  Decode does not call it."""
    kv = k.shape[2]
    if kv == h:
        return k
    return jnp.repeat(k, h // kv, axis=2)


def _attend_dense(q, k, v, *, causal, window, softcap, scale,
                  q_pos0=0, kv_pos0=0):
    b, s, h, hd = q.shape
    t = k.shape[1]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    m = _mask(s, t, causal=causal, window=window, q_pos0=q_pos0,
              kv_pos0=kv_pos0)
    scores = jnp.where(m[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", p, v)
    return out


# Above this query length, attention runs as an unrolled loop over query
# blocks with the K/V range sliced to the causal/window support of each
# block.  Bounds transient score memory to O(B*H*QB*T_blk).
BLOCK_THRESHOLD = 8192
Q_BLOCK = 1024


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float = 1.0,
                    q_offset: int = 0) -> jax.Array:
    """Grouped-query attention. q: (B,S,H,hd); k,v: (B,T,KV,hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    if s <= BLOCK_THRESHOLD:
        return _attend_dense(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, q_pos0=q_offset)
    assert s % Q_BLOCK == 0, (s, Q_BLOCK)
    outs = []
    for i in range(s // Q_BLOCK):
        qs = i * Q_BLOCK
        lo = 0
        hi = t
        if causal:
            hi = min(t, q_offset + qs + Q_BLOCK)
        if window is not None and window > 0:
            lo = max(0, q_offset + qs - window + 1)
        outs.append(_attend_dense(
            q[:, qs:qs + Q_BLOCK], k[:, lo:hi], v[:, lo:hi],
            causal=causal, window=window, softcap=softcap, scale=scale,
            q_pos0=q_offset + qs, kv_pos0=lo))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     lengths: jax.Array, window: int | None = None,
                     softcap: float | None = None,
                     scale: float = 1.0) -> jax.Array:
    """Single-token decode. q: (B,1,H,hd); k,v: (B,T,KV,hd); lengths: (B,).

    Grouped-query: q is viewed as (B, KV, G, hd) with G = H // KV, and each
    group of G query heads attends its own KV head in place (head h reads
    KV head h // G, the mapping ``_expand_kv`` gives).  The cache is read
    as it stands; no copy of it with H heads is made.
    """
    b, _, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, kv, h // kv, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32) * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    ti = jnp.arange(t)[None, :]
    valid = ti < lengths[:, None]
    if window is not None and window > 0:
        valid &= ti >= (lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v)
    return out.reshape(b, 1, h, hd)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            zero_centered: bool = True) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    sc = scale.astype(jnp.float32)
    sc = 1.0 + sc if zero_centered else sc
    return (xf * sc).astype(x.dtype)


def mamba_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                     c: jax.Array, d: jax.Array, *, chunk: int = 256,
                     h0: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD, sequential reference (exact recurrence).

    x:  (B, S, NH, HD)   inputs per head
    dt: (B, S, NH)       softplus-ed step sizes (already positive)
    a:  (NH,)            negative decay rates (A = -exp(a_log))
    b:  (B, S, NS)       input matrix (single group)
    c:  (B, S, NS)       output matrix
    d:  (NH,)            skip connection
    h0: (B, NH, HD, NS)  initial state
    Returns (y: (B,S,NH,HD), h_final: (B,NH,HD,NS)).
    """
    bs, s, nh, hd = x.shape
    ns = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bs, nh, hd, ns), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,NH,HD), (B,NH), (B,NS), (B,NS)
        decay = jnp.exp(dtt * a[None])  # (B, NH)
        dbx = jnp.einsum("bh,bn,bhd->bhdn", dtt, bt, xt)  # (B,NH,HD,NS)
        h = h * decay[..., None, None] + dbx
        y = jnp.einsum("bhdn,bn->bhd", h, ct) + d[None, :, None] * xt
        return h, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, h_final


def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    i_gate: jax.Array, f_gate: jax.Array, *,
                    eps: float = 1e-6) -> jax.Array:
    """xLSTM mLSTM, full-quadratic stabilized reference.

    q,k,v: (B, S, NH, HD); i_gate,f_gate: (B, S, NH) pre-activation.
    Returns (B, S, NH, HD).
    """
    bs, s, nh, hd = q.shape
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))  # (B,S,NH)
    logf_cum = jnp.cumsum(logf, axis=1)
    # D[t, u] = sum_{j=u+1..t} logf_j + i_u  for u <= t
    dmat = (logf_cum[:, :, None] - logf_cum[:, None, :]
            + i_gate.astype(jnp.float32)[:, None, :, :])  # (B,S_t,S_u,NH)
    tri = jnp.tril(jnp.ones((s, s), bool))
    dmat = jnp.where(tri[None, :, :, None], dmat, -jnp.inf)
    m = jnp.max(dmat, axis=2, keepdims=True)  # (B,S,1,NH)
    m = jnp.maximum(m, -1e30)  # guard all -inf rows
    dexp = jnp.exp(dmat - m)
    scores = jnp.einsum("bsnh,bunh->bsun", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (hd ** 0.5)
    w = scores * dexp
    norm = jnp.maximum(jnp.abs(jnp.sum(w, axis=2)), jnp.exp(-m[:, :, 0]))
    y = jnp.einsum("bsun,bunh->bsnh", w, v.astype(jnp.float32))
    y = y / (norm[..., None] + eps)
    return y.astype(v.dtype)


def topk_gating(logits: jax.Array, k: int, *, router: str = "softmax",
                bias: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """MoE router reference. logits: (T, E) -> (weights (T,k), idx (T,k))."""
    sel = logits
    if bias is not None:
        sel = sel + bias[None]
    _, idx = jax.lax.top_k(sel, k)  # selection may use bias (DSv3)
    gathered = jnp.take_along_axis(logits, idx, axis=-1)
    if router == "sigmoid":
        w = jax.nn.sigmoid(gathered.astype(jnp.float32))
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        w = jax.nn.softmax(gathered.astype(jnp.float32), axis=-1)
    return w, idx
