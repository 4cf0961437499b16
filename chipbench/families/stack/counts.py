"""Operations and bytes the ``stack`` family's work needs, from the
configuration's shapes alone.

These count what the algorithm requires, not what the program happens to
run: padding, masked cache positions, recomputation and the head over a
prefill's last position (whose logits the engine discards) are left out.
A multiply-add is two operations.
"""
from __future__ import annotations


def _mamba(conf):
    d_inner = conf["mamba_expand"] * conf["hidden_size"]
    nh = d_inner // conf["mamba_headdim"]
    return d_inner, nh, conf["mamba_d_state"], conf["mamba_d_conv"]


def _applications(conf) -> list[dict]:
    """Every layer application in order (a shared block once per repeat)."""
    reps = conf["num_hidden_layers"] // len(conf["block_pattern"])
    return list(conf["block_pattern"]) * reps


def layer_matmul_params(conf: dict, block: dict) -> int:
    """Weights of one layer application that take part in a product."""
    d = conf["hidden_size"]
    n = 0
    if block["kind"] == "attn":
        h, kv, hd = (conf["num_attention_heads"],
                     conf["num_key_value_heads"], conf["head_dim"])
        n += d * h * hd * 2 + d * kv * hd * 2
    elif block["kind"] == "mamba2":
        d_inner, nh, ns, _ = _mamba(conf)
        n += d * (2 * d_inner + 2 * ns + nh) + d_inner * d
    if block["mlp"] == "glu":
        n += 3 * d * conf["intermediate_size"]
    return n


def layer_flops(conf: dict, block: dict, ctx: int) -> float:
    """One token through one layer application, attending ``ctx``
    positions (itself included)."""
    f = 2.0 * layer_matmul_params(conf, block)
    if block["kind"] == "attn":
        # scores and the weighted sum of values: 2 x 2 x heads x hd per key
        f += 4.0 * conf["num_attention_heads"] * conf["head_dim"] * ctx
    elif block["kind"] == "mamba2":
        d_inner, nh, ns, kc = _mamba(conf)
        # state update (decay and input) and the readout, per head entry
        f += 6.0 * nh * conf["mamba_headdim"] * ns
        f += 2.0 * kc * (d_inner + 2 * ns)
    return f


def head_flops(conf: dict) -> float:
    return 2.0 * conf["hidden_size"] * conf["vocab_size"]


def prefill_flops(conf: dict, n: int) -> float:
    """Filling the cache with ``n`` prompt tokens (causal; no head)."""
    tot = 0.0
    for block in _applications(conf):
        f = n * layer_flops(conf, block, 0)
        if block["kind"] == "attn":
            f += (4.0 * conf["num_attention_heads"] * conf["head_dim"]
                  * n * (n + 1) / 2)
        tot += f
    return tot


def decode_flops(conf: dict, ctx: int) -> float:
    """One generated token whose position attends ``ctx`` positions."""
    return (sum(layer_flops(conf, b, ctx) for b in _applications(conf))
            + head_flops(conf))


def weight_bytes(conf: dict) -> int:
    """Weights a decode step reads: every product's weights once, the head
    (the embedding table when tied) once, and the norms.  The embedding
    lookup of a few rows is left out."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    pattern = conf["block_pattern"]
    reps = conf["num_hidden_layers"] // len(pattern)
    n = d * v + d                     # head and final norm
    for block in pattern:
        per = layer_matmul_params(conf, block) + d
        if block["mlp"] == "glu":
            per += d
        if block["kind"] == "mamba2":
            d_inner, nh, ns, kc = _mamba(conf)
            per += kc * (d_inner + 2 * ns) + (d_inner + 2 * ns) + d_inner
            per += 3 * nh * 2         # a_log, dt_bias, d_skip in float32
        n += per * (1 if block.get("shared") else reps)
    return n * 2                      # bfloat16


def mlp_weight_bytes(conf: dict) -> int:
    """The GLU MLPs' weights a decode step reads: the gate, up and down
    projections of each layer once (a shared block once, as in
    :func:`weight_bytes`), without the norm before them."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    reps = conf["num_hidden_layers"] // len(conf["block_pattern"])
    n = sum(3 * d * ff * (1 if block.get("shared") else reps)
            for block in conf["block_pattern"] if block["mlp"] == "glu")
    return n * 2                      # bfloat16


def kv_bytes_per_position(conf: dict) -> int:
    """Key and value bytes of one position over all attention layers."""
    n_attn = sum(1 for b in _applications(conf) if b["kind"] == "attn")
    return n_attn * 2 * conf["num_key_value_heads"] * conf["head_dim"] * 2


def state_bytes_per_sequence(conf: dict) -> int:
    """Recurrent state of one sequence: float32 SSM state and the bf16
    convolution tail, over all mamba2 layers."""
    n_m = sum(1 for b in _applications(conf) if b["kind"] == "mamba2")
    if not n_m:
        return 0
    d_inner, nh, ns, kc = _mamba(conf)
    return n_m * (nh * conf["mamba_headdim"] * ns * 4
                  + (kc - 1) * (d_inner + 2 * ns) * 2)


def decode_token_bytes(conf: dict, ctx: int) -> int:
    """Bytes one sequence adds to a decode step: the keys and values of its
    ``ctx`` - 1 earlier positions read and its new ones written, and its
    state read and written."""
    return (kv_bytes_per_position(conf) * ctx
            + 2 * state_bytes_per_sequence(conf))
