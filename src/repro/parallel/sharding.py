"""Sharding rules: parameter / optimizer / cache / input PartitionSpecs.

Policy:
  * TP over the ``model`` axis: attention heads, FFN hidden dim, vocab.
  * FSDP over ``data`` for archs flagged ``cfg.fsdp`` (big dense weights get
    their non-TP dim sharded over data; XLA inserts all-gathers).
  * EP: expert dim over ``model`` when divisible; for very large expert
    counts (DeepSeek-V3) over ``(model, data)`` jointly (1 expert/device).
  * DP: batch over ``(pod, data)`` (or whatever prefix divides the batch).
  * KV caches: heads over ``model`` when divisible, otherwise the sequence
    dim (flash-decoding-style sharded-KV softmax), batch over data axes.

All rules are *hints*: GSPMD preserves correctness regardless; these choices
drive the collective schedule.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.models import model as model_lib
from repro.models.common import SHAPE_CASES, ShapeCase
from repro.models.config import ModelConfig


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _path_keys(path) -> list[str]:
    keys = []
    for e in path:
        if isinstance(e, DictKey):
            keys.append(str(e.key))
        elif isinstance(e, SequenceKey):
            keys.append(f"[{e.idx}]")
    return keys


def batch_axes(mesh: Mesh, batch: int) -> tuple[str, ...]:
    """Longest prefix of (pod, data) whose product divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen: list[str] = []
    size = 1
    for a in sorted(axes, key=lambda a: a != "data"):  # prefer data first
        if batch % (size * _axis_size(mesh, a)) == 0:
            chosen.append(a)
            size *= _axis_size(mesh, a)
    return tuple(chosen)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def param_spec(cfg: ModelConfig, mesh: Mesh, path, leaf) -> P:
    keys = _path_keys(path)
    name = keys[-1]
    shape = leaf.shape
    # scan-over-layers stacks non-shared slot params along a leading repeat
    # axis: rules must apply to shape[1:], with the repeat dim replicated
    stacked = False
    if len(keys) >= 4 and keys[0] == "groups" and keys[2] == "slots":
        gi = int(keys[1].strip("[]"))
        si = int(keys[3].strip("[]"))
        stacked = not cfg.groups[gi].pattern[si].shared
    if stacked:
        shape = shape[1:]
    entries = tuple(_spec_entries(cfg, mesh, keys, name, shape))
    if stacked:
        entries = (None,) + entries
    return P(*entries)


def _spec_entries(cfg: ModelConfig, mesh: Mesh, keys, name, shape) -> tuple:
    tp = _axis_size(mesh, "model")
    dp = _axis_size(mesh, "data")
    fsdp = "data" if cfg.fsdp else None

    def ok(dim, ax):  # divisibility check for an axis name
        n = _axis_size(mesh, ax) if ax else 1
        return ax is not None and shape[dim] % n == 0 and n > 1

    # --- embeddings / heads ---
    if name == "embed":
        if cfg.num_codebooks:
            return (None, "model" if ok(1, "model") else None, None)
        return ("model" if ok(0, "model") else None, None)
    if name == "head":
        if cfg.num_codebooks:
            return (None, None, "model" if ok(2, "model") else None)
        return (None, "model" if ok(1, "model") else None)

    # --- MoE experts ---
    if "experts" in keys:
        e = cfg.moe.num_experts
        if e % (tp * dp) == 0 and tp * dp > 1:        # EP over model+data
            return (("model", "data"), None, None)
        if e % tp == 0 and tp > 1:                    # EP over model
            return ("model", fsdp if ok(1, fsdp) else None, None)
        # TP inside experts: shard the F dim (dim2 for wi/wu, dim1 for wo)
        if name in ("wi", "wu"):
            return (None, fsdp if ok(1, fsdp) else None,
                     "model" if ok(2, "model") else None)
        return (None, "model" if ok(1, "model") else None,
                 fsdp if ok(2, fsdp) else None)
    if "router" in keys:
        return ()

    # --- mamba / xlstm (small models: replicate projections) ---
    if name in ("in_proj", "out_proj", "conv_w", "conv_b", "a_log",
                "dt_bias", "d_skip", "r", "w_gates", "up", "down",
                "up_gate", "w_in"):
        return tuple([None] * len(shape))

    # --- attention / MLP 2D weights ---
    if name in ("wq", "wq_b", "wk_b", "wv_b"):
        return (fsdp if ok(0, fsdp) else None,
                 "model" if ok(1, "model") else None)
    if name in ("wk", "wv"):
        # shard KV projection over model only if kv heads divide tp
        kv_ok = cfg.num_kv_heads % tp == 0 and tp > 1 and ok(1, "model")
        return (fsdp if ok(0, fsdp) else None, "model" if kv_ok else None)
    if name == "wo":
        return ("model" if ok(0, "model") else None,
                 fsdp if ok(1, fsdp) else None)
    if name in ("wi", "wu"):
        return (fsdp if ok(0, fsdp) else None,
                 "model" if ok(1, "model") else None)
    if name in ("wq_a", "wkv_a"):
        return (fsdp if ok(0, fsdp) else None, None)

    # norms, gates, scalars
    return tuple([None] * len(shape))


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> Any:
    shapes = model_lib.abstract_params(cfg)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_spec(cfg, mesh, p, l)), shapes)


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, mesh: Mesh, batch: int, path, leaf) -> P:
    keys = _path_keys(path)
    name = keys[-1]
    tp = _axis_size(mesh, "model")
    b_axes = batch_axes(mesh, batch)
    b = b_axes if b_axes else None
    shape = leaf.shape  # leading dims: (repeat, B, ...)

    if name in ("k", "v"):            # (R, B, S, KV, hd) attention cache
        kv = shape[3]
        if kv % tp == 0 and tp > 1:
            return P(None, b, None, "model", None)
        if shape[2] % tp == 0 and tp > 1:
            return P(None, b, "model", None, None)  # shard sequence
        return P(None, b, None, None, None)
    if name == "ckv":                 # (R, B, S, r) MLA latent
        if shape[3] % tp == 0 and tp > 1:
            return P(None, b, None, "model")
        return P(None, b, None, None)
    if name == "krope":
        return P(None, b, None, None)
    if name == "ssm":                 # (R, B, NH, HD, NS)
        if shape[2] % tp == 0 and tp > 1:
            return P(None, b, "model", None, None)
        return P(None, b, None, None, None)
    if name == "conv":                # (R, B, K-1, conv_dim)
        return P(None, b, None, "model" if shape[3] % tp == 0 and tp > 1
                 else None)
    if name in ("c", "n", "h", "m"):  # xLSTM states (R, B, NH, ...)
        return P(None, b, *([None] * (len(shape) - 2)))
    if name == "filled":
        return P(*([None] * len(shape)))
    return P(None, b, *([None] * (len(shape) - 2)))


# ---------------------------------------------------------------------------
# Input specs (the assigned shape cells)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, case: ShapeCase | str, mesh: Mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    if isinstance(case, str):
        case = SHAPE_CASES[case]
    b, s = case.global_batch, case.seq_len
    b_axes = batch_axes(mesh, b) or None
    seq_axes = None
    if b_axes is None and s % _axis_size(mesh, "data") == 0:
        seq_axes = "data"  # long-context batch=1: shard sequence

    def tok(shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32,
            sharding=NamedSharding(mesh, P(b_axes, *([seq_axes] +
                                           [None] * (len(shape) - 2)))))

    out: dict = {}
    tok_shape = ((b, s, cfg.num_codebooks) if cfg.num_codebooks else (b, s))
    if case.kind == "train":
        out["tokens"] = tok(tok_shape)
        out["labels"] = tok(tok_shape)
    elif case.kind == "prefill":
        out["tokens"] = tok(tok_shape)
    else:  # decode: one new token against a seq_len cache
        one = ((b, 1, cfg.num_codebooks) if cfg.num_codebooks else (b, 1))
        out["tokens"] = jax.ShapeDtypeStruct(
            one, jnp.int32, sharding=NamedSharding(
                mesh, P(b_axes, *([None] * (len(one) - 1)))))
        out["pos"] = jax.ShapeDtypeStruct(
            (b,), jnp.int32, sharding=NamedSharding(mesh, P(b_axes)))
    if cfg.vision_dim and case.kind != "decode":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_image_tokens, cfg.vision_dim), cfg.compute_dtype,
            sharding=NamedSharding(mesh, P(b_axes, None, None)))
    return out
