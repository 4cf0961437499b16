"""Operations and bytes the model's work needs, from the configuration's
shapes alone, as its family (``families/<family>/counts.py``) counts them.

These count what the algorithm requires, not what the program happens to
run: padding, masked cache positions, recomputation and the head over a
prefill's last position (whose logits the engine discards) are left out.
A multiply-add is two operations.
"""
from __future__ import annotations

import spec


def prefill_flops(conf: dict, n: int) -> float:
    """Filling the cache with ``n`` prompt tokens (causal; no head)."""
    return spec.family(conf, "counts").prefill_flops(conf, n)


def decode_flops(conf: dict, ctx: int) -> float:
    """One generated token whose position attends ``ctx`` positions."""
    return spec.family(conf, "counts").decode_flops(conf, ctx)


def weight_bytes(conf: dict) -> int:
    """Weights a decode step reads."""
    return spec.family(conf, "counts").weight_bytes(conf)


def decode_token_bytes(conf: dict, ctx: int) -> int:
    """Bytes one sequence adds to a decode step: its cache and state read
    and written."""
    return spec.family(conf, "counts").decode_token_bytes(conf, ctx)
