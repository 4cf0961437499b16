"""Device time of one program's operations by the ``jax.named_scope`` they
were traced under.

The device trace names an operation by its HLO instruction
(``%fusion.127 = bf16[...] fusion(...)``), not by its scope.  The scope is
in the compiled program's HLO text, in each instruction's
``metadata={op_name="jit(decode_fn)/while/body/attn/decode_attention/..."}``;
:func:`op_names` reads it.  :func:`scope_times` sums the device time of the
program's leaf operations in a trace by scope.  Container operations
(``while``, ``call``, ``conditional``) enclose their bodies' operations in
the trace and are not counted, so the sum stays within the program's time.
"""
from __future__ import annotations

import bisect
import collections
import re

import tracereduce

CONTAINERS = frozenset({"while", "call", "conditional"})

#: the scope names the program gives: the ops of ``kernels/ops.py`` and the
#: parts of the model (``models/model.py``, ``models/blocks.py``)
SCOPES = frozenset({
    "decode_attention", "flash_attention", "rmsnorm", "mamba_chunk_scan",
    "mlstm", "embed", "layers", "attn", "mla", "cross_attn", "mamba2",
    "slstm", "mlp", "moe", "final_norm", "head"})

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_\-]*)\(")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the ``op_name`` of its metadata, from a compiled
    program's HLO text."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}


def opcode(op: str) -> str:
    """The opcode of an operation named by its HLO text in the trace."""
    m = _OPCODE.search(op.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def scope(op_name: str) -> str:
    """The program's scope names in an ``op_name``, outermost first, as
    ``attn/decode_attention``; empty where it has none."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


def scope_times(red: tracereduce.Reduced, names: dict,
                program: str) -> tuple[collections.Counter, int]:
    """Device nanoseconds of the leaf operations inside the runs of the
    program whose name holds ``program``, by :func:`scope` ("" for none),
    and the number of those runs."""
    runs = [(s, e) for s, e, n in red.modules if program in n]
    starts = [s for s, _ in runs]
    by_scope = collections.Counter()
    for s, e, op in red.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or runs[i][1] < e or opcode(op) in CONTAINERS:
            continue
        by_scope[scope(names.get(tracereduce.short_name(op), ""))] += e - s
    return by_scope, len(runs)


def within(by_scope: collections.Counter, name: str) -> int:
    """The nanoseconds of ``by_scope`` under the scope ``name``."""
    return sum(t for k, t in by_scope.items() if name in k.split("/"))
