"""Device time of one program's operations by the ``jax.named_scope`` they
were traced under.

The device trace names an operation by its HLO instruction
(``%fusion.127 = bf16[...] fusion(...)``), not by its scope.  The scope is
in the compiled program's HLO text, in each instruction's
``metadata={op_name="jit(decode_fn)/while/body/attn/decode_attention/..."}``;
:func:`op_names` reads it.  :func:`scope_times` sums the device time of the
program's leaf operations in a trace by scope, and :func:`ns_under` the
time under any one name, whether or not :data:`SCOPES` lists it.  Container
operations (``while``, ``call``, ``conditional``) enclose their bodies'
operations in the trace and are not counted, so the sum stays within the
program's time.
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

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_\-]*)\(")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the ``op_name`` of its metadata, from a compiled
    program's HLO text; "" for an instruction that the compiler made and
    gave none (the copies it inserts, say)."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        name = _OP_NAME.search(m.group(2))
        out[m.group(1)] = name.group(1) if name else ""
    return out


def opcode(op: str) -> str:
    """The opcode of an operation named by its HLO text in the trace."""
    m = _OPCODE.search(op.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def scope(op_name: str) -> str:
    """The program's scope names in an ``op_name``, outermost first, as
    ``attn/decode_attention``; empty where it has none."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


def leaves(red: tracereduce.Reduced,
           program: str) -> tuple[list[tuple[str, int]], int]:
    """(instruction, device nanoseconds) of each leaf operation inside the
    runs of the program whose name holds ``program``, and the number of
    those runs."""
    runs = [(s, e) for s, e, n in red.modules if program in n]
    starts = [s for s, _ in runs]
    out = []
    for s, e, op in red.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or runs[i][1] < e or opcode(op) in CONTAINERS:
            continue
        out.append((tracereduce.short_name(op), e - s))
    return out, len(runs)


def scope_times(red: tracereduce.Reduced, names: dict,
                program: str) -> tuple[collections.Counter, int]:
    """Device nanoseconds of the program's leaf operations by
    :func:`scope` ("" for none), and the number of its runs."""
    ops, runs = leaves(red, program)
    by_scope = collections.Counter()
    for op, ns in ops:
        by_scope[scope(names.get(op, ""))] += ns
    return by_scope, runs


def ns_under(m, program: str, name: str) -> tuple[int, int]:
    """Device nanoseconds of the leaf operations of ``program`` whose op
    name has ``name`` as one of its ``/``-separated parts, in the traced
    window of ``m`` (a ``harness.Measured``), and the number of the
    program's runs; (0, 0) where the run kept no trace or no op names of
    the program."""
    names = m.op_names.get(program)
    if m.trace is None or not names:
        return 0, 0
    ops, runs = leaves(m.trace, program)
    return sum(ns for op, ns in ops
               if name in names.get(op, "").split("/")), runs


def named_share(red: tracereduce.Reduced, names: dict,
                program: str) -> float | None:
    """The share of the program's leaf device time that falls on
    instructions ``names`` holds; None where it ran no leaf operation."""
    ops, _ = leaves(red, program)
    total = sum(ns for _, ns in ops)
    return sum(ns for op, ns in ops if op in names) / total if total else None
