"""Jit-friendly op dispatch between the pure-jnp references and the Pallas
TPU kernels.  The model code only ever imports this module.

The default is ``ref`` on every backend, the TPU included: nothing in the
program switches it.  ``set_impl('pallas')`` routes the hot ops to the
Pallas kernels (``interpret=True`` runs them on the CPU, as the tests do;
``chip_smoke.py`` runs them compiled on the chip).  Calls already traced
under ``jax.jit`` keep the implementation they were traced with.

Each op runs under a ``jax.named_scope`` named after the op, whichever
implementation serves it, so the op's instructions carry that name in
their HLO metadata and a device trace can be read by op.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax

from repro.kernels import ref

_IMPL: Literal["ref", "pallas"] = "ref"
_INTERPRET = False


def set_impl(impl: str, *, interpret: bool = False) -> None:
    global _IMPL, _INTERPRET
    assert impl in ("ref", "pallas")
    _IMPL = impl
    _INTERPRET = interpret


def get_impl() -> str:
    return _IMPL


def _scoped(op):
    @functools.wraps(op)
    def run(*args, **kwargs):
        with jax.named_scope(op.__name__):
            return op(*args, **kwargs)
    return run


@_scoped
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=1.0, q_offset=0):
    if _IMPL == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  q_offset=q_offset, interpret=_INTERPRET)
    return ref.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, q_offset=q_offset)


@_scoped
def decode_attention(q, k, v, *, lengths, window=None, softcap=None,
                     scale=1.0):
    if _IMPL == "pallas":
        from repro.kernels import decode_attention as da
        return da.decode_attention(q, k, v, lengths=lengths, window=window,
                                   softcap=softcap, scale=scale,
                                   interpret=_INTERPRET)
    return ref.decode_attention(q, k, v, lengths=lengths, window=window,
                                softcap=softcap, scale=scale)


@_scoped
def rmsnorm(x, scale, *, eps=1e-6, zero_centered=True):
    if _IMPL == "pallas":
        from repro.kernels import rmsnorm as rn
        return rn.rmsnorm(x, scale, eps=eps, zero_centered=zero_centered,
                          interpret=_INTERPRET)
    return ref.rmsnorm(x, scale, eps=eps, zero_centered=zero_centered)


@_scoped
def mamba_chunk_scan(x, dt, a, b, c, d, *, chunk=256, h0=None):
    if _IMPL == "pallas":
        from repro.kernels import mamba_chunk_scan as mcs
        return mcs.mamba_chunk_scan(x, dt, a, b, c, d, chunk=chunk, h0=h0,
                                    interpret=_INTERPRET)
    return ref.mamba_chunk_scan(x, dt, a, b, c, d, chunk=chunk, h0=h0)


@_scoped
def mlstm(q, k, v, i_gate, f_gate, *, eps=1e-6, chunk=256):
    # chunked mLSTM runs through the model-side associative-scan path; the
    # quadratic stabilised oracle lives in ref (no Pallas variant yet)
    return ref.mlstm_chunkwise(q, k, v, i_gate, f_gate, eps=eps)
