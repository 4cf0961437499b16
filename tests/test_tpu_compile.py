"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with JAX, so these tests catch what interpret
mode cannot (block shapes the tiling rules refuse, kernels that do not
lower, programs that do not fit the chip's memory) at no chip time.
Nothing runs: they say nothing about results or speed.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and a test
module that decided at import time whether its tests exist would give
pytest-xdist workers different collections.  Keep every such compile in
this one file.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_chunk_scan import mamba_chunk_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.models import model as model_lib
from repro.train.optimizer import make_optimizer
from repro.train.trainer import mean_and_apply

HBM_BYTES = 16 * 2**30          # one v5e chip
LLAMA = configs.get_config("llama3.2-1b")
ZAMBA = configs.get_config("zamba2-2.7b")
DEEPSEEK = configs.get_config("deepseek-coder-33b")


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(tree, sharding):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _decode_fn(cfg):
    """The serving engine's decode step: next tokens and the new cache."""
    def decode_fn(params, tokens, cache, pos):
        logits, cache = model_lib.decode_step(params, cfg, tokens, cache,
                                              pos)
        return jnp.argmax(logits[:, 0], axis=-1), cache
    return decode_fn


def _cut(cfg, layers):
    """``cfg`` with its one group of layers cut to ``layers``."""
    g, = cfg.groups
    return dataclasses.replace(cfg, groups=(dataclasses.replace(
        g, repeat=layers),))


@pytest.mark.parametrize("s", [16, 128, 256])
def test_flash_attention_compiles(one_chip, s):
    h, kv, hd = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.head_dim
    c = _compile(functools.partial(flash_attention, scale=hd ** -0.5),
                 _spec(one_chip, (1, s, h, hd)),
                 _spec(one_chip, (1, s, kv, hd)),
                 _spec(one_chip, (1, s, kv, hd)))
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_compiles(one_chip):
    h, kv, hd = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.head_dim
    b, t = 8, 256
    c = _compile(
        lambda q, k, v, n: decode_attention(q, k, v, lengths=n,
                                            scale=hd ** -0.5),
        _spec(one_chip, (b, 1, h, hd)), _spec(one_chip, (b, t, kv, hd)),
        _spec(one_chip, (b, t, kv, hd)), _spec(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [8, 15, 1024])
def test_rmsnorm_compiles(one_chip, rows):
    d = LLAMA.d_model
    c = _compile(rmsnorm, _spec(one_chip, (rows, d)),
                 _spec(one_chip, (d,)))
    assert "tpu_custom_call" in c.as_text()


def test_mamba_chunk_scan_compiles(one_chip):
    m = ZAMBA.mamba
    nh = m.expand * ZAMBA.d_model // m.head_dim
    b, s, f32 = 1, 4 * m.chunk, jnp.float32
    c = _compile(
        functools.partial(mamba_chunk_scan, chunk=m.chunk),
        _spec(one_chip, (b, s, nh, m.head_dim), f32),
        _spec(one_chip, (b, s, nh), f32), _spec(one_chip, (nh,), f32),
        _spec(one_chip, (b, s, m.d_state), f32),
        _spec(one_chip, (b, s, m.d_state), f32),
        _spec(one_chip, (nh,), f32))
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture
def impl(request):
    ops.set_impl(request.param)
    yield request.param
    ops.set_impl("ref")


@pytest.mark.parametrize("impl", ["ref", "pallas"], indirect=True)
def test_llama_decode_step_fits_one_chip(one_chip, impl):
    """The serving engine's jitted decode step at full width, batch 8,
    max_len 256: compiles, and its arguments, outputs and temporaries fit
    one chip's HBM."""
    cfg = LLAMA
    b, max_len = 8, 256
    c = _compile(_decode_fn(cfg),
                 _on_chip(model_lib.abstract_params(cfg), one_chip),
                 _spec(one_chip, (b, 1), jnp.int32),
                 _on_chip(model_lib.abstract_cache(cfg, b, max_len),
                          one_chip),
                 _spec(one_chip, (b,), jnp.int32),
                 donate_argnums=(2,))
    assert ("tpu_custom_call" in c.as_text()) == (impl == "pallas")
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    params_bytes = sum(math.prod(x.shape) * x.dtype.itemsize for x in
                       jax.tree.leaves(model_lib.abstract_params(cfg)))
    assert params_bytes < mem.argument_size_in_bytes < total < HBM_BYTES


@pytest.mark.parametrize("impl", ["ref", "pallas"], indirect=True)
def test_llama_prefill_compiles(one_chip, impl):
    """The engine's batch-1 prefill at its largest smoke bucket."""
    cfg = LLAMA
    c = _compile(lambda p, t, cache: model_lib.prefill(p, cfg, t, cache),
                 _on_chip(model_lib.abstract_params(cfg), one_chip),
                 _spec(one_chip, (1, 128), jnp.int32),
                 _on_chip(model_lib.abstract_cache(cfg, 1, 256), one_chip))
    assert ("tpu_custom_call" in c.as_text()) == (impl == "pallas")
    assert c.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_microbatch_update_fits_one_chip(one_chip):
    """MicrobatchCoordinator's averaged-gradient optimizer update for the
    2-layer full-width cut that chip_smoke.py trains, with four
    microbatch gradients held: fits one chip with room to spare."""
    cfg = _cut(LLAMA, 2)
    opt = make_optimizer("adamw")
    params = model_lib.abstract_params(cfg)
    state = jax.eval_shape(opt.init, params)
    c = _compile(functools.partial(mean_and_apply, opt),
                 _on_chip(params, one_chip), _on_chip(state, one_chip),
                 [_on_chip(params, one_chip)] * 4, donate_argnums=(0, 1))
    mem = c.memory_analysis()
    # the new parameters and optimizer state reuse the donated buffers
    assert mem.alias_size_in_bytes > 0.99 * mem.output_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES // 2


def test_llama_train_step_fits_one_chip(one_chip):
    """The gradient of the training loss for the same 2-layer cut, batch 8
    of 256 tokens: compiles, and fits one chip."""
    cfg = _cut(LLAMA, 2)
    grad = jax.grad(lambda p, t, l: model_lib.forward_loss(p, cfg, t, l)[0])
    c = _compile(grad, _on_chip(model_lib.abstract_params(cfg), one_chip),
                 _spec(one_chip, (8, 256), jnp.int32),
                 _spec(one_chip, (8, 256), jnp.int32))
    mem = c.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def _copied_slices(hlo: str, shapes) -> list[str]:
    """The copies and dynamic-slice fusions in ``hlo`` that produce a value
    of one of ``shapes``."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (.*?) (?:copy|copy-start"
                     r"|fusion)\(", line)
        if (m and re.search("copy|dynamic-slice", m[1])
                and any(shape in m[2] for shape in shapes)):
            found.append(m[1])
    return found


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_qkv_weights_read_in_place(one_chip, mode):
    """deepseek-coder-33b's serving programs, cut to 2 layers: no layer's
    slice of the stacked q/k/v projections is materialised or copied to
    another layout; the projections read the weights where they lie."""
    cfg = _cut(DEEPSEEK, 2)
    d, h, kv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.head_dim)
    params = _on_chip(model_lib.abstract_params(cfg), one_chip)
    if mode == "decode":
        b, max_len = 16, 1536
        c = _compile(_decode_fn(cfg), params,
                     _spec(one_chip, (b, 1), jnp.int32),
                     _on_chip(model_lib.abstract_cache(cfg, b, max_len),
                              one_chip),
                     _spec(one_chip, (b,), jnp.int32), donate_argnums=(2,))
    else:
        c = _compile(lambda p, t, cache: model_lib.prefill(p, cfg, t, cache),
                     params, _spec(one_chip, (1, 1024), jnp.int32),
                     _on_chip(model_lib.abstract_cache(cfg, 1, 1536),
                              one_chip))
    shapes = [f"[1,{d},{h * hd}]", f"[1,{d},{kv * hd}]"]
    assert _copied_slices(c.as_text(), shapes) == []
