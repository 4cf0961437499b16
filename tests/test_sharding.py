"""Sharding rules: every spec must evenly divide its dim on the production
mesh, and a real sharded train step must run on multi host devices
(subprocess, since device count is fixed at jax init)."""
import subprocess
import sys
import textwrap

import pytest

from repro import configs

ARCHS = configs.all_arch_names()


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divide_evenly(arch):
    """Validate specs against the production mesh axis sizes without
    building 512 devices: divisibility is checked symbolically."""
    import numpy as np
    import jax
    from repro.models import model as model_lib
    from repro.parallel import sharding

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    cfg = configs.get_config(arch)
    shapes = model_lib.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        spec = sharding.param_spec(cfg, FakeMesh(), path, leaf)
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([FakeMesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % n == 0, (
                jax.tree_util.keystr(path), leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k",
                                   "long_500k"])
def test_cache_and_input_specs_divide(arch, shape):
    import numpy as np
    import jax
    from repro.models.common import SHAPE_CASES
    from repro.models import model as model_lib
    from repro.parallel import sharding

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    cfg = configs.get_config(arch)
    if shape == "long_500k" and not cfg.subquadratic:
        pytest.skip("long_500k is for sub-quadratic archs only")
    case = SHAPE_CASES[shape]
    shapes = model_lib.abstract_cache(cfg, case.global_batch, 64)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        spec = sharding.cache_spec(cfg, FakeMesh(), case.global_batch,
                                   path, leaf)
        for dim, entry in enumerate(spec):
            if entry is None or dim == 2:  # dim2=seq uses real max_len
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([FakeMesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % n == 0, (
                jax.tree_util.keystr(path), leaf.shape, spec)


def test_sharded_train_step_runs_on_host_mesh():
    """End-to-end: real (not abstract) sharded train step on 8 placeholder
    devices in a subprocess."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import model as model_lib
        from repro.parallel import sharding
        from repro.parallel.annotate import logical_rules, make_rules
        from repro.train.optimizer import make_optimizer
        from repro.train.train_step import make_train_step

        cfg = configs.get_config("llama3.2-1b", smoke=True)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
        pspecs = sharding.param_shardings(cfg, mesh)
        params = jax.device_put(params, pspecs)
        opt = make_optimizer("adamw")
        state = opt.init(params)
        toks = jnp.asarray(np.random.randint(0, cfg.vocab_size, (4, 32)))
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch = {"tokens": jax.device_put(toks,
                    NamedSharding(mesh, P("data", None))),
                 "labels": jax.device_put(toks,
                    NamedSharding(mesh, P("data", None)))}
        with logical_rules(mesh, make_rules(cfg, mesh, 4)):
            step = jax.jit(make_train_step(cfg, opt))
            p2, s2, m = step(params, state, batch)
        assert np.isfinite(float(m["loss"]))
        print("SHARDED_OK", float(m["loss"]))
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"},
                       cwd=__import__("pathlib").Path(
                           __file__).parent.parent)
    assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]
