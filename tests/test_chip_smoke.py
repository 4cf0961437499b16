"""CPU rehearsal of chip_smoke.py: its phases at the smoke config with the
Pallas kernels in interpret mode, its refusal to run without a TPU, and
where the persistent compilation cache goes."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro import configs

ROOT = Path(__file__).resolve().parent.parent
CFG = configs.get_config("llama3.2-1b", smoke=True)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    """Smoke-size weights, prompts from both prefill buckets, and the
    batch-1 reference they are served against."""
    params = smoke.make_params(CFG, 0)
    prompts = smoke.make_prompts(CFG, 0, n=6)
    return params, prompts, smoke.reference_generate(CFG, params, prompts, 6)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_prompts_span_two_prefill_buckets(smoke):
    from repro.serve.engine import _bucket
    lens = [len(p) for p in smoke.make_prompts(CFG, 3)]
    assert len(lens) == smoke.N_REQUESTS
    assert min(lens) >= 8 and max(lens) <= 120
    assert {_bucket(n - 1) for n in lens} == {16, 128}


def test_kernel_phase_interpret(smoke):
    errs = smoke.kernel_phase(CFG, seed=0, interpret=True)
    assert set(errs) == {"flash_attention", "decode_attention", "rmsnorm"}
    assert max(errs.values()) < smoke.KERNEL_TOL


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_serve_phase_matches_reference(smoke, served, impl):
    params, prompts, reference = served
    out = smoke.serve_phase(CFG, params, prompts, reference, impl=impl,
                            interpret=True, max_new=6, wait_s=300)
    assert out["requests"] == len(prompts)
    assert out["tokens"] == 6 * len(prompts)
    assert out["matched"] + out["tie_stopped"] == len(prompts)


def test_serve_phase_flags_a_wrong_token(smoke, served):
    params, prompts, reference = served
    toks, ties = reference[0]
    bad = [([t + 1 for t in toks], [False] * len(ties))] + reference[1:]
    with pytest.raises(smoke.SmokeFailure, match="differ"):
        smoke.serve_phase(CFG, params, prompts, bad, max_new=6,
                          wait_s=300)
    # the same wrong token at a near-tie step stops the comparison there
    tie = [([t + 1 for t in toks], [True] * len(ties))] + reference[1:]
    out = smoke.serve_phase(CFG, params, prompts, tie, max_new=6,
                            wait_s=300)
    assert out["tie_stopped"] >= 1


def test_train_phase_loss_falls(smoke):
    out = smoke.train_phase(smoke.train_config(CFG), seed=0, steps=3,
                            batch=8, seq=32)
    assert len(out["losses"]) == 3
    assert out["losses"][-1] < out["losses"][0]


def test_train_config_cuts_depth_only(smoke):
    full = configs.get_config("llama3.2-1b")
    cut = smoke.train_config(full)
    assert cut.num_layers == smoke.TRAIN_LAYERS < full.num_layers
    assert (cut.d_model, cut.num_heads, cut.vocab_size) == \
        (full.d_model, full.num_heads, full.vocab_size)


_CACHE_PROG = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    print(compile_cache.enable())
    print(jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
""")


def _run_cache_prog(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROG], env=env,
                       cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    enabled, configured = _run_cache_prog(tmp_path)
    assert enabled == configured == str(tmp_path)
    assert any(tmp_path.iterdir())          # written there


def test_compile_cache_defaults_inside_the_checkout():
    from repro.launch import compile_cache
    enabled, configured = _run_cache_prog(None)
    assert enabled == configured == str(ROOT / ".jax_cache") == \
        str(compile_cache.REPO_CACHE_DIR)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
