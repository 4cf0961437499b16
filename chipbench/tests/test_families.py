"""Model families as directories (``families/<family>/``).

The ``stack`` family holds what the harness ran before families existed,
and must compute exactly what it computed then: the numbers pinned here
were taken from the code it was moved from.  A family written into a fresh
directory runs through the whole harness with no file of the benchmark
edited.  No family's reference imports the program.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import ast
import hashlib
import textwrap
import time

import jax
import numpy as np
import pytest

import smoke
import counts
import reference
import scopes
import spec

DS = spec.load_json(spec.HERE / "configs" / "deepseek-coder-33b-8L.json")
SMOKE = smoke.smoke_cell("deepseek-coder-33b-8L.chat").config
HYBRID = smoke.hybrid_config(SMOKE)
SEED = 2**31 + 11


def _digest(tree) -> str:
    d = hashlib.sha256()
    for a in jax.tree.leaves(tree):
        d.update(np.asarray(a).tobytes())
    return d.hexdigest()[:16]


@pytest.mark.parametrize("conf,want", [
    (DS, (8947742720, 9182380032.0, 8599879680000.0, 33554432)),
    (HYBRID, (252352, 537088.0, 337536000.0, 167680)),
], ids=["deepseek-coder-33b-8L", "hybrid-smoke"])
def test_stack_counts_as_before(conf, want):
    assert (counts.weight_bytes(conf), counts.decode_flops(conf, 1024),
            counts.prefill_flops(conf, 1000),
            counts.decode_token_bytes(conf, 1024)) == want


@pytest.mark.parametrize("conf,want", [
    (SMOKE, ("9358e364a53a351f", "0e9baacceb8a01cb", "1e17d8ac78b26d3b",
             "2b90e7f4955da135")),
    (HYBRID, ("38d13e397b0aaa31", "38ce4b785cf1c237", "ce2ec66046f7a5f2",
              "0b003d0f5884f123")),
], ids=["smoke", "hybrid-smoke"])
def test_stack_weights_and_reference_as_before(conf, want):
    """Weights from one seed, and the reference's hidden states, head
    readings and float8 control on them, bit for bit."""
    w = spec.make_weights(spec.seed_key(SEED), conf)
    toks = np.random.default_rng(7).integers(0, conf["vocab_size"], (2, 40),
                                             dtype=np.int32)
    x = reference.hidden(w, conf, toks)
    got = (_digest(w), _digest(x),
           _digest(reference.head_stats(w, conf, x, toks)),
           _digest(reference.hidden(w, conf, toks, quant="fp8")))
    assert got == want


def test_no_family_reference_imports_the_program():
    refs = sorted(spec.FAMILIES.glob("*/reference.py"))
    assert refs
    for path in refs:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n.split(".")[0] in ("repro", "spec") for n in names
                           ), (path, names)


#: an attention-only family, written from its own equations: every layer
#: is a GQA attention block with RoPE and no MLP
ATTN_ONLY = {
    "program.py": '''
        import jax
        import jax.numpy as jnp


        def model_config(conf):
            from repro.models.config import GroupSpec, LayerSpec, ModelConfig
            return ModelConfig(
                name=conf["name"],
                groups=(GroupSpec(pattern=(LayerSpec("attn", "none"),),
                                  repeat=conf["num_hidden_layers"]),),
                d_model=conf["hidden_size"],
                num_heads=conf["num_attention_heads"],
                num_kv_heads=conf["num_key_value_heads"],
                head_dim=conf["head_dim"], d_ff=conf["hidden_size"],
                vocab_size=conf["vocab_size"], rope_theta=conf["rope_theta"],
                tie_embeddings=False, norm_eps=conf["rms_norm_eps"],
                dtype=conf["torch_dtype"], remat="none")


        def make_weights(key, conf):
            d, h, kv, hd, v, n = (conf[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "vocab_size", "num_hidden_layers"))
            dt = jnp.dtype(conf["torch_dtype"])
            k = jax.random.split(key, 7)

            def normal(k, shape, std):
                return jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)

            layer = {"pre_norm": {"scale": normal(k[0], (n, d), 0.1)},
                     "mixer": {"wq": normal(k[1], (n, d, h * hd), d ** -0.5),
                               "wk": normal(k[2], (n, d, kv * hd), d ** -0.5),
                               "wv": normal(k[3], (n, d, kv * hd), d ** -0.5),
                               "wo": normal(k[4], (n, h * hd, d),
                                            (h * hd) ** -0.5)}}
            return {"embed": normal(k[5], (v, d), 0.02),
                    "groups": [{"slots": (layer,)}],
                    "final_norm": {"scale": jnp.zeros((d,), dt)},
                    "head": normal(k[6], (d, v), d ** -0.5)}
    ''',
    "reference.py": '''
        import jax
        import jax.numpy as jnp

        F32 = jnp.float32


        def _mm(x, w, quant):
            x, w = x.astype(F32), w.astype(F32)
            if quant == "fp8":
                x = x.astype(jnp.float8_e4m3fn).astype(F32)
                w = w.astype(jnp.float8_e4m3fn).astype(F32)
            return jnp.matmul(x, w, precision="highest")


        def _norm(scale, x, eps):
            x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            return x * (1.0 + scale.astype(F32))


        def _rope(x, theta):
            t, hd = x.shape[1], x.shape[-1]
            inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
            ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)


        def hidden(w, conf, tokens, quant=None):
            h, kv, hd = (conf["num_attention_heads"],
                         conf["num_key_value_heads"], conf["head_dim"])
            eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
            x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
            b, t, _ = x.shape
            causal = jnp.tril(jnp.ones((t, t), bool))
            (layers,) = w["groups"][0]["slots"]
            for i in range(conf["num_hidden_layers"]):
                p = jax.tree.map(lambda a: a[i], layers)
                y = _norm(p["pre_norm"]["scale"], x, eps)
                m = p["mixer"]
                q = _rope(_mm(y, m["wq"], quant).reshape(b, t, h, hd), theta)
                k = _rope(_mm(y, m["wk"], quant).reshape(b, t, kv, hd), theta)
                v = _mm(y, m["wv"], quant).reshape(b, t, kv, hd)
                k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               precision="highest") / hd ** 0.5
                a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
                o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision="highest")
                x = x + _mm(o.reshape(b, t, h * hd), m["wo"], quant)
            return _norm(w["final_norm"]["scale"], x, eps)


        def head_stats(w, conf, x, targets, quant=None):
            logits = _mm(x, w["head"], quant)
            at = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return (jnp.max(logits, -1), at,
                    jnp.argmax(logits, -1).astype(jnp.int32))
    ''',
    "counts.py": '''
        def _per_token(conf):
            d, h, kv, hd = (conf["hidden_size"], conf["num_attention_heads"],
                            conf["num_key_value_heads"], conf["head_dim"])
            return 2 * d * hd * (2 * h + 2 * kv)


        def prefill_flops(conf, n):
            attn = 4 * conf["num_attention_heads"] * conf["head_dim"]
            return conf["num_hidden_layers"] * (
                n * _per_token(conf) + attn * n * (n + 1) / 2)


        def decode_flops(conf, ctx):
            attn = 4 * conf["num_attention_heads"] * conf["head_dim"] * ctx
            return (conf["num_hidden_layers"] * (_per_token(conf) + attn)
                    + 2 * conf["hidden_size"] * conf["vocab_size"])


        def weight_bytes(conf):
            d, n = conf["hidden_size"], conf["num_hidden_layers"]
            return 2 * (n * (_per_token(conf) // 2 + d)
                        + d * conf["vocab_size"] + d)


        def decode_token_bytes(conf, ctx):
            return (conf["num_hidden_layers"] * 2
                    * conf["num_key_value_heads"] * conf["head_dim"] * 2 * ctx)
    ''',
}


def _attn_only_cell(tmp_path, monkeypatch) -> spec.Cell:
    """The chat cell at smoke widths, of the attention-only family written
    into a directory of its own."""
    home = tmp_path / "families" / "attn-only"
    home.mkdir(parents=True)
    for name, text in ATTN_ONLY.items():
        (home / name).write_text(textwrap.dedent(text).lstrip())
    monkeypatch.setattr(spec, "FAMILIES", tmp_path / "families")
    base = smoke.smoke_cell("deepseek-coder-33b-8L.chat")
    conf = dict(base.config, family="attn-only", name="attn-only-smoke")
    return spec.Cell(name=base.name, chips=1, config=conf,
                     traffic=base.traffic, sizes=base.sizes)


def test_a_new_family_runs_through_the_harness(tmp_path, monkeypatch):
    """A family in a directory of its own, and a configuration that names
    it, run through the whole harness and pass the output check."""
    import harness
    cell = _attn_only_cell(tmp_path, monkeypatch)
    conf = cell.config
    assert spec.model_config(conf).groups[0].pattern[0].mlp == "none"
    bench = spec.load_json(smoke.ROOT / "BENCHMARK.json")
    result, notes = harness.run(cell, bench, SEED, 2.0, False,
                                time.perf_counter(), jax.devices()[0],
                                smoke.PEAKS)
    assert result["correct"], notes
    assert result["failed"] == 0 and result["attempted"] > 0
    # the family's counts, through the names the metric readers call
    assert counts.decode_flops(conf, 10) > counts.decode_flops(conf, 1)
    assert counts.weight_bytes(conf) > 0


#: a per-layer metric a family's PR would add as ``metrics/<name>.py``: the
#: device time under a scope the program names for the family's own kernel
#: (no entry of ``scopes.SCOPES``), per decode step the engine counted
PROBE_METRIC = '''
    import scopes


    def read(m):
        ns, runs = scopes.ns_under(m, "decode_fn", "probe_kernel")
        steps = m.trace_counters.get("n_decode_steps", 0)
        return ns * 1e-6 / steps if runs and steps else None
'''

METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"


def test_a_new_family_brings_a_scope_and_counter_metric(tmp_path,
                                                         monkeypatch):
    """A family and a metric of its own, from new files alone: the metric
    reads the device time under a scope that no benchmark file names, and
    an engine counter, through a traced run of the whole harness.  The CPU
    gives no device plane, so the trace is made by hand from the op names
    the harness took from the compiled program: 5 ns on each instruction
    under the scope, 7 ns on one outside it."""
    import harness
    import tracereduce
    from repro.kernels import ops
    cell = _attn_only_cell(tmp_path, monkeypatch)
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "probe_kernel_ms.py").write_text(
        textwrap.dedent(PROBE_METRIC).lstrip())
    monkeypatch.setattr(spec, "HERE", tmp_path)
    bench = {"end_to_end": [], "per_layer": [{
        "name": "probe_kernel_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "ops and kernels",
        "moves": "tokens_per_s"}]}

    # the family's kernel as the program would bring it, under its own name
    attend = ops.decode_attention

    def probe_kernel(*args, **kw):
        with jax.named_scope("probe_kernel"):
            return attend(*args, **kw)
    monkeypatch.setattr(ops, "decode_attention", probe_kernel)
    assert "probe_kernel" not in scopes.SCOPES

    names = {}
    op_names = scopes.op_names

    def keep(hlo_text):
        names.update(op_names(hlo_text))
        return names
    monkeypatch.setattr(scopes, "op_names", keep)
    under = []

    def by_hand(path):
        under.extend(i for i, n in names.items()
                     if "probe_kernel" in n.split("/"))
        head = next(i for i, n in names.items() if "head" in n.split("/"))
        ops_ = [(10 * k, 10 * k + 5, f"%{i} = f32[] add()")
                for k, i in enumerate(under)] + [(0, 7, f"%{head} = add()")]
        return tracereduce.Reduced(lo=0, hi=10 ** 6, ops=sorted(ops_),
                                   host=[],
                                   modules=[(0, 10 ** 5, "jit_decode_fn(1)")])
    monkeypatch.setattr(tracereduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(tracereduce, "reduce", by_hand)
    made = smoke.kept_measured(monkeypatch)

    # the compile cache leaves op names out of its key; here only the names
    # differ from a program compiled before, so they go into it
    was = getattr(jax.config, METADATA_KEY)
    jax.config.update(METADATA_KEY, True)
    try:
        result, notes = harness.run(cell, bench, SEED, 3.0, True,
                                    time.perf_counter(), jax.devices()[0],
                                    smoke.PEAKS)
    finally:
        jax.config.update(METADATA_KEY, was)
    assert result["correct"], notes
    (m,) = made
    assert under and m.trace_counters["n_decode_steps"] > 0
    assert result["metrics"]["probe_kernel_ms"]["value"] == pytest.approx(
        5 * len(under) * 1e-6 / m.trace_counters["n_decode_steps"])
