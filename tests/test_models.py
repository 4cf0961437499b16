"""Per-arch smoke tests (reduced same-family configs) + train/prefill/
decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as model_lib

ARCHS = configs.all_arch_names()


def _inputs(cfg, b, s, rng):
    if cfg.num_codebooks:
        toks = rng.integers(0, cfg.vocab_size, (b, s, cfg.num_codebooks))
    else:
        toks = rng.integers(0, cfg.vocab_size, (b, s))
    img = None
    if cfg.vision_dim:
        img = jnp.asarray(rng.standard_normal(
            (b, cfg.num_image_tokens, cfg.vision_dim)), jnp.float32)
    return jnp.asarray(toks, jnp.int32), img


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_smoke(arch, rng):
    cfg = configs.get_config(arch, smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    toks, img = _inputs(cfg, 2, 32, rng)
    logits, aux = jax.jit(
        lambda p, t: model_lib.forward(p, cfg, t, img))(params, toks)
    expect = ((2, 32, cfg.num_codebooks, cfg.vocab_size)
              if cfg.num_codebooks else (2, 32, cfg.vocab_size))
    assert logits.shape == expect
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux["moe_aux_loss"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch, rng):
    from repro.train.optimizer import make_optimizer
    from repro.train.train_step import make_train_step
    cfg = configs.get_config(arch, smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adamw")
    state = opt.init(params)
    toks, img = _inputs(cfg, 2, 32, rng)
    batch = {"tokens": toks, "labels": toks}
    if img is not None:
        batch["image_embeds"] = img
    step = jax.jit(make_train_step(cfg, opt))
    params2, state2, metrics = step(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    """Forward logits at the last position must match prefill(t[:-1]) +
    one decode step — the cache path is numerically consistent with the
    training path (for every mixer family: attention, MLA, mamba2,
    m/sLSTM, cross-attn)."""
    cfg = configs.get_config(arch, smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 2, 32
    toks, img = _inputs(cfg, b, s, rng)
    full, _ = jax.jit(lambda p, t: model_lib.forward(p, cfg, t, img))(
        params, toks)

    cache = model_lib.init_cache(cfg, b, s + 4)
    _, cache = jax.jit(lambda p, t, c: model_lib.prefill(p, cfg, t, c, img))(
        params, toks[:, :-1], cache)
    pos = jnp.full((b,), s - 1, jnp.int32)
    dec, _ = jax.jit(lambda p, t, c, q: model_lib.decode_step(p, cfg, t, c,
                                                              q))(
        params, toks[:, -1:], cache, pos)
    want = full[:, -1]
    got = dec[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_param_counts_match_published():
    import numpy as np
    expected = {
        "gemma_7b": 8.5e9, "gemma2_27b": 27.2e9, "llama3_2_1b": 1.24e9,
        "deepseek_coder_33b": 33.3e9, "grok_1_314b": 316e9,
        "deepseek_v3_671b": 671e9, "llama3_2_vision_90b": 87.6e9,
        "musicgen_medium": 1.38e9,
    }
    for arch, want in expected.items():
        cfg = configs.get_config(arch)
        shapes = model_lib.abstract_params(cfg)
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert abs(n - want) / want < 0.02, (arch, n, want)


def test_layer_counts():
    for arch in ARCHS:
        cfg = configs.get_config(arch)
        assert cfg.num_layers == {
            "gemma_7b": 28, "gemma2_27b": 46, "llama3_2_1b": 16,
            "deepseek_coder_33b": 62, "zamba2_2_7b": 54,
            "grok_1_314b": 64, "deepseek_v3_671b": 61, "xlstm_350m": 24,
            "llama3_2_vision_90b": 100, "musicgen_medium": 48}[arch]


def test_gemma2_softcap_applied(rng):
    cfg = configs.get_config("gemma2_27b", smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    toks, _ = _inputs(cfg, 1, 16, rng)
    logits, _ = model_lib.forward(params, cfg, toks)
    assert float(jnp.max(jnp.abs(logits))) <= cfg.final_softcap + 1e-3


def test_moe_router_bias_is_selection_only(rng):
    """DSv3 aux-free bias: changing the bias changes *selection* but never
    receives gradient."""
    from repro.train.train_step import make_loss_fn
    cfg = configs.get_config("deepseek_v3_671b", smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    toks, _ = _inputs(cfg, 2, 16, rng)
    loss_fn = make_loss_fn(cfg)
    g = jax.grad(lambda p: loss_fn(p, {"tokens": toks, "labels": toks})[0])(
        params)
    for gi, group in enumerate(g["groups"]):
        for slot in group["slots"]:
            mlp = slot.get("mlp", {})
            if isinstance(mlp, dict) and "router" in mlp \
                    and "bias" in mlp["router"]:
                assert float(jnp.max(jnp.abs(mlp["router"]["bias"]))) == 0.0


def test_mla_absorbed_matches_baseline(rng):
    """The weight-absorbed MLA decode path (beyond-paper opt) must equal
    the naive K/V-expanding formulation."""
    cfg = configs.get_config("deepseek_v3_671b", smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    cache_a = model_lib.init_cache(cfg, 2, 32)
    cache_b = model_lib.init_cache(cfg, 2, 32)
    la, ca = model_lib.prefill(params, cfg, toks, cache_a,
                               mla_absorbed=False)
    lb, cb = model_lib.prefill(params, cfg, toks, cache_b,
                               mla_absorbed=True)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(la),
                               rtol=2e-4, atol=2e-4)
    pos = jnp.full((2,), 16, jnp.int32)
    nxt = toks[:, :1]
    da, _ = model_lib.decode_step(params, cfg, nxt, ca, pos,
                                  mla_absorbed=False)
    db, _ = model_lib.decode_step(params, cfg, nxt, cb, pos,
                                  mla_absorbed=True)
    np.testing.assert_allclose(np.asarray(db), np.asarray(da),
                               rtol=2e-4, atol=2e-4)


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, those nested in others too."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _scans(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _scans(sub)


@pytest.mark.parametrize("path", ["prefill", "decode_step"])
@pytest.mark.parametrize("arch", ["deepseek_coder_33b", "zamba2_2_7b",
                                  "gemma2_27b", "deepseek_v3_671b",
                                  "llama3_2_vision_90b"])
def test_cache_is_carried_not_scanned(arch, path, rng):
    """No scan of the traced prefill or decode step takes an array with
    the cache's time axis as a scanned input or gives one as a stacked
    output, and every cache leaf is among the carries of the layer scan:
    the stacked cache is updated in place, not sliced out and back."""
    cfg = configs.get_config(arch, smoke=True)
    b, max_len, s = 2, 23, 11
    params = model_lib.abstract_params(cfg)
    cache = model_lib.abstract_cache(cfg, b, max_len)
    if path == "prefill":
        toks, img = _inputs(cfg, b, s, rng)
        closed = jax.make_jaxpr(
            lambda p, t, c: model_lib.prefill(p, cfg, t, c, img))(
            params, toks, cache)
    else:
        toks, _ = _inputs(cfg, b, 1, rng)
        closed = jax.make_jaxpr(
            lambda p, t, c, q: model_lib.decode_step(p, cfg, t, c, q))(
            params, toks, cache, jnp.full((b,), s, jnp.int32))
    scans = list(_scans(closed.jaxpr))
    assert scans
    carried = set()
    for eqn in scans:
        n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = eqn.invars[n_const + n_carry:]
        ys = eqn.outvars[n_carry:]
        for var in (*xs, *ys):
            assert max_len not in var.aval.shape, (var.aval, eqn)
        carried |= {(v.aval.shape, v.aval.dtype)
                    for v in eqn.invars[n_const:n_const + n_carry]}
    leaves = jax.tree.leaves(cache)
    assert any(max_len in a.shape for a in leaves)
    for a in leaves:
        assert (a.shape, a.dtype) in carried, a


def _per_layer_run(params, cfg, tokens, cache, positions, img):
    """The layer stack as a plain loop: each layer's params and cache are
    sliced out of the stacks, run through ``blocks.apply_layer`` (the cache
    as a stack of one) and the caches stacked again."""
    from repro.models import blocks
    from repro.models.common import rmsnorm
    x = model_lib._embed(params, cfg, tokens)
    ctx = {"positions": positions, "image_embeds": img}
    new_groups = []
    for gspec, gp, gc in zip(cfg.groups, params["groups"], cache):
        slots = [[] for _ in gspec.pattern]
        for r in range(gspec.repeat):
            for i, spec in enumerate(gspec.pattern):
                p = gp["slots"][i]
                if not spec.shared:
                    p = jax.tree.map(lambda a: a[r], p)
                c = jax.tree.map(lambda a: a[r:r + 1], gc["slots"][i])
                x, nc, _ = blocks.apply_layer(p, cfg, spec, x, ctx,
                                              c or None, 0)
                slots[i].append(jax.tree.map(lambda a: a[0], nc or {}))
        new_groups.append({"slots": tuple(
            jax.tree.map(lambda *a: jnp.stack(a), *s) if s[0] else {}
            for s in slots)})
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    return model_lib._head(params, cfg, x[:, -1:]), new_groups


@pytest.mark.parametrize("arch", ["deepseek_coder_33b", "gemma2_27b",
                                  "deepseek_v3_671b", "zamba2_2_7b",
                                  "xlstm_350m", "llama3_2_vision_90b"])
def test_carried_cache_matches_per_layer_loop(arch, rng):
    """prefill then two decode steps, two sequences at different
    positions, give the logits and the cache leaves of a per-layer loop
    that slices each layer's cache out and stacks the results."""
    cfg = configs.get_config(arch, smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    b, s, max_len = 2, 12, 24
    toks, img = _inputs(cfg, b, s + 2, rng)
    cache = model_lib.init_cache(cfg, b, max_len)
    ref_cache = cache

    got, cache = jax.jit(lambda p, t, c: model_lib.prefill(
        p, cfg, t, c, img))(params, toks[:, :s], cache)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    want, ref_cache = jax.jit(lambda p, t, c: _per_layer_run(
        p, cfg, t, c, pos, img))(params, toks[:, :s], ref_cache)
    steps = [(got, want)]
    decode = jax.jit(lambda p, t, c, q: model_lib.decode_step(p, cfg, t, c, q))
    ref_decode = jax.jit(lambda p, t, c, q: _per_layer_run(
        p, cfg, t, c, q[:, None], None))
    for j in range(2):
        q = jnp.asarray([s + j, s - 5 + j], jnp.int32)
        t = toks[:, s + j:s + j + 1]
        got, cache = decode(params, t, cache, q)
        want, ref_cache = ref_decode(params, t, ref_cache, q)
        steps.append((got, want))
    for got, want in steps:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    assert (jax.tree.structure(cache) == jax.tree.structure(ref_cache))
    for a, w in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        assert a.shape == w.shape and a.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)
