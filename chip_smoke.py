"""Run the system's main path once on one TPU chip and check what comes out.

    python chip_smoke.py [--seed N]

Everything runs in this one process, since a chip belongs to one process
at a time:

1. kernels: the Pallas flash-attention, decode-attention and RMSNorm
   kernels, compiled for the chip at llama3.2-1b widths, each against
   ``repro.kernels.ref`` (allclose with rtol = atol = ``KERNEL_TOL``).
2. serve: the published llama3.2-1b (16 layers, d=2048, V=128256, bf16,
   random weights made on the device from the seed) behind a
   ``ServingEngine`` (max_batch 8, max_len 256) on a thread-runtime
   ``Cluster``.  16 requests with 8-120 prompt tokens, drawn inside two
   prefill buckets, ask for 16 new tokens each.  Every request must
   finish, and its tokens must match a batch-1 reference that calls
   ``model.prefill`` and ``model.decode_step`` directly.  bf16 can flip
   an argmax where two logits nearly tie, so a mismatch is allowed only
   where the reference's top-1/top-2 logit margin at that step is under
   ``TIE_ULPS`` bf16 ulps of its top logit; that request is compared no
   further.  The phase runs once with the ops layer's default ``ref``
   implementation and once with the Pallas kernels.
3. train: 5 ``MicrobatchCoordinator`` steps (4 microbatches, so each
   step is a task graph on the ``Cluster``) at llama3.2-1b widths with
   the depth cut to 2 layers, on one repeated batch.  The loss must be
   finite and must fall.

With no TPU, or without the repository's ``src/`` beside it, the script
exits nonzero and prints no result; so does any failed phase.  The last
line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

KERNEL_TOL = 2e-2          # bf16 kernel vs reference (rtol and atol)
TIE_ULPS = 8               # near-tie margin, in bf16 ulps of the top logit
# prompt lengths per prefill bucket: s - 1 tokens are prefilled, so these
# pad to the engine's 16- and 128-token buckets
PROMPT_LENGTHS = ((8, 17), (66, 120))
MAX_BATCH, MAX_LEN = 8, 256
N_REQUESTS, MAX_NEW = 16, 16
TRAIN_LAYERS, TRAIN_STEPS, N_MICRO = 2, 5, 4
TRAIN_BATCH, TRAIN_SEQ = 8, 128
WAIT_S = 600.0             # per serving run, compilation included


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# phase 1: kernels against their references
# ---------------------------------------------------------------------------

def kernel_phase(cfg, *, seed: int, interpret: bool = False) -> dict:
    """Each Pallas kernel of the attention path against kernels/ref.py at
    ``cfg``'s widths.  Returns the largest absolute error per kernel."""
    import jax
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm

    h, kv, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.d_model
    dt = cfg.compute_dtype
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    scale = 1.0 / math.sqrt(hd)
    s = 128
    q = jax.random.normal(ks[0], (1, s, h, hd), dt)
    k = jax.random.normal(ks[1], (1, s, kv, hd), dt)
    v = jax.random.normal(ks[2], (1, s, kv, hd), dt)
    qd = jax.random.normal(ks[3], (MAX_BATCH, 1, h, hd), dt)
    kc = jax.random.normal(ks[4], (MAX_BATCH, MAX_LEN, kv, hd), dt)
    vc = jax.random.normal(ks[5], (MAX_BATCH, MAX_LEN, kv, hd), dt)
    lengths = jax.random.randint(ks[6], (MAX_BATCH,), 1, MAX_LEN + 1)
    x = jax.random.normal(ks[7], (MAX_BATCH, s, d), dt)
    w = jax.random.normal(ks[0], (d,), dt) * 0.1
    cases = {
        "flash_attention": (
            flash_attention(q, k, v, scale=scale, interpret=interpret),
            ref.flash_attention(q, k, v, scale=scale)),
        "decode_attention": (
            decode_attention(qd, kc, vc, lengths=lengths, scale=scale,
                             interpret=interpret),
            ref.decode_attention(qd, kc, vc, lengths=lengths, scale=scale)),
        "rmsnorm": (rmsnorm(x, w, interpret=interpret), ref.rmsnorm(x, w)),
    }
    errs = {}
    for name, (got, want) in cases.items():
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            raise SmokeFailure(f"{name}: shape {got.shape} vs {want.shape}"
                               " or non-finite output")
        errs[name] = float(np.max(np.abs(got - want)))
        if not np.allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL):
            raise SmokeFailure(f"{name}: max |kernel - ref| = "
                               f"{errs[name]} beyond rtol=atol={KERNEL_TOL}")
    return errs


# ---------------------------------------------------------------------------
# phase 2: serving through ServingEngine / Cluster against a batch-1 reference
# ---------------------------------------------------------------------------

def make_params(cfg, seed: int):
    """Random weights, made on the device from ``seed``."""
    import jax
    from repro.models import model as model_lib
    return jax.jit(functools.partial(model_lib.init_params, cfg=cfg))(
        jax.random.PRNGKey(seed))


def make_prompts(cfg, seed: int, n: int = N_REQUESTS) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        lo, hi = PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)]
        out.append(rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(lo, hi + 1)),
                                dtype=np.int32))
    return out


def _bf16_ulp(x: float) -> float:
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def reference_generate(cfg, params, prompts, max_new: int) -> list:
    """Greedy tokens per prompt from model.prefill + model.decode_step at
    batch 1, each with the near-tie tolerance its step earns:
    ``[(tokens, ties)]`` where ``ties[j]`` says the top-1/top-2 margin
    at step j is under ``TIE_ULPS`` bf16 ulps of the top logit."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    prefill = jax.jit(
        lambda p, t, c: model_lib.prefill(p, cfg, t, c)[1])

    @jax.jit
    def decode(p, t, c, pos):
        logits, c = model_lib.decode_step(p, cfg, t, c, pos)
        vals, idx = jax.lax.top_k(logits[0, 0].astype(jnp.float32), 2)
        return vals, idx[0], c

    out = []
    for prompt in prompts:
        cache = model_lib.init_cache(cfg, 1, MAX_LEN)
        cache = prefill(params, jnp.asarray(prompt[None, :-1]), cache)
        cur, pos = int(prompt[-1]), len(prompt) - 1
        toks, ties = [], []
        for _ in range(max_new):
            vals, top, cache = decode(params, jnp.asarray([[cur]], jnp.int32),
                                      cache, jnp.asarray([pos], jnp.int32))
            v1, v2 = (float(x) for x in vals)
            cur = int(top)
            toks.append(cur)
            ties.append(v1 - v2 < TIE_ULPS * _bf16_ulp(v1))
            pos += 1
        out.append((toks, ties))
    return out


def compare(got: list, want: list, ties: list) -> tuple[str, int]:
    """('match' | 'tie' | 'mismatch', tokens compared)."""
    if len(got) != len(want):
        return "mismatch", 0
    for j, (g, w) in enumerate(zip(got, want)):
        if g != w:
            return ("tie" if ties[j] else "mismatch"), j
    return "match", len(want)


def serve_phase(cfg, params, prompts, reference, *, impl: str = "ref",
                interpret: bool = False, max_new: int = MAX_NEW,
                wait_s: float = WAIT_S) -> dict:
    """Serve ``prompts`` through ServingEngine with ops implementation
    ``impl`` and compare every request with ``reference``."""
    from repro.kernels import ops
    from repro.serve.engine import ServingEngine

    ops.set_impl(impl, interpret=interpret)
    try:
        eng = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                            max_len=MAX_LEN)
        eng.start()
        t0 = time.perf_counter()
        try:
            reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
            deadline = t0 + wait_s
            unfinished = [r.rid for r in reqs if not r.done.wait(
                max(deadline - time.perf_counter(), 0.0))]
            wall = time.perf_counter() - t0
        finally:
            eng.stop()
    finally:
        ops.set_impl("ref")
    if eng.error is not None:
        raise SmokeFailure(f"serve[{impl}]: engine failed: "
                           f"{eng.error!r}") from eng.error
    if unfinished:
        raise SmokeFailure(f"serve[{impl}]: requests {unfinished} did not "
                           f"finish within {wait_s}s")
    counts = {"match": 0, "tie": 0, "mismatch": 0}
    compared = 0
    bad = []
    for r, (want, ties) in zip(reqs, reference):
        verdict, n = compare(r.out_tokens, want, ties)
        counts[verdict] += 1
        compared += n
        if verdict == "mismatch":
            bad.append((r.rid, n, r.out_tokens, want))
    if bad:
        raise SmokeFailure(f"serve[{impl}]: {len(bad)} request(s) differ "
                           "from the reference away from a near-tie; first "
                           f"(rid, step, got, want): {bad[0]}")
    return {"requests": len(reqs), "tokens": eng.n_generated,
            "decode_steps": eng.n_decode_steps, "matched": counts["match"],
            "tie_stopped": counts["tie"], "tokens_compared": compared,
            "wall_s": wall}


# ---------------------------------------------------------------------------
# phase 3: microbatch training through the Cluster
# ---------------------------------------------------------------------------

def train_config(cfg, layers: int = TRAIN_LAYERS):
    """``cfg`` at its own widths with the depth cut to ``layers``."""
    g = cfg.groups[0]
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-{layers}L",
        groups=(dataclasses.replace(g, repeat=layers // len(g.pattern)),))


def train_phase(cfg, *, seed: int, steps: int = TRAIN_STEPS,
                n_micro: int = N_MICRO, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ) -> dict:
    """``steps`` MicrobatchCoordinator steps on one repeated batch."""
    import numpy as np
    from repro.data.pipeline import SyntheticDataset
    from repro.train.optimizer import make_optimizer
    from repro.train.trainer import MicrobatchCoordinator

    mc = MicrobatchCoordinator(
        cfg, n_executors=n_micro, n_microbatches=n_micro, seed=seed,
        optimizer=make_optimizer("adamw", lr=1e-3, warmup=1,
                                 weight_decay=0.0))
    data = SyntheticDataset(cfg, batch, seq, seed).batch_at(0)
    try:
        param_bytes = tree_bytes(mc.params)
        losses = []
        for _ in range(steps):
            r = mc.train_step(data)
            if r["timed_out"] or r["loss"] is None:
                raise SmokeFailure(f"train step {r['step']} timed out")
            losses.append(r["loss"])
    finally:
        mc.close()
    if not all(np.isfinite(losses)):
        raise SmokeFailure(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"train: loss did not fall {losses}")
    return {"losses": losses, "param_bytes": param_bytes}


# ---------------------------------------------------------------------------

def run(seed: int) -> None:
    import jax
    from repro import configs
    from repro.launch import compile_cache

    cfg = configs.get_config("llama3.2-1b")

    def phase(name, fn, *a, show=True, **kw):
        c0 = compile_cache.stats()
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        c1 = compile_cache.stats()
        if show:
            print(f"{name}: {out}")
        print(f"{name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{c1['compiles'] - c0['compiles']} compiles "
              f"({c1['compile_s'] - c0['compile_s']:.1f}s), "
              f"peak_bytes_in_use={peak_bytes()}", flush=True)
        return out

    phase("kernels", kernel_phase, cfg, seed=seed)

    params = make_params(cfg, seed)
    jax.block_until_ready(params)
    print(f"serve: {cfg.name} {cfg.num_layers}L d={cfg.d_model} "
          f"V={cfg.vocab_size} {cfg.dtype}, params "
          f"{tree_bytes(params)} bytes", flush=True)
    prompts = make_prompts(cfg, seed)
    print(f"serve: prompt lengths {[len(p) for p in prompts]}, "
          f"max_new_tokens={MAX_NEW}, max_batch={MAX_BATCH}, "
          f"max_len={MAX_LEN}, tie margin {TIE_ULPS} bf16 ulps")
    reference = phase("reference", reference_generate, cfg, params,
                      prompts, MAX_NEW, show=False)
    print(f"reference: {sum(any(t) for _, t in reference)} of "
          f"{len(reference)} requests meet a near-tie step")
    for impl in ("ref", "pallas"):
        phase(f"serve[{impl}]", serve_phase, cfg, params, prompts,
              reference, impl=impl)
    del params, reference
    gc.collect()

    tcfg = train_config(cfg)
    print(f"train: depth cut {cfg.num_layers} -> {tcfg.num_layers} layers "
          "at full width (AdamW moments plus one gradient per microbatch "
          "do not fit 16 GB at full depth); bytes_in_use before: "
          f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}",
          flush=True)
    phase("train", train_phase, tcfg, seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the serving and training paths once on one TPU "
                    "chip and check their results.")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the program is not here ({SRC / 'repro'})",
              file=sys.stderr)
        return 1
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev['platform']} "
              f"({dev['kind']})", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    print(f"device: {dev}", flush=True)
    try:
        run(args.seed)
    except Exception as exc:
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    st = compile_cache.stats()
    print(f"compile cache: {cache_dir}, hits={st['hits']} "
          f"misses={st['misses']}, {st['compiles']} backend compiles "
          f"({st['compile_s']:.1f}s)")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
