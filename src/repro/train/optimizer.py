"""Optimizers: AdamW, Adafactor (factored second moments — the
memory-frugal choice for the 300B+ archs), and Lion.

Functional API: ``opt.init(params) -> state``; ``opt.apply(params, grads,
state) -> (new_params, new_state, metrics)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup: int = 100
    decay_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule(c: OptConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(c.warmup, 1), 1.0)
    t = jnp.clip((step - c.warmup) / jnp.maximum(c.decay_steps - c.warmup, 1),
                 0.0, 1.0)
    cos = c.min_lr_frac + (1 - c.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return c.lr * warm * cos


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), tree), norm


def _map_unzip(fn, *trees):
    """Map ``fn`` (returning a tuple) over leaves and unzip into one tree
    per output.  Flatten-based, so tuples *inside* the tree structure (e.g.
    group slots) never get mistaken for packed leaves."""
    flat0, tree = jax.tree_util.tree_flatten(trees[0])
    rest = [tree.flatten_up_to(t) for t in trees[1:]]
    outs = [fn(*xs) for xs in zip(flat0, *rest)]
    width = len(outs[0]) if outs else 0
    return tuple(tree.unflatten([o[i] for o in outs])
                 for i in range(width))


class Optimizer:
    def __init__(self, cfg: OptConfig):
        self.cfg = cfg

    def init(self, params) -> Any:
        raise NotImplementedError

    def apply(self, params, grads, state) -> tuple[Any, Any, dict]:
        raise NotImplementedError


class AdamW(Optimizer):
    def init(self, params):
        zeros = lambda t: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), t)
        return {"m": zeros(params), "v": zeros(params),
                "step": jnp.zeros((), jnp.int32)}

    def apply(self, params, grads, state):
        c = self.cfg
        step = state["step"] + 1
        grads, gnorm = clip_by_global_norm(grads, c.grad_clip)
        lr = schedule(c, step)
        bc1 = 1 - c.b1 ** step.astype(jnp.float32)
        bc2 = 1 - c.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            m = c.b1 * m + (1 - c.b1) * g
            v = c.b2 * v + (1 - c.b2) * jnp.square(g)
            u = (m / bc1) / (jnp.sqrt(v / bc2) + c.eps)
            u = u + c.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v

        new_params, new_m, new_v = _map_unzip(upd, params, grads,
                                              state["m"], state["v"])
        return (new_params, {"m": new_m, "v": new_v, "step": step},
                {"grad_norm": gnorm, "lr": lr})


class Adafactor(Optimizer):
    """Momentum-free Adafactor with factored second moments for rank>=2."""

    def init(self, params):
        def stat(p):
            if len(p.shape) >= 2:
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"stats": jax.tree.map(stat, params),
                "step": jnp.zeros((), jnp.int32)}

    def apply(self, params, grads, state):
        c = self.cfg
        step = state["step"] + 1
        grads, gnorm = clip_by_global_norm(grads, c.grad_clip)
        lr = schedule(c, step)
        decay = 1.0 - (step.astype(jnp.float32) + 1.0) ** -0.8

        def upd(p, g, s):
            g2 = jnp.square(g) + 1e-30
            if "vr" in s:
                vr = decay * s["vr"] + (1 - decay) * jnp.mean(g2, axis=-1)
                vc = decay * s["vc"] + (1 - decay) * jnp.mean(g2, axis=-2)
                denom = jnp.sqrt(
                    vr[..., None] * vc[..., None, :]
                    / (jnp.mean(vr, axis=-1, keepdims=True)[..., None]
                       + 1e-30))
                u = g / (denom + c.eps)
                ns = {"vr": vr, "vc": vc}
            else:
                v = decay * s["v"] + (1 - decay) * g2
                u = g / (jnp.sqrt(v) + c.eps)
                ns = {"v": v}
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
            u = u / jnp.maximum(1.0, rms)  # update clipping (RMS <= 1)
            u = u + c.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype), ns

        new_params, new_stats = _map_unzip(upd, params, grads,
                                           state["stats"])
        return (new_params, {"stats": new_stats, "step": step},
                {"grad_norm": gnorm, "lr": lr})


class Lion(Optimizer):
    def init(self, params):
        return {"m": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}

    def apply(self, params, grads, state):
        c = self.cfg
        step = state["step"] + 1
        grads, gnorm = clip_by_global_norm(grads, c.grad_clip)
        lr = schedule(c, step)

        def upd(p, g, m):
            u = jnp.sign(c.b1 * m + (1 - c.b1) * g)
            u = u + c.weight_decay * p.astype(jnp.float32)
            m2 = c.b2 * m + (1 - c.b2) * g
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m2

        new_params, new_m = _map_unzip(upd, params, grads, state["m"])
        return (new_params, {"m": new_m, "step": step},
                {"grad_norm": gnorm, "lr": lr})


def make_optimizer(name: str, **kw) -> Optimizer:
    cfg = OptConfig(name=name, **kw)
    return {"adamw": AdamW, "adafactor": Adafactor,
            "lion": Lion}[name](cfg)
