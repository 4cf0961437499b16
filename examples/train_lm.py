"""End-to-end training driver: train an LM with the full substrate
(prefetched data, jitted train step, async checkpointing, eval hooks,
exact restart).

Default is a quick ~1-minute run on a reduced llama3.2 config; pass
``--model-dim 768 --layers 12 --steps 300`` for a ~100M-param run (slow on
1 CPU core — the configuration is the point, the wall-clock is not).

    PYTHONPATH=src python examples/train_lm.py [--steps N] [--resume]
"""
import argparse
import dataclasses

from repro import configs
from repro.launch import compile_cache
from repro.models.config import LayerSpec, uniform_groups
from repro.train.optimizer import make_optimizer
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--model-dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    base = configs.get_config("llama3.2-1b", smoke=True)
    cfg = dataclasses.replace(
        base,
        name=f"train-lm-{args.model_dim}d{args.layers}L",
        groups=uniform_groups(args.layers, LayerSpec(kind="attn",
                                                     mlp="glu")),
        d_model=args.model_dim,
        num_heads=max(args.model_dim // 64, 4),
        num_kv_heads=max(args.model_dim // 128, 2),
        head_dim=64 if args.model_dim >= 256 else 16,
        d_ff=args.model_dim * 4,
        vocab_size=32000 if args.model_dim >= 512 else 2048,
    )
    import jax, numpy as np
    from repro.models import model as model_lib
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(model_lib.abstract_params(cfg)))
    print(f"model: {cfg.name} ({n/1e6:.1f}M params)")

    tr = Trainer(cfg, TrainerConfig(
        steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        ckpt_every=max(args.steps // 4, 10),
        eval_every=max(args.steps // 2, 10),
        ckpt_dir=args.ckpt_dir, log_every=5),
        optimizer=make_optimizer("adamw", lr=1e-3, warmup=10))
    if args.resume and tr.maybe_restore():
        print(f"resumed from step {tr.step}")
    hist = tr.train()
    print(f"\ndone: {len(hist)} steps, final loss {hist[-1]['loss']:.4f} "
          f"(first {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
