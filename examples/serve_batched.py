"""Serve a small model with batched requests: continuous batching over
prefill/decode with the engine's slot-based KV cache.

    PYTHONPATH=src python examples/serve_batched.py

Exits nonzero if any request did not finish or failed.
"""
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models import model as model_lib
from repro.serve.engine import ServingEngine


def main() -> int:
    compile_cache.enable()
    cfg = configs.get_config("llama3.2-1b", smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_batch=8, max_len=256)
    eng.start()

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    size=rng.integers(4, 40)),
                       max_new_tokens=16)
            for _ in range(24)]
    unfinished = [r.rid for r in reqs if not r.done.wait(300)]
    wall = time.perf_counter() - t0
    eng.stop()
    failed = [r.rid for r in reqs if r.error is not None]
    if unfinished or failed:
        print(f"unfinished requests {unfinished}, failed {failed}: "
              f"{eng.error!r}", file=sys.stderr)
        return 1

    lat = [r.finish_t - r.submit_t for r in reqs]
    print(f"served {len(reqs)} requests in {wall:.2f}s "
          f"({eng.n_generated / wall:.1f} tok/s aggregate)")
    print(f"decode steps: {eng.n_decode_steps} "
          f"(batching efficiency {eng.n_generated / eng.n_decode_steps:.2f} "
          f"tokens/step vs 1.0 unbatched)")
    print(f"latency p50={np.percentile(lat, 50)*1e3:.0f}ms "
          f"p95={np.percentile(lat, 95)*1e3:.0f}ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
