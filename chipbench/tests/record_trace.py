"""Record a small profiler trace on the chip, with the benchmark's window
span, for the trace-reduction test: a named jitted program run a few times
inside the window, with idle time between the runs.

    python3 chipbench/tests/record_trace.py <out_dir>
"""
import sys
import time

import jax
import jax.numpy as jnp

import smoke  # noqa: F401
import tracereduce


def main(out_dir: str) -> None:
    @jax.jit
    def decode_fn(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    decode_fn(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
        for _ in range(3):
            time.sleep(0.002)
            with jax.profiler.TraceAnnotation("host-work"):
                decode_fn(x).block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = tracereduce.find_xplane(out_dir)
    for plane in ProfileData.from_file(path).planes:
        lines = {ln.name: sum(1 for _ in ln.events) for ln in plane.lines}
        print(plane.name, lines)
    red = tracereduce.reduce(path)
    print("window_s", red.window_s, "busy_s", tracereduce.busy_ns(red) * 1e-9)
    print("modules", sorted({n for _, _, n in red.modules}))
    print("ops", sorted({n for _, _, n in red.ops})[:20])
    print(tracereduce.breakdown(red))


if __name__ == "__main__":
    main(sys.argv[1])
