"""Benchmark harness entry: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (us_per_call = per-task or
per-step microseconds where meaningful)."""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (bench_scaling, bench_scheduler, bench_server,
                            bench_table1, bench_zero_worker)
    suites = [
        ("table1", bench_table1.run),
        ("scheduler(fig2)", bench_scheduler.run),
        ("server(fig3-4)", bench_server.run),
        ("scaling(fig5)", bench_scaling.run),
        ("zero_worker(fig6-8)", bench_zero_worker.run),
    ]
    print("name,us_per_call,derived")
    for name, fn in suites:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # keep the harness robust
            print(f"{name}/ERROR,,{type(e).__name__}:{e}")
            continue
        for r in rows:
            print(",".join(str(x) for x in r))
        print(f"_meta/{name}/wall_s,,{time.time() - t0:.1f}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
