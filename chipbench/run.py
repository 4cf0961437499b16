"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
the program under test is the checkout's ``src/repro``.  The last line of
standard output is the run's result as one JSON object; the numbers that
the output check compared, each beside its limit, are the last lines of
standard error.

A run needs a TPU: with none, with fewer chips than the cell asks for, or
with a device kind that ``chipbench/peaks.json`` does not list, it prints
no result and exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src'}")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import spec
    try:
        bench = spec.load_json(ROOT / "BENCHMARK.json")
        cell = spec.find_cell(args.workload, bench)
    except (OSError, KeyError, ValueError) as e:
        return _fail(f"cannot read the cell: {e!r}")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        return _fail(f"the cell asks for {cell.chips} chips, "
                     f"JAX found {len(devices)}")
    try:
        peaks = spec.load_peaks(devices[0].device_kind)
    except KeyError as e:
        return _fail(str(e))

    import harness
    result, notes = harness.run(cell, bench, args.seed % 2**64,
                                args.seconds, bool(args.trace), T_START,
                                devices[0], peaks)
    for line in notes:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
