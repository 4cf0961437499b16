"""Sweep an open-loop cell's rate to find its knee: the highest rate at
which requests do not pile up for slots over a window.

For each rate and seed, one whole run of the cell (weights, engine,
warm-up, lead-in, a window, the check) with the mix's ``rate_per_s``
replaced, all in one process.  Each row gives the rate, the end-to-end
metrics, how many requests were due in the window and finished in it, and
how many waited for a slot when the window opened and when it closed.

    python3 chipbench/tests/sweep.py --workload <cell> --rates 2,2.5,3 \\
        --seeds 1 --seconds 10 --out sweep.jsonl

runs on the chip; ``--smoke`` runs the cell cut to CPU size.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import smoke
import spec


def sweep(cell: spec.Cell, rates, seeds, seconds: float, device,
          peaks: dict) -> list[dict]:
    import harness
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    rows = []
    for rate in rates:
        at = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                    rate_per_s=rate))
        for seed in seeds:
            result, _ = harness.run(at, bench, seed, seconds, False,
                                    time.perf_counter(), device, peaks)
            gc.collect()
            rows.append(dict(
                {"rate_per_s": rate, "seed": seed,
                 "correct": result["correct"],
                 "finished_in_window": result["attempted"],
                 "memory_peak_bytes": result["device"]["memory_peak_bytes"]},
                **result["load"],
                **{k: v["value"] for k, v in result["metrics"].items()}))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    device = jax.devices()[0]
    if args.smoke:
        cell, peaks = smoke.smoke_cell(args.workload), smoke.PEAKS
    else:
        cell = spec.find_cell(args.workload)
        peaks = spec.load_peaks(device.device_kind)
    if cell.traffic["loop"] != "open":
        print(f"{args.workload} is not an open loop", file=sys.stderr)
        return 2
    rows = sweep(cell, [float(r) for r in args.rates.split(",")],
                 [int(s) for s in args.seeds.split(",")], args.seconds,
                 device, peaks)
    for row in rows:
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(row, workload=args.workload)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
