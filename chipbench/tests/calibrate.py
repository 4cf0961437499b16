"""Readings that the output check's limit is set from.

For each seed, one whole run of the cell (weights, engine, warm-up,
lead-in, a window, the check) in one process, with the float8 control read
on the same checked requests.  The program's widest logit gap over a dozen
seeds or more gives the limit's lower reading, the control's smallest gap
over its seeds the upper one.  The control is also judged against the
cell's limit by the harness's own verdict, and has to come out as not
correct on every seed: the script exits with 1 where it does not.

    python3 chipbench/tests/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 15 --out calibrate.jsonl

runs on the chip.  ``--smoke`` runs the cell cut to CPU size instead
(``smoke.py``); the tests call :func:`calibrate` so.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import smoke  # noqa: F401  (puts the program and the harness on the path)
import spec


def calibrate(cell: spec.Cell, seeds, seconds: float, device,
              peaks: dict) -> list[dict]:
    import harness
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    rows = []
    for seed in seeds:
        t = time.perf_counter()
        result, _ = harness.run(cell, bench, seed, seconds, False, t,
                                device, peaks, control=True)
        gc.collect()
        r = result["readings"]
        rows.append({
            "seed": seed, "correct": result["correct"],
            "program_gap": r["program"]["max_logit_gap"],
            "program_mismatch": r["program"]["mismatch_share"],
            "control_gap": r["control"]["max_logit_gap"],
            "control_correct": r["control"]["correct"],
            "control_mismatch": r["control"]["mismatch_share"],
            "control_nonfinite": r["control"]["nonfinite"],
            "tokens": r["program"]["tokens"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    device = jax.devices()[0]
    if args.smoke:
        cell, peaks = smoke.smoke_cell(args.workload, "bfloat16"), smoke.PEAKS
    else:
        cell = spec.find_cell(args.workload)
        peaks = spec.load_peaks(device.device_kind)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = calibrate(cell, seeds, args.seconds, device, peaks)
    for row in rows:
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(row, workload=args.workload)) + "\n")
    lower = max(r["program_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "ratio": upper / lower if lower else
                      None}))
    # the control has to come out as not correct on every seed
    return 1 if any(r["control_correct"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
