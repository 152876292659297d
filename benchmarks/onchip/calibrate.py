"""Calibration runs that the benchmark's own runs never make, each in one
process so that set-up is paid once per configuration:

* ``--rates``: the open-loop rate sweep that finds a chat cell's knee
  (no correctness check), in rising order; it stops after the first rate
  that is not sustained;
* ``--seeds``: the program's readings of the compared numbers on many
  seeds, with the lower-precision controls (``--controls int8,fp8``) read
  on the same served tokens;
* ``--seeds`` with ``--control-as-program fp8``: whole runs in which that
  control's tokens are judged in the program's place, whose result line
  has to read ``correct`` false;
* ``--pick-rate SWEEP.jsonl``: no chip; from a sweep's lines, the knee is
  the highest rate that was sustained (no miss, no standing queue when
  the window opened and one that grew by at most two in it, a
  95th-percentile TTFT under 3 s), and the cell's rate
  (``cells/<cell>.json``) becomes four fifths of it.

    python3 -m benchmarks.onchip.calibrate --workload smollm360m.chat \
        --seconds 40 --seeds 11,12,13 --controls int8,fp8

One JSON line per run goes to standard output and, with ``--out``, to a
file.  Off the TPU it refuses, as the benchmark does.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

from benchmarks.onchip import run as run_mod


def sustained(r) -> bool:
    s, m = r["served"], r["metrics"]
    return (s["missed"] == 0 and s["queue_first"] <= 2
            and s["queue_last"] <= s["queue_first"] + 2
            and m.get("ttft_p95_ms", {}).get("value", math.inf) < 3000.0)


def pick_rate(workload: str, sweep: str) -> int:
    from benchmarks.onchip import spec
    with open(sweep) as f:
        rows = [json.loads(line) for line in f]
    for r in rows:
        print(r["rate"], "sustained" if sustained(r) else "not sustained",
              r["served"], {k: v["value"] for k, v in r["metrics"].items()})
    ok = [r["rate"] for r in rows if sustained(r)]
    if not ok:
        print("no rate of the sweep was sustained", file=sys.stderr)
        return 1
    rate = round(0.8 * max(ok), 2)
    path = spec.HERE / "cells" / f"{workload}.json"
    cell = json.loads(path.read_text())
    cell["rate_per_s"] = rate
    path.write_text(json.dumps(cell, indent=2) + "\n")
    print(f"knee {max(ok)} requests/s; rate {rate} written to {path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--pick-rate", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-as-program", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.pick_rate:
        return pick_rate(args.workload, args.pick_rate)
    root = run_mod.ROOT
    sys.path.insert(0, str(root / "src"))
    from benchmarks.onchip import driver, spec
    cell = spec.load_cell(args.workload)
    run_mod.enable_cache()
    devices = run_mod.devices_for(cell)
    if devices is None:
        return run_mod.refuse("calibration needs the cell's TPU chips")
    controls = tuple(c for c in args.controls.split(",") if c)
    runs = []
    if args.rates:
        seed = int(args.seeds.split(",")[0]) if args.seeds else 1
        runs = [(seed, float(r)) for r in args.rates.split(",")]
    else:
        runs = [(int(s), None) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    try:
        for seed, rate in runs:
            if rate is not None:
                cell.cell["rate_per_s"] = rate
            t0 = time.perf_counter()
            res = driver.run_cell(
                cell, seed, args.seconds, 0, t_start=t0, devices=devices,
                controls=controls, check=rate is None,
                control_as_program=args.control_as_program)
            res.update(workload=cell.name, seed=seed, rate=rate,
                       wall_s=time.perf_counter() - t0)
            line = json.dumps(run_mod.clean(res))
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
            if rate is not None and not sustained(res):
                break
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
