"""Run one cell of the on-chip serving benchmark.

    python3 -m benchmarks.onchip.run --workload smollm360m.chat \
        --seed 12345 --seconds 30 --trace 0

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  It refuses (exit 2, no result) where JAX finds no TPU or
fewer chips than the cell asks for, and where the program under test is
not beside it.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; the numbers that
decided ``correct`` come last, under ``checks``, and again as the last
lines of standard error.  Earlier lines on standard error give set-up's
phases, the generator's lateness and the compiles inside the loop.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def refuse(msg) -> int:
    print(f"onchip: {msg}", file=sys.stderr)
    return 2


def clean(x):
    """Non-finite floats are not JSON: drop NaN metrics, cap infinities."""
    if isinstance(x, dict):
        return {k: clean(v) for k, v in x.items()
                if not (isinstance(v, dict) and isinstance(v.get("value"),
                                                           float)
                        and math.isnan(v["value"]))}
    if isinstance(x, list):
        return [clean(v) for v in x]
    if isinstance(x, float) and math.isinf(x):
        return math.copysign(1e308, x)
    return x


def enable_cache():
    """The persistent compilation cache, at a fixed path in the checkout
    (the path is part of the key), holding every program however fast it
    compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default="",
                    help="with --trace 1, also save the reduced trace "
                         "events (gzipped JSON) here, as a reduction "
                         "test's input")
    return ap.parse_args(argv)


def devices_for(cell):
    """The chips the cell runs on, or None where the machine lacks them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        return None
    return devs[:cell.chips]


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return refuse(f"the program (src/repro) is not in {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.onchip import spec
    cell = spec.load_cell(args.workload)
    enable_cache()
    import jax
    devices = devices_for(cell)
    if devices is None:
        d = jax.devices()
        return refuse(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                      f"JAX found {len(d)} {d[0].platform} device(s)")
    from benchmarks.onchip import driver
    result = driver.run_cell(cell, args.seed, args.seconds, args.trace,
                             t_start=T_START, devices=devices,
                             dump_events=args.dump_events)
    checks = result.pop("checks")
    result["checks"] = checks            # last key of the line
    print(json.dumps(clean(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
