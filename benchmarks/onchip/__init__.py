"""On-chip serving benchmark.

    python3 -m benchmarks.onchip.run --workload smollm360m.chat \
        --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` at the repository root binds a configuration to a
traffic mix as a cell.  Everything else is found by name under this
directory: ``configs/<config>.json`` (sizes and serving knobs),
``references/<config>.py`` (the plain float32 reference),
``traffic/<mix>.json`` (the generator's parameters),
``cells/<cell>.json`` (rate and the correctness limit),
``metrics/<metric>.py`` (one per-layer reader each),
``costs/<kernel>.py`` (a kernel's operations and bytes) and
``peaks.json`` (the chip's peaks by ``device_kind``).
"""
