"""Capture a profiler trace of the window and reduce it to plain events.

The reduction keeps three lists on one clock (nanoseconds):

* ``ops``: device operations (the TPU plane's ``XLA Ops`` line), each
  with the program (``XLA Modules``) it ran in;
* ``modules``: device program executions;
* ``host``: the benchmark's own spans (``bench.*``, ``layer.*``).

Everything the per-layer readers need is computed from these lists by
the functions below, so a recorded trace (``Events.to_json``) can be
reduced again without the chip.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

import jax

WINDOW = "bench.window"
# host spans an idle gap can be attributed to, innermost first
HOST_PREFIXES = ("bench.", "layer.")


@dataclasses.dataclass
class Events:
    window: tuple                 # (start_ns, end_ns) of the traced window
    ops: list                     # [name, module, start_ns, end_ns]
    modules: list                 # [name, start_ns, end_ns]
    host: list                    # [name, start_ns, end_ns]
    devices: int = 1

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(window=tuple(d["window"]), ops=d["ops"],
                   modules=d["modules"], host=d["host"],
                   devices=d.get("devices", 1))

    def save(self, path):
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path) -> "Events":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@contextlib.contextmanager
def capture(out: dict):
    """Trace the body; on exit ``out["events"]`` holds its Events."""
    tmp = tempfile.mkdtemp(prefix="onchip_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
        try:
            out["events"] = load_xplane(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def op_name(hlo: str) -> str:
    """The instruction's own name from an op event's HLO text
    ("%linear_scan.6 = bf16[...] custom-call(...)" -> "linear_scan.6")."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def load_xplane(directory: str) -> Events:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(paths[0])
    ops, modules, host, window = [], [], [], None
    devices = 0
    planes = []
    for plane in pd.planes:
        if _device_plane(plane.name):
            devices += 1
            planes.append(plane.name)
            if devices > 1:        # one-chip cells: the first device only
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [[op_name(e.name), "", e.start_ns, e.end_ns]
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, e.start_ns, e.end_ns])
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    print(f"trace: device planes {planes} (first read), {len(modules)} "
          f"program runs, {len(ops)} ops, {len(host)} benchmark spans",
          file=sys.stderr)
    modules.sort(key=lambda m: m[1])
    _assign_modules(ops, modules)
    return Events(window=window, ops=ops, modules=modules, host=host,
                  devices=max(devices, 1))


def _assign_modules(ops, modules):
    """Name each op's enclosing program execution (ops and modules are
    intervals on one device line, so a sweep suffices)."""
    ops.sort(key=lambda o: o[2])
    j = 0
    for op in ops:
        while j < len(modules) and modules[j][2] < op[2]:
            j += 1
        for m in modules[max(j - 1, 0):j + 1]:
            if m[1] <= op[2] <= m[2]:
                op[1] = m[0].split("(")[0]
                break


def _clip(iv, window):
    a, b = max(iv[0], window[0]), min(iv[1], window[1])
    return (a, b) if b > a else None


def busy_intervals(ev: Events) -> list:
    """Union of the device op intervals inside the window."""
    ivs = sorted(filter(None, (_clip((o[2], o[3]), ev.window)
                               for o in ev.ops)))
    out = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(ev: Events) -> float:
    return sum(b - a for a, b in busy_intervals(ev)) * 1e-9


def idle_gaps(ev: Events) -> list:
    """Idle device intervals inside the window."""
    gaps, t = [], ev.window[0]
    for a, b in busy_intervals(ev):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if ev.window[1] > t:
        gaps.append((t, ev.window[1]))
    return gaps


def idle_by_host_span(ev: Events) -> list:
    """[[host span name, idle seconds]] by the innermost benchmark span
    around each idle gap's midpoint ("other" where none is)."""
    spans = sorted(ev.host, key=lambda h: h[2] - h[1])
    tot = {}
    for a, b in idle_gaps(ev):
        mid = (a + b) // 2
        name = next((h[0] for h in spans if h[1] <= mid <= h[2]), "other")
        tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])


def op_seconds(ev: Events, match=None) -> float:
    """Device seconds of ops whose name contains ``match`` (all ops when
    None), clipped to the window."""
    s = 0
    for name, _m, a, b in ev.ops:
        if match is None or match in name:
            iv = _clip((a, b), ev.window)
            if iv:
                s += iv[1] - iv[0]
    return s * 1e-9


def module_seconds(ev: Events, match) -> list:
    """Durations (s) of program executions whose name contains any of
    ``match`` and that lie inside the window."""
    return [(b - a) * 1e-9 for name, a, b in ev.modules
            if any(m in name for m in match)
            and a >= ev.window[0] and b <= ev.window[1]]


def leaf_ops(ev: Events) -> list:
    """The ops that hold no other op (a loop's event spans its body's)."""
    ops = sorted(ev.ops, key=lambda o: (o[2], -o[3]))
    leaves = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[2] >= o[3]:
            leaves.append(o)
    return leaves


def top_ops(ev: Events, n: int = 10) -> list:
    """[[program/op, seconds]] of the n leaf ops that took most device
    time, by name without the instruction's number."""
    tot = {}
    for name, mod, a, b in leaf_ops(ev):
        name = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() \
            else name
        iv = _clip((a, b), ev.window)
        if iv:
            key = f"{mod}/{name}" if mod else name
            tot[key] = tot.get(key, 0.0) + (iv[1] - iv[0]) * 1e-9
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]
