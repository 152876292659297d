"""The reduction from trace events to busy time, idle gaps, kernel and
program time, on a small hand-made trace."""
import pytest

from benchmarks.onchip import trace

MS = 1_000_000


def _events():
    # window 0..100 ms; two decode programs, one prefill program
    modules = [["jit__step_impl_paged(1)", 10 * MS, 30 * MS],
               ["jit_run(2)", 40 * MS, 50 * MS],
               ["jit__step_impl_paged(1)", 60 * MS, 80 * MS]]
    ops = [["paged_gqa_decode", "", 10 * MS, 25 * MS],
           ["copy", "", 25 * MS, 30 * MS],
           ["linear_scan", "", 40 * MS, 50 * MS],
           ["paged_gqa_decode", "", 60 * MS, 75 * MS],
           ["copy", "", 75 * MS, 80 * MS],
           ["late", "", 95 * MS, 110 * MS]]       # runs past the window
    host = [["bench.step", 6 * MS, 32 * MS],
            ["layer.decode_step", 8 * MS, 31 * MS],
            ["bench.idle", 32 * MS, 39 * MS],
            ["bench.step", 39 * MS, 90 * MS]]
    ev = trace.Events(window=(0, 100 * MS), ops=ops, modules=modules,
                      host=host)
    trace._assign_modules(ev.ops, ev.modules)
    return ev


def test_busy_and_idle():
    ev = _events()
    assert trace.busy_s(ev) == pytest.approx(0.055)
    gaps = trace.idle_gaps(ev)
    assert [(a // MS, b // MS) for a, b in gaps] == \
        [(0, 10), (30, 40), (50, 60), (80, 95)]
    by = dict(trace.idle_by_host_span(ev))
    assert by["other"] == pytest.approx(0.010)       # 0..10: before a step
    assert by["bench.idle"] == pytest.approx(0.010)  # 30..40, mid 35
    assert by["bench.step"] == pytest.approx(0.025)  # 50..60, 80..95


def test_kernel_and_program_time():
    ev = _events()
    assert trace.op_seconds(ev, "paged_gqa_decode") == pytest.approx(0.030)
    assert trace.op_seconds(ev, "late") == pytest.approx(0.005)
    assert trace.module_seconds(ev, ("step_impl",)) == \
        pytest.approx([0.020, 0.020])
    top = trace.top_ops(ev)
    assert top[0][0] == "jit__step_impl_paged/paged_gqa_decode"
    assert top[0][1] == pytest.approx(0.030)


def test_json_round_trip(tmp_path):
    ev = _events()
    ev.save(tmp_path / "ev.json.gz")
    back = trace.Events.load(tmp_path / "ev.json.gz")
    assert back.window == ev.window
    assert trace.busy_s(back) == trace.busy_s(ev)


def test_recorded_trace_slice():
    """A 14 ms slice of a traced mingru360m.longdoc run on one v5e chip:
    one decode step and the next prefill chunk, with the benchmark's host
    spans on the same clock."""
    import pathlib
    ev = trace.Events.load(pathlib.Path(__file__).parent / "data" /
                           "mingru_longdoc_slice.json.gz")
    # the device is busy inside the programs, and the host spans bracket
    # the programs they dispatched
    assert trace.busy_s(ev) == pytest.approx(0.009523447)
    step = [m for m in ev.modules if "step_impl" in m[0]][0]
    outer = [h for h in ev.host if h[0] == "bench.step"
             and h[1] <= step[1] and step[2] <= h[2]]
    assert outer, "the decode program runs inside the step that issued it"
    assert trace.module_seconds(ev, ("step_impl",)) == \
        pytest.approx([0.00432781])
    assert trace.module_seconds(ev, ("jit_run",)) == \
        pytest.approx([0.005111553])
    # kernel time counts the kernel's own ops, not fusions that read it
    assert trace.op_seconds(ev, "linear_scan") == pytest.approx(0.000245733)
    top = dict(trace.top_ops(ev))
    assert top["jit_run/convert"] == pytest.approx(0.002858969)
    assert all(n.split("/")[0] in {"jit_run", "jit__step_impl", "jit__pad",
                                   "jit_dynamic_slice",
                                   "jit_convert_element_type"}
               for n in top)
    gaps = dict(trace.idle_by_host_span(ev))
    assert set(gaps) <= {"bench.step", "bench.record", "bench.submit",
                         "bench.idle", "layer.prefill", "layer.admit",
                         "layer.decode_step", "layer.write_slots",
                         "layer.sample", "other"}
    assert sum(gaps.values()) == pytest.approx(ev.window_s - trace.busy_s(ev))
