"""The reduction from a device trace to busy time, kernel time and idle
gaps: exactly on a hand-made trace, and on a small trace recorded on a
TPU v5e (two frames of one 256x320 pair through ``VRRigExecutor``)."""

import json
import os

import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUSTOM = 'custom_call_target="tpu_custom_call"'
INTEGRAL = f"%k.1 = f32[8,16,128] custom-call(f32[8,16,128] %a), {CUSTOM}"
BLUR = (f"%k.2 = (f32[4,5,17], f32[4,5,17]) custom-call(f32[4,5,17] %v, "
        f"f32[4,5,17] %w), {CUSTOM}")

HAND = {
    "devices": {"/device:TPU:0": [
        ["%while.1 = (s32[]) while(%t)", 100, 50],       # holds the next two
        [INTEGRAL, 110, 10],
        ["%fusion.3 = f32[8] fusion(%x)", 130, 15],
        [BLUR, 160, 10],
        ["%fusion.4 = f32[8] fusion(%y)", 165, 20],     # overlaps the blur
        ["%copy.5 = f32[8] copy(%z)", 250, 30],         # ends past the window
    ]},
    "host": [["window", 90, 180], ["frame", 95, 90], ["readback", 185, 40],
             ["wait", 225, 45]],
}


def test_window_is_the_harness_span():
    assert devtrace.window(HAND) == (90, 270)
    with pytest.raises(ValueError):
        devtrace.window({"host": [], "devices": {}})


def test_busy_is_the_union_of_device_intervals_inside_the_window():
    ops = HAND["devices"]["/device:TPU:0"]
    # [100, 150) + [160, 185) + [250, 270) clipped at the window's end
    assert devtrace.busy_ns(ops, 90, 270) == 50 + 25 + 20
    assert devtrace.gaps(ops, 90, 270) == [[90, 100], [150, 160],
                                           [185, 250]]
    idle = 1 - devtrace.busy_ns(ops, 90, 270) / 180
    assert idle == pytest.approx(85 / 180)


def test_kernel_time_is_found_by_the_kernel_signature():
    ops = HAND["devices"]["/device:TPU:0"]
    assert devtrace.kernel_ns(ops, "integral_image", 90, 270) == (10.0, 1)
    assert devtrace.kernel_ns(ops, "bilateral_blur", 90, 270) == (10.0, 1)
    assert devtrace.kernel_ns(ops, "quant_matmul", 90, 270) == (0.0, 0)


def test_gaps_are_labelled_by_what_the_host_was_doing():
    ops = HAND["devices"]["/device:TPU:0"]
    got = devtrace.top_gaps(ops, HAND["host"], 90, 270)
    # [185, 250) overlaps readback by 40 ns and wait by 25 ns
    assert [g[0] for g in got] == ["readback", "frame", "frame"]
    assert [g[1] for g in got] == pytest.approx([65e-9, 10e-9, 10e-9])


def test_top_ops_count_a_loop_by_its_own_time_only():
    ops = HAND["devices"]["/device:TPU:0"]
    top = dict(devtrace.top_ops(ops, 90, 270))
    assert top["%while.1 = (s32[]) while(%t)"] == pytest.approx(25e-9)
    assert top["%copy.5 = f32[8] copy(%z)"] == pytest.approx(30e-9)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_excerpt.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_consistently(recorded):
    w0, w1 = devtrace.window(recorded)
    (plane, ops), = recorded["devices"].items()
    assert plane == "/device:TPU:0"
    busy = devtrace.busy_ns(ops, w0, w1)
    idle = sum(e - s for s, e in devtrace.gaps(ops, w0, w1))
    assert 0 < busy < w1 - w0
    assert busy + idle == pytest.approx(w1 - w0)
    labels = {g[0] for g in devtrace.top_gaps(ops, recorded["host"], w0, w1)}
    assert labels <= {"frame", "readback", "wait", "other"}
    # per frame: D=8 gives nine hypotheses, two chunks of summed-area
    # tables, and two refinement iterations give two blur calls.  The device
    # clock of this trace runs just under a millisecond ahead of the host's,
    # so the first frame's tables fall before the window's host span.
    t_int, n_int = devtrace.kernel_ns(ops, "integral_image", w0, w1)
    t_blur, n_blur = devtrace.kernel_ns(ops, "bilateral_blur", w0, w1)
    assert (n_int, n_blur) == (2, 4)
    assert devtrace.kernel_ns(ops, "integral_image", 0, w1)[1] == 4
    assert 0 < t_int + t_blur < busy


def test_tick_idle_is_device_idle_inside_the_tick_spans():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tick_idle", os.path.join(os.path.dirname(DATA), os.pardir,
                                  "metrics", "tick_idle_ms.fleet.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ops = HAND["devices"]["/device:TPU:0"]
    r = type("Reading", (), {"w0": 90, "w1": 270, "ops": lambda self: ops})()
    r.host = [["window", 90, 180], ["tick", 95, 60], ["tick", 180, 80]]
    # idle [90, 100), [150, 160), [185, 250): 5 + 5 ns in the first tick,
    # 65 ns in the second
    assert reader.read(r) == pytest.approx(75 / 2 * 1e-6)
    r.host = [["window", 90, 180], ["wait", 95, 60]]
    assert reader.read(r) is None
