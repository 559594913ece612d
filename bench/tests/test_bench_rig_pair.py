"""The ``rig_pair_4k`` cell's pieces on the CPU at a small size: its system
module through the harness, its limits against the control and a broken
depth map, the depth step's name scopes and ``dispatch`` spans as the
program emits them, and the four scope readers against arithmetic done by
hand."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from bench import progtrace
from bench.tests import cpu_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SCOPES = ("rough", "splat", "refine", "slice")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_rig_pair() -> tuple:
    """vr_rig_pair_4k at ``cpu_cell.small_rig``'s sizes: a strip of the
    camera's full width."""
    small, tr = cpu_cell.small_rig()
    cfg = cpu_cell.load("configs", "vr_rig_pair_4k")
    cfg.update(camera=small["camera"], bssa=small["bssa"])
    return cfg, tr


@pytest.fixture(scope="module")
def run():
    return load(os.path.join(BENCH, "run.py"), "bench_run_rig_pair")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def execute(run, bench, tmp_path, monkeypatch):
    monkeypatch.setattr(run.Context, "memory_peak_bytes", lambda self: 0)
    cfg, tr = small_rig_pair()
    return run.execute(bench, run.cell_of(bench, "rig_pair_4k"), cfg, tr,
                       2 ** 31 + 91, 3.0, False, DEVICE, str(tmp_path))


def test_sound_run_is_correct_and_reports_frames_per_s(run, bench, tmp_path,
                                                        monkeypatch):
    line = execute(run, bench, tmp_path, monkeypatch)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "frames_per_s"}
    assert set(line["checks"]) == {"depth_mean_err_px"}
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_control_is_not_correct():
    sysm = load(os.path.join(BENCH, "systems", "rig_pair.py"), "ctl_pair")
    ctx = cpu_cell.Context(*small_rig_pair(), 2 ** 31 + 92, 3.0)
    ctx.control = True
    numbers = sysm.serve(ctx, sysm.prepare(ctx))["control"]
    assert any(numbers[k] > lim for k, lim in sysm.LIMITS.items()), numbers


def test_altered_depth_is_not_correct(run, bench, tmp_path, monkeypatch):
    from repro.camera.pipelines import VRRigExecutor

    orig = VRRigExecutor.depth_maps

    def altered(self, lefts, rights):
        d = orig(self, lefts, rights)
        return d.at[:, : d.shape[1] // 8].add(2.0)
    monkeypatch.setattr(VRRigExecutor, "depth_maps", altered)
    line = execute(run, bench, tmp_path, monkeypatch)
    assert line["correct"] is False
    assert line["checks"]["depth_mean_err_px"]["value"] > 0.1


# -- what the program emits -------------------------------------------------


def _executor(telemetry=None):
    from repro.camera.bssa import GridSpec
    from repro.camera.pipelines import VRRigExecutor

    return VRRigExecutor(GridSpec(sigma_spatial=8), max_disp=4, n_iters=2,
                         telemetry=telemetry)


def _pairs(n=2, h=24, w=40):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    views = np.floor(rng.random((2, n, h, w)) * 256) / 256
    return jnp.asarray(views[0], jnp.float32), jnp.asarray(views[1],
                                                           jnp.float32)


def test_depth_step_ops_carry_the_four_scopes():
    import re

    lefts, rights = _pairs()
    hlo = _executor()._depth.lower(lefts, rights).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', hlo):
        found.update(progtrace.scopes_of(op_name))
    assert set(SCOPES) <= found, sorted(found)


def test_each_depth_call_is_one_dispatch_span():
    from repro.obs.telemetry import Telemetry

    tel = Telemetry()
    ex = _executor(tel)
    lefts, rights = _pairs()
    for _ in range(3):
        np.asarray(ex.depth_maps(lefts, rights))
    spans = tel.trace.records("dispatch")
    assert [s.name for s in spans] == ["vr_depth"] * 3
    assert all(s.args == {"pairs": 2} and s.dur > 0 for s in spans)
    # the instrumented step returns what the plain one does
    plain = np.asarray(_executor().depth_maps(lefts, rights))
    np.testing.assert_array_equal(np.asarray(ex.depth_maps(lefts, rights)),
                                  plain)


# -- the scope readers on a hand-made trace, times in ns --------------------

SPANS = [["dispatch.vr_depth", 100, 400], ["dispatch.vr_depth", 600, 300]]
OPS = [
    ["%a = rough loop", 110, 100, ("rough",)],     # holds the next one
    ["%b = sad", 120, 60, ("rough", "while", "body")],
    ["%c = scatter", 210, 30, ("splat",)],
    ["%d = blur loop", 240, 40, ("refine",)],      # holds the next one
    ["%e = pallas blur", 245, 30, ("refine", "while", "body")],
    ["%f = gather", 280, 100, ("slice",)],
    ["%g = copy", 380, 20, ()],
    ["%h = slice outside a dispatch", 520, 10, ("slice",)],
    ["%i = sad", 610, 50, ("rough", "while", "body")],
    ["%j = scatter", 660, 20, ("splat",)],
    ["%k = pallas blur", 680, 20, ("refine",)],
    ["%l = gather", 700, 150, ("slice",)],
]
HAND = {"window": (0.0, 1000.0), "spans": SPANS,
        "devices": {"/device:TPU:0": OPS}}
R = types.SimpleNamespace(w0=0.0, w1=1000.0)


def reader(name):
    return load(os.path.join(BENCH, "metrics", name + ".py"),
                "reader_" + name.replace(".", "_"))


@pytest.mark.parametrize("name, want", [
    # the loop's own 40 and its body's 60, then 50
    ("rough_ms.rig", (40 + 60 + 50) / 2 * 1e-6),
    ("splat_ms.rig", (30 + 20) / 2 * 1e-6),
    # the loop's own 10 and its body's 30, then 20
    ("refine_ms.rig", (10 + 30 + 20) / 2 * 1e-6),
    # %h runs outside both dispatches
    ("slice_ms.rig", (100 + 150) / 2 * 1e-6),
])
def test_scope_readers_agree_with_the_hand_arithmetic(monkeypatch, name,
                                                      want):
    monkeypatch.setattr(progtrace, "load", lambda r: HAND)
    got = reader(name).read(R)
    value = got[0] if isinstance(got, tuple) else got
    assert value == pytest.approx(want)


def test_rough_reports_the_share_of_busy_time_under_the_scopes(monkeypatch):
    monkeypatch.setattr(progtrace, "load", lambda r: HAND)
    _v, extra = reader("rough_ms.rig").read(R)
    # own time 540 ns in the window, of which the copy's 20 is unscoped
    assert extra == {"dispatches": 2,
                     "scoped_busy": pytest.approx(100 * 520 / 540),
                     "largest_unscoped": "%g = copy"}


@pytest.mark.parametrize("name", [s + "_ms.rig" for s in SCOPES])
def test_scope_readers_find_nothing_without_a_dispatch_span(monkeypatch,
                                                            name):
    bare = dict(HAND, spans=[])
    monkeypatch.setattr(progtrace, "load", lambda r: bare)
    assert reader(name).read(R) is None
