"""``correct`` on the CPU at a small size: sound runs pass, the control
(the reference in the next lower precision, in the program's place) fails,
and so does a run whose timed path is broken underneath.  The harness's
look for a chip is skipped; everything after it runs."""

import importlib.util
import json
import os

import numpy as np
import pytest

from bench.tests import cpu_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return load(os.path.join(BENCH, "run.py"), "bench_run_cpu")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


RIG_CELL = {"name": "rig_4k", "config": "vr_rig_4k", "traffic": "pair_ring",
            "chips": 1}


def execute(run, bench, cell, cfg, tr, tmp_path, monkeypatch):
    monkeypatch.setattr(run.Context, "memory_peak_bytes", lambda self: 0)
    if cell == "rig_4k":
        # the rig's cell is out of BENCHMARK.json (PERF.md, Open
        # questions); its end-to-end metric is given here
        bench = dict(bench, workloads=bench["workloads"] + [RIG_CELL],
                     end_to_end=bench["end_to_end"] + [
                         {"name": "rig_fps", "unit": "frames/s",
                          "workloads": ["rig_4k"]}])
    return run.execute(bench, run.cell_of(bench, cell), cfg, tr,
                       2 ** 31 + 77, 3.0, False, DEVICE, str(tmp_path))


def control(system, cfg, tr):
    sysm = load(os.path.join(BENCH, "systems", system + ".py"), "ctl_" + system)
    ctx = cpu_cell.Context(cfg, tr, 2 ** 31 + 78, 3.0)
    ctx.control = True
    res = sysm.serve(ctx, sysm.prepare(ctx))
    return res["control"], sysm.LIMITS


def fails(numbers, limits):
    return any(not numbers[k] <= limits[k] for k in numbers if k in limits)


# -- the fleet ------------------------------------------------------------


def test_fleet_sound_run_is_correct(run, bench, tmp_path, monkeypatch):
    cfg, tr = cpu_cell.small_fleet()
    line = execute(run, bench, "fleet_busy", cfg, tr, tmp_path, monkeypatch)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "frame_p95_s", "frames_per_s"}
    assert list(line)[-1] == "checks"


def test_fleet_control_is_not_correct():
    numbers, limits = control("fleet", *cpu_cell.small_fleet())
    assert fails(numbers, limits), numbers


def _break_step(monkeypatch, breaker):
    from repro.camera.pipelines import FaceAuthExecutor

    orig = FaceAuthExecutor.batch_step

    def broken(self, *a, **k):
        step = orig(self, *a, **k)

        def wrapped(frames, valid):
            return breaker(dict(step(frames, valid)))
        return wrapped
    monkeypatch.setattr(FaceAuthExecutor, "batch_step", broken)


def test_fleet_altered_answer_is_not_correct(run, bench, tmp_path,
                                             monkeypatch):
    def alter(out):
        out["scores"] = out["scores"] + 0.25 * out["window_valid"]
        return out
    _break_step(monkeypatch, alter)
    line = execute(run, bench, "fleet_busy", *cpu_cell.small_fleet(),
                   tmp_path, monkeypatch)
    assert line["correct"] is False


def test_fleet_half_batch_left_out_is_not_correct(run, bench, tmp_path,
                                                  monkeypatch):
    def halve(out):
        import jax.numpy as jnp

        n = out["motion"].shape[0]
        keep = jnp.arange(n) % 2 == 0
        for k, v in out.items():
            mask = keep.reshape((n,) + (1,) * (v.ndim - 1))
            fill = -1 if k == "window_id" else 0
            out[k] = jnp.where(mask, v, jnp.full_like(v, fill))
        return out
    _break_step(monkeypatch, halve)
    cfg, tr = cpu_cell.small_fleet()
    line = execute(run, bench, "fleet_busy", cfg, tr, tmp_path, monkeypatch)
    assert line["correct"] is False


# -- the rig ----------------------------------------------------------------


def test_rig_sound_run_is_correct(run, bench, tmp_path, monkeypatch):
    line = execute(run, bench, "rig_4k", *cpu_cell.small_rig(), tmp_path,
                   monkeypatch)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "rig_fps"}


def test_rig_control_is_not_correct():
    numbers, limits = control("rig", *cpu_cell.small_rig())
    assert fails(numbers, limits), numbers


def test_rig_altered_answer_is_not_correct(run, bench, tmp_path, monkeypatch):
    from repro.camera.pipelines import VRRigExecutor

    orig = VRRigExecutor.depth_maps

    def altered(self, lefts, rights):
        d = orig(self, lefts, rights)
        return d.at[:, : d.shape[1] // 8].add(2.0)
    monkeypatch.setattr(VRRigExecutor, "depth_maps", altered)
    line = execute(run, bench, "rig_4k", *cpu_cell.small_rig(), tmp_path,
                   monkeypatch)
    assert line["correct"] is False
    assert np.isfinite(line["checks"]["depth_mean_err_px"]["value"])
