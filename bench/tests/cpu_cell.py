"""A cell's configuration and traffic cut to a size the CPU runs in
seconds, and a run context that needs no chip, for the tests."""

from __future__ import annotations

import copy
import json
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_DETECTOR_SHA256 = ("b325898dcd2ccaf13203f65ece3c61ad"
                       "49ee6ffe4862e7cca70f5b8160a7327d")


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def small_fleet(traffic: str = "busy") -> tuple:
    """wispcam_fleet with a toy cascade and network (2 stages of 6 and
    the 400-8-1 net, trained on 80 faces per class for 60 steps) and a
    coarse scan; six streams for a few seconds, every decided chunk
    checked."""
    cfg = copy.deepcopy(load("configs", "wispcam_fleet"))
    cfg["weights"].update(file="bench/tests/data/toy_detector.npz",
                          sha256=TOY_DETECTOR_SHA256)
    cfg["detector"].update(stages=2, per_stage=6)
    cfg["scan"].update(scale_factor=1.6, step=8.0, adaptive=False)
    tr = copy.deepcopy(load("traffic", traffic))
    tr.update(streams=6, pool=3, lead_s=1.0, drain_limit_s=20.0,
              check_chunks=64)
    return cfg, tr


def small_rig() -> tuple:
    cfg = copy.deepcopy(load("configs", "vr_rig_4k"))
    # a strip of the camera's full width: past 256 pixels bfloat16 no
    # longer holds a column index, which the control must show
    cfg["camera"].update(h=24, w=3840)
    cfg["bssa"].update(max_disp=8, n_iters=2)
    tr = copy.deepcopy(load("traffic", "pair_ring"))
    tr.update(ring=2, scene_disp=6, check_frames=2)
    return cfg, tr


class Context:
    """The harness's run context without the chip or the profiler."""

    def __init__(self, config, traffic, seed, seconds):
        self.cell = {"name": "cpu", "chips": 1}
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, False
        self.t_start = time.perf_counter()
        self.windows = []

    def window(self, opening: bool):
        self.windows.append(opening)

    def annotate(self, label: str):
        import contextlib

        return contextlib.nullcontext()

    def memory_peak_bytes(self) -> int:
        return 0
