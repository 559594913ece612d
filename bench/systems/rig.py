"""This chip's share of the paper's VR rig: its stereo pairs' depth maps
through the program's ``VRRigExecutor``, in a closed loop of pair frames.

Set-up makes the traffic's ring of distinct pair frames from the seed
(camera pixels at the configuration's bit depth), puts them on the
device, and runs ``depth_maps`` twice on the first (compile and one steady
call).  The window then runs pair frames back to back, each read back to
the host, until the frame that crosses ``--seconds`` has come back;
``rig_fps`` is the frames over that whole time.  A sample of the frames,
drawn from the seed, is compared afterwards with the plain reference
(``bench/reference/bssa.py``) run on the device.
"""

from __future__ import annotations

import time

import numpy as np

from bench import costs, gen
from bench.reference import bssa as ref

# limit of the number compared, set from readings at 256x320, where the
# program equals the reference (PERF.md); at 3840x2160 no limit separates
# the program from the control
LIMITS = {"depth_mean_err_px": 0.02}


def make_ring(ctx) -> list:
    """(left, right) per slot of the ring: (pairs, h, w) float32 each."""
    cfg, tr = ctx.config, ctx.traffic
    h, w, n = cfg["camera"]["h"], cfg["camera"]["w"], cfg["pairs"]
    bits = cfg["camera"]["bits"]
    ring = []
    for k in range(tr["ring"]):
        ls, rs = [], []
        for p in range(n):
            left, right, _ = gen.stereo_pair(h, w, max_disp=tr["scene_disp"],
                                             seed=(ctx.seed, k, p))
            ls.append(gen.quantize(left, bits))
            rs.append(gen.quantize(right, bits))
        ring.append((np.stack(ls), np.stack(rs)))
    return ring


def executor(cfg: dict):
    from repro.camera.bssa import GridSpec
    from repro.camera.pipelines import VRRigExecutor

    b = cfg["bssa"]
    return VRRigExecutor(GridSpec(sigma_spatial=b["sigma_spatial"],
                                  sigma_range=b["sigma_range"]),
                         max_disp=b["max_disp"], n_iters=b["n_iters"])


def reference_depth(cfg: dict, left, right, dtype=None):
    import jax.numpy as jnp

    b = cfg["bssa"]
    return np.asarray(ref.depth(
        jnp.asarray(left), jnp.asarray(right), max_disp=b["max_disp"],
        n_iters=b["n_iters"], sigma_spatial=b["sigma_spatial"],
        sigma_range=b["sigma_range"], dtype=dtype or jnp.float32))


def check(ctx, ring, kept, dtype=None) -> dict:
    """The worst of each compared number over the kept frames.  With a
    lower ``dtype`` the reference in that precision stands in for the
    program (the control)."""
    worst: dict = {}
    cache: dict = {}
    for slot, depths in kept:
        lefts, rights = ring[slot]
        for p in range(lefts.shape[0]):
            key = (slot, p)
            if key not in cache:
                cache[key] = reference_depth(ctx.config, lefts[p], rights[p])
            got = (depths[p] if dtype is None else reference_depth(
                ctx.config, lefts[p], rights[p], dtype))
            for k, v in ref.compare(got, cache[key]).items():
                worst[k] = max(worst.get(k, v), v)
    return worst


def prepare(ctx) -> dict:
    """What does not depend on the seed: the executor."""
    return {"ex": executor(ctx.config)}


def run(ctx) -> dict:
    return serve(ctx, prepare(ctx))


def serve(ctx, prep) -> dict:
    """One run of the closed loop, then its checks."""
    import jax.numpy as jnp

    cfg, tr = ctx.config, ctx.traffic
    ring_host = make_ring(ctx)
    ring = [(jnp.asarray(l), jnp.asarray(r)) for l, r in ring_host]
    ex = prep["ex"]
    for _ in range(2):
        np.asarray(ex.depth_maps(*ring[0]))
    setup_s = time.perf_counter() - ctx.t_start

    rng = np.random.default_rng((ctx.seed, 11))
    keep_n = tr["check_frames"]
    kept, n = [], 0
    ctx.window(True)
    t0 = time.perf_counter()
    while True:
        slot = n % len(ring)
        with ctx.annotate("frame"):
            depths = ex.depth_maps(*ring[slot])
        with ctx.annotate("readback"):
            host = np.asarray(depths)
        n += 1
        # reservoir sample of the frames, drawn from the seed
        if len(kept) < keep_n:
            kept.append((slot, host))
        else:
            j = int(rng.integers(0, n))
            if j < keep_n:
                kept[j] = (slot, host)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    ctx.window(False)
    peak = ctx.memory_peak_bytes()
    del ring, depths

    numbers = check(ctx, ring_host, kept)
    checks = [(k, numbers[k], LIMITS[k]) for k in LIMITS]
    b, cam = cfg["bssa"], cfg["camera"]
    pairs = cfg["pairs"]
    gy, gx, gr = costs.grid_dims(cam["h"], cam["w"], b["sigma_spatial"],
                                 b["sigma_range"])
    reading = {
        "frames": n, "window_s": window_s,
        "pair_ops": costs.bssa_pair(cam["h"], cam["w"], b["max_disp"],
                                    b["n_iters"], b["sigma_spatial"],
                                    b["sigma_range"]),
        "pairs": pairs,
        # per frame: the cost volume's summed-area table of the edge-padded
        # pair for each hypothesis, and one blur pass per iteration
        "integral_work": tuple((b["max_disp"] + 1) * pairs * x for x in
                               costs.integral_image(1, cam["h"] + 4,
                                                    cam["w"] + 4)),
        "blur_work": tuple(b["n_iters"] * pairs * x for x in
                           costs.bilateral_blur(gy, gx, gr)),
    }
    res = {"e2e": {"setup_s": setup_s, "rig_fps": n / window_s},
           "attempted": n, "failed": 0, "checks": checks,
           "memory_peak_bytes": peak, "reading": reading}
    if getattr(ctx, "control", False):
        # the reference in the next lower precision in the program's place
        res["control"] = check(ctx, ring_host, kept, dtype=jnp.bfloat16)
    return res
