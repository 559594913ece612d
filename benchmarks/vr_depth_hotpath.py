"""VR depth hot path (paper §IV): seed per-pair jnp oracle vs the
rig-resident fused executor.

Timed configurations on a synthetic 8-pair rig at the working resolution
(a 1/8-linear-scale 4K tile per camera — the CPU-host stand-in for the
paper's per-pair FPGA slice; the oracle at full 4K is minutes per frame,
and the claim is the *ratio*):

  oracle — the seed ``bssa_depth_ref`` dataflow, kept verbatim: a Python
           loop over the rig's pairs, each materializing D+1 full-frame
           SAD maps (one separable box sum per disparity hypothesis)
           eagerly, then the scan-refine.  Timed warm (per-op caches
           populated) — the steady-state number, not first-call compile;
  fused  — ``VRRigExecutor``: the chunked cost volume with a running
           min, splat, ``refine_grid``, slice — one vmapped
           jit region per rig frame on a single device;
  rig    — the same executor with the pairs split over the host's
           devices (the paper's 8-parallel-FPGA rig shape); on the CPU it
           is measured in a subprocess with 8 fake host devices (same
           mechanism as tests/conftest.py).

Also times the batched panorama composition and reports fused-vs-oracle
per-block ms plus output parity (same argmin disparities up to
fp-borderline ties; refined depth within tolerance) — the acceptance
criteria of the fused rewrite.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np

from benchmarks.timing import run_json_child, timed as _timed

WORK_H, WORK_W = 270, 480          # 1/8-linear-scale 4K per camera
N_PAIRS = 8                        # the 16-camera rig
MAX_DISP = 32                      # VRWorkloadStats.disp_range
N_ITERS = 8
SIGMA = 16


def _rig(h=WORK_H, w=WORK_W, n_pairs=N_PAIRS):
    import jax.numpy as jnp

    from repro.camera.synthetic import stereo_pair

    pairs = [stereo_pair(h=h, w=w, seed=s) for s in range(n_pairs)]
    lefts = jnp.stack([jnp.asarray(p[0]) for p in pairs])
    rights = jnp.stack([jnp.asarray(p[1]) for p in pairs])
    return lefts, rights


def _rig_parallel(lefts, rights):
    """The pmapped executor over this process's devices."""
    import jax

    from repro.camera.bssa import GridSpec
    from repro.camera.pipelines import VRRigExecutor

    ex = VRRigExecutor(GridSpec(sigma_spatial=SIGMA), max_disp=MAX_DISP,
                       n_iters=N_ITERS, rig_parallel=True)
    t_depth, depths = _timed(ex.depth_maps, lefts, rights)
    t_pano, _ = _timed(lambda: ex.panorama(lefts, rights, depths))
    return {"depth_ms": 1e3 * t_depth, "pano_ms": 1e3 * t_pano,
            "n_devices": jax.local_device_count()}


def _rig_parallel_child():
    """Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8:
    prints :func:`_rig_parallel` as one JSON line."""
    print(json.dumps(_rig_parallel(*_rig())))


def _rig_parallel_ms(lefts, rights):
    """On the CPU, in a subprocess with 8 fake devices (the in-process
    backend is already initialized single-device); on an accelerator host
    with several devices, in-process over them; None on one accelerator."""
    import jax

    if jax.default_backend() == "cpu":
        return run_json_child(["benchmarks.vr_depth_hotpath", "--rig-child"])
    if jax.local_device_count() > 1:
        return _rig_parallel(lefts, rights)
    return None


def rows(n_oracle_pairs: int = 2, smoke: bool = False):
    import jax
    import jax.numpy as jnp

    from repro.camera.bssa import (
        GridSpec, bssa_depth_ref, refine, rough_disparity,
        rough_disparity_ref, slice_grid, slice_grid_ref, splat)
    from repro.camera.pipelines import VR_FPS_TARGET, VRRigExecutor
    from repro.kernels.bilateral_blur.ops import refine_grid

    out = []
    spec = GridSpec(sigma_spatial=SIGMA)
    if smoke:                      # toy tile, no subprocess: CI liveness only
        n_oracle_pairs = 1
        lefts, rights = _rig(h=64, w=96, n_pairs=2)
    else:
        lefts, rights = _rig()
    n_pairs = int(lefts.shape[0])

    # ---- fused: whole rig frame through the executor (single device) --------
    ex = VRRigExecutor(spec, max_disp=MAX_DISP, n_iters=N_ITERS)
    t_depth, depths = _timed(ex.depth_maps, lefts, rights)
    t_pano, _ = _timed(lambda: ex.panorama(lefts, rights, depths))

    # ---- rig-parallel: pairs split over the devices -------------------------
    rig = None if smoke else _rig_parallel_ms(lefts, rights)

    # ---- oracle: the seed per-pair Python loop, eager, warm -----------------
    bssa_depth_ref(lefts[0], rights[0], spec, MAX_DISP,
                   N_ITERS).block_until_ready()        # warm per-op caches
    t0 = time.time()
    oracle = [bssa_depth_ref(lefts[i], rights[i], spec, MAX_DISP, N_ITERS)
              for i in range(n_oracle_pairs)]
    oracle[-1].block_until_ready()
    t_oracle_pair = (time.time() - t0) / n_oracle_pairs

    # ---- parity -------------------------------------------------------------
    rough_f = np.asarray(jax.vmap(
        functools.partial(rough_disparity, max_disp=MAX_DISP))(
            lefts[:n_oracle_pairs], rights[:n_oracle_pairs]))
    rough_o = np.stack([np.asarray(rough_disparity_ref(
        lefts[i], rights[i], MAX_DISP)) for i in range(n_oracle_pairs)])
    argmin_match = float((rough_f == rough_o).mean())
    depth_err = max(float(jnp.abs(depths[i] - oracle[i]).max())
                    for i in range(n_oracle_pairs))

    # ---- per-block ms: fused (jitted) vs oracle (eager, warm), one pair -----
    l0, r0 = lefts[0], rights[0]
    blocks = []
    t, rough0 = _timed(jax.jit(functools.partial(
        rough_disparity, max_disp=MAX_DISP)), l0, r0)
    blocks.append(("rough", t))
    t, (gv, gw) = _timed(jax.jit(functools.partial(splat, spec=spec)),
                         l0, rough0)
    blocks.append(("splat", t))
    t, (gv, gw) = _timed(functools.partial(refine_grid, n_iters=N_ITERS),
                         gv, gw)
    blocks.append(("refine", t))
    t, _ = _timed(jax.jit(functools.partial(slice_grid, spec=spec)),
                  gv, gw, l0)
    blocks.append(("slice", t))

    t_or, o_rough = _timed(functools.partial(rough_disparity_ref,
                                             max_disp=MAX_DISP), l0, r0)
    t_os, (ogv, ogw) = _timed(splat, l0, o_rough, spec)
    t_orf, (ogv, ogw) = _timed(functools.partial(refine, n_iters=N_ITERS),
                               ogv, ogw)
    t_osl, _ = _timed(slice_grid_ref, ogv, ogw, l0, spec)
    oracle_blocks = dict(rough=t_or, splat=t_os, refine=t_orf, slice=t_osl)

    # ---- rows ---------------------------------------------------------------
    fused_pair_ms = 1e3 * t_depth / n_pairs
    speedup_1dev = t_oracle_pair * 1e3 / fused_pair_ms
    out.append(("vr_depth", "working_resolution",
                f"{lefts.shape[2]}x{lefts.shape[1]}x{n_pairs}pairs",
                f"{'SMOKE tile' if smoke else '1/8-linear 4K per camera'}, "
                f"D={MAX_DISP}, {N_ITERS} iters, sigma={SIGMA}"))
    out.append(("vr_depth", "oracle_ms_per_pair", f"{1e3*t_oracle_pair:.1f}",
                f"seed eager loop, warm, {n_oracle_pairs} pairs timed"))
    out.append(("vr_depth", "fused_ms_per_pair_1dev", f"{fused_pair_ms:.1f}",
                "vmapped executor, single device, rig batch amortized"))
    out.append(("vr_depth", "speedup_fused_1dev", f"{speedup_1dev:.1f}x",
                "fusion alone, same device count as the oracle"))
    if rig:
        rig_pair_ms = rig["depth_ms"] / N_PAIRS
        t_rig_frame = (rig["depth_ms"] + rig["pano_ms"]) / 1e3
        out.append(("vr_depth", "rig_ms_per_pair", f"{rig_pair_ms:.1f}",
                    f"pmapped executor, pairs split over "
                    f"{rig['n_devices']} devices (the paper's "
                    "8-parallel-FPGA rig shape)"))
        out.append(("vr_depth", "speedup_vs_seed",
                    f"{1e3*t_oracle_pair/rig_pair_ms:.1f}x",
                    "acceptance: >= 10x (paper: up to 10x FPGA vs CPU/GPU "
                    "on the depth block)"))
        out.append(("vr_depth", "rig_depth_ms_per_frame",
                    f"{rig['depth_ms']:.0f}", f"{N_PAIRS} pairs, pmapped"))
        out.append(("vr_depth", "fused_rig_fps", f"{1/t_rig_frame:.1f}",
                    f"depth+panorama; target {VR_FPS_TARGET:.0f} (paper: "
                    "only accelerated BSSA clears it)"))
    out.append(("vr_depth", "pano_ms_per_rig_frame", f"{1e3*t_pano:.1f}",
                "batched warp + scatter blend, both eyes"))
    for name, t in blocks:
        out.append(("vr_depth", f"block_{name}_ms",
                    f"oracle={1e3*oracle_blocks[name]:.1f} fused={1e3*t:.2f}",
                    "per pair, single device"))
    out.append(("vr_depth", "argmin_parity", f"{argmin_match:.4f}",
                "fraction of pixels with identical disparity hypothesis"))
    out.append(("vr_depth", "depth_max_abs_diff", f"{depth_err:.2e}",
                "fused vs oracle refined depth"))
    return out


def main():
    if "--rig-child" in sys.argv:
        _rig_parallel_child()
        return
    for row in rows():
        print(",".join(str(c) for c in row))


if __name__ == "__main__":
    main()
