"""On-chip smoke check: the repository's main paths on a TPU.

    python3 chip_smoke.py            # one chip: §III funnel, serving tick,
                                     # VR rig, integral kernel at a 4K eye
    python3 chip_smoke.py --chips 4  # a four-chip host: the multi-device paths

One process holds the chip for the whole run and starts no children.  Every
phase drives the system through its normal entry points (``FaceAuthExecutor``,
``StreamingServer``, ``VRRigExecutor``), checks what comes out against the
repository's own references, and raises on the first disagreement.  Per
phase it prints the first-call (compile) and steady-state seconds and the
path each kernel took (Pallas or XLA, read from the lowered program).  The
last line of standard output is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU, or outside a checkout
of the repository, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Pallas kernel (function) names per kernel package, as they appear in the
# lowered program's ``kernel_name`` attribute
KERNELS = {
    "integral_image": ("_integral_kernel",),
    "quant_matmul": ("_qmm_kernel",),
    "wire_codec": ("_encode_kernel", "_decode_kernel"),
    "bilateral_blur": ("_blur_kernel",),
}
# the path each kernel must take on a TPU (kernels/*/ops.py dispatch)
TPU_PATHS = {"integral_image": "pallas", "quant_matmul": "pallas",
             "wire_codec": "pallas", "bilateral_blur": "pallas"}


class CheckFailed(AssertionError):
    """An output disagreed with its reference."""


def check(ok, what: str):
    if not ok:
        raise CheckFailed(what)


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------


def _arrays(x):
    """The arrays inside a result, with result dataclasses unpacked (JAX
    does not see into them, so ``block_until_ready`` would not wait)."""
    if dataclasses.is_dataclass(x):
        return [_arrays(getattr(x, f.name)) for f in dataclasses.fields(x)]
    if isinstance(x, (tuple, list)):
        return [_arrays(v) for v in x]
    if isinstance(x, dict):
        return {k: _arrays(v) for k, v in x.items()}
    return x


def first_and_steady(fn, reps: int = 3):
    """(first-call s, median steady-state s, result of the first call).

    The first call pays tracing and compilation; the steady figure is the
    median of ``reps`` further calls, each waited for on the device.
    """
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(_arrays(out))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(_arrays(fn()))
        times.append(time.perf_counter() - t0)
    return first, float(np.median(times)), out


def kernel_paths(lowered, names) -> dict:
    """Which of ``names`` run as a Pallas kernel in a lowered program."""
    found = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    return {k: "pallas" if found & set(KERNELS[k]) else "xla" for k in names}


def check_paths(phase: str, paths: dict):
    for k, p in paths.items():
        check(p == TPU_PATHS[k],
              f"{phase}: {k} took the {p} path, expected {TPU_PATHS[k]}")


def report(phase: str, first: float, steady: float, paths: dict, **extra):
    kern = " ".join(f"{k}={v}" for k, v in paths.items())
    more = " ".join(f"{k}={v}" for k, v in extra.items())
    log(f"[{phase}] compile_s={first - steady:.3f} first_call_s={first:.3f} "
        f"steady_s={steady:.4f} kernels: {kern or '-'} {more}".rstrip())


def same_result(a: dict, b: dict, keys) -> list:
    """Names of the result leaves that differ (bitwise) between a and b."""
    return [k for k in keys
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k]))]


RESULT_KEYS = ("motion", "n_windows", "n_auth", "scores", "window_id",
               "window_valid", "auth", "windows_dropped", "motion_dropped",
               "cascade_dropped")


def _as_dict(res) -> dict:
    return {k: getattr(res, k) for k in RESULT_KEYS}


# ---------------------------------------------------------------------------
# phase: the §III funnel
# ---------------------------------------------------------------------------


def funnel_phase(frames, casc, nn, scan):
    """FaceAuthExecutor over the security video vs the golden host loop."""
    import jax.numpy as jnp

    from benchmarks.workloads import host_loop_funnel
    from repro.camera.pipelines import FaceAuthExecutor
    from repro.kernels.quant_matmul.ops import nn_forward_quantized

    h, w = frames.shape[1:]
    ex = FaceAuthExecutor(casc, nn, h, w, **scan)
    t0 = time.perf_counter()
    fcap, wcap, caps = ex.calibrate(frames)
    t_cal = time.perf_counter() - t0
    fj = jnp.asarray(frames)
    first, steady, res = first_and_steady(lambda: ex(fj))
    paths = kernel_paths(ex._single.lower(fj, *ex._consts),
                         ("integral_image", "quant_matmul"))
    check_paths("funnel", paths)

    # golden host loop on the executor's own int8 datapath (the platform's
    # dispatch: the Pallas kernel on a TPU)
    kernel_nn = lambda x: nn_forward_quantized(  # noqa: E731
        ex.qnn, jnp.asarray(x), ex.lut, ex.lut_meta)
    mask, n_win, n_auth, scores, prepared = host_loop_funnel(
        ex, frames, kernel_nn)
    check(np.array_equal(np.asarray(res.motion), mask), "funnel: motion mask")
    check(np.array_equal(np.asarray(res.n_windows), n_win),
          "funnel: window counts")
    check(np.array_equal(np.asarray(res.n_auth), n_auth),
          "funnel: auth counts")
    check(res.total_dropped() == 0, "funnel: calibrated capacities dropped")
    check(int(n_win.sum()) > 0, "funnel: no window reached the NN")
    for i, s in scores.items():
        v = np.asarray(res.window_valid[i])
        check(np.array_equal(np.asarray(res.scores[i])[v], s),
              f"funnel: frame {i} scores differ from the host loop")

    # the int8 kernel against the XLA reference math on the same crops
    # (tests/test_kernels.py tolerance)
    ref_nn = lambda x: nn_forward_quantized(  # noqa: E731
        ex.qnn, jnp.asarray(x), ex.lut, ex.lut_meta, use_pallas=False)
    _, _, n_auth_ref, scores_ref, _ = host_loop_funnel(
        ex, frames, ref_nn, prepared=prepared)
    score_diff = max((float(np.abs(scores[i] - scores_ref[i]).max())
                      for i in scores), default=0.0)
    check(np.array_equal(n_auth, n_auth_ref),
          "funnel: int8 kernel and reference disagree on auth counts")
    check(score_diff <= 1e-5,
          f"funnel: int8 kernel vs reference scores differ by {score_diff}")
    report("funnel", first, steady, paths,
           frames=len(frames), motion=int(mask.sum()),
           windows=int(n_win.sum()), auth=int(n_auth.sum()),
           capacities=f"f={fcap},w={wcap},vj={caps}",
           calibrate_s=f"{t_cal:.3f}", kernel_vs_ref_score_diff=score_diff)
    return ex


# ---------------------------------------------------------------------------
# phase: the serving tick
# ---------------------------------------------------------------------------


def serving_phase(ex, frames, n_streams: int = 32, n_local: int = 4,
                  chunk: int = 4, feed_ticks: int = 2):
    """StreamingServer over the executor: local and ``vj``/8-bit streams,
    exactly-once delivery, and single-stream bit-identity."""
    import jax.numpy as jnp

    from benchmarks.serving import _bitexact_row
    from repro.camera.offload import ETH_25G_LINK
    from repro.camera.offload.executors import FaceAuthOffloadExecutor
    from repro.camera.serve import ServeConfig, StreamingServer

    # the offload executor's wire codec on the platform's dispatch (Pallas
    # on a TPU) against the XLA codec: same payload, same decisions
    fj = jnp.asarray(frames[:chunk * 2])
    off = FaceAuthOffloadExecutor(ex, "vj", bits=8)
    off_ref = FaceAuthOffloadExecutor(ex, "vj", bits=8, use_pallas=False)
    first, steady, (res_k, pay_k) = first_and_steady(lambda: off(fj))
    res_r, pay_r = off_ref(fj)
    paths = kernel_paths(off._node.lower(fj, *off._consts), ("wire_codec",))
    check_paths("serving", paths)
    diff = same_result(_as_dict(res_k), _as_dict(res_r), RESULT_KEYS)
    check(not diff, f"serving: Pallas vs XLA wire codec results differ: {diff}")
    check(float(pay_k.wire_b) == float(pay_r.wire_b),
          "serving: Pallas vs XLA wire codec byte counts differ")
    for name, arr in pay_k.arrays.items():
        check(np.array_equal(np.asarray(arr), np.asarray(pay_r.arrays[name])),
              f"serving: payload {name!r} differs between codec paths")
    report("serving.codec", first, steady, paths, cut="vj", bits=8)

    # a fleet: n_local streams on the local rung, the rest offloaded at vj/8
    cfg = ServeConfig(chunk=chunk, capacity=n_streams, tick_s=1.0,
                      max_queue_s=1e9, link_window=4)
    srv = StreamingServer(ex, link=ETH_25G_LINK, config=cfg)
    for k in range(n_streams):
        cut = None if k < n_local else "vj"
        dec = srv.register(f"s{k}", fps=float(chunk), cut=cut,
                           bits=8 if cut else None)
        check(dec.admitted and dec.cut == cut, f"serving: admission {dec}")
    t0 = time.perf_counter()
    srv.prewarm([(None, None), ("vj", 8)])
    t_warm = time.perf_counter() - t0
    delivered: dict = {}
    tick_s, t = [], 0.0
    for tick in range(feed_ticks + 2):
        if tick < feed_ticks:                    # then drain
            for k in range(n_streams):
                for j in range(chunk):
                    idx = (7 * k + tick * chunk + j) % len(frames)
                    srv.enqueue(f"s{k}", frames[idx], t=t + j / chunk)
        t += cfg.tick_s
        t0 = time.perf_counter()
        rep = srv.tick(t)
        tick_s.append(time.perf_counter() - t0)
        for comp in rep.completions:
            delivered.setdefault(comp.sid, []).extend(comp.seqs)
    audit = srv.seq_audit()
    check(audit["ok"], f"serving: seq_audit failed {audit}")
    check(audit["shed"] == 0 and audit["queued"] == 0,
          f"serving: frames left shed/queued {audit}")
    check(audit["delivered"] == audit["enqueued"] == n_streams * chunk
          * feed_ticks, f"serving: delivered != enqueued {audit}")
    for k in range(n_streams):
        seqs = delivered.get(f"s{k}", [])
        check(sorted(seqs) == list(range(chunk * feed_ticks)),
              f"serving: stream s{k} delivered seqs {seqs}")

    # one stream served alone is bit-identical to the executor: on the local
    # rung against FaceAuthExecutor, at vj/8 against the offload executor
    row = _bitexact_row(ex, frames[:chunk * 2], None, None, "local")
    check(row[2] == "1", f"serving: local stream differs from the executor "
                         f"({row})")
    one = StreamingServer(ex, link=ETH_25G_LINK, config=ServeConfig(
        chunk=chunk * 2, capacity=1, tick_s=1.0, max_queue_s=1e9,
        link_window=4))
    check(one.register("v", fps=1.0, cut="vj", bits=8).admitted,
          "serving: vj stream not admitted")
    for i, f in enumerate(frames[:chunk * 2]):
        one.enqueue("v", f, t=i / (chunk * 2))
    (comp,) = one.tick(t=1.0).completions
    diff = same_result(comp.result, _as_dict(res_r), RESULT_KEYS)
    check(not diff, f"serving: vj/8 stream differs from the offload "
                    f"executor: {diff}")
    log(f"[serving] streams={n_streams} local={n_local} offloaded_vj8="
        f"{n_streams - n_local} ticks={len(tick_s)} prewarm_s={t_warm:.3f} "
        f"tick_s={' '.join(f'{x:.4f}' for x in tick_s)} "
        f"delivered={audit['delivered']} exactly_once=1 bitexact_local=1 "
        f"bitexact_vj8=1")


# ---------------------------------------------------------------------------
# phase: the VR rig
# ---------------------------------------------------------------------------


def rig_phase(lefts, rights):
    """One rig frame through VRRigExecutor vs the per-pair oracle."""
    import functools

    import jax
    import jax.numpy as jnp

    from benchmarks.vr_depth_hotpath import MAX_DISP, N_ITERS, SIGMA
    from repro.camera.bssa import (GridSpec, bssa_depth_ref,
                                   rough_disparity, rough_disparity_ref)
    from repro.camera.pipelines import VRRigExecutor

    spec = GridSpec(sigma_spatial=SIGMA)
    ex = VRRigExecutor(spec, max_disp=MAX_DISP, n_iters=N_ITERS)
    first, steady, (lp, rp, depths) = first_and_steady(
        lambda: ex(lefts, rights))
    paths = kernel_paths(ex._depth.lower(lefts, rights), ("bilateral_blur",))
    check_paths("rig", paths)
    check(bool(jnp.all(jnp.isfinite(lp)) & jnp.all(jnp.isfinite(rp))),
          "rig: non-finite panorama")

    n = lefts.shape[0]
    # both cost volumes add the same SADs in the same order, so the
    # winners agree at every pixel and the depth follows the oracle's
    rough_f = np.asarray(jax.jit(jax.vmap(functools.partial(
        rough_disparity, max_disp=MAX_DISP)))(lefts, rights))
    rough_o = np.stack([np.asarray(rough_disparity_ref(lefts[i], rights[i],
                                                       MAX_DISP))
                        for i in range(n)])
    argmin_parity = float((rough_f == rough_o).mean())
    check(argmin_parity == 1.0, f"rig: argmin parity {argmin_parity} != 1")
    depth_err = max(float(jnp.abs(depths[i] - bssa_depth_ref(
        lefts[i], rights[i], spec, MAX_DISP, N_ITERS)).max())
        for i in range(n))
    check(depth_err <= 1e-4, f"rig: depth differs from the oracle by "
                             f"{depth_err}")
    report("rig", first, steady, paths,
           pairs=n, size=f"{lefts.shape[1]}x{lefts.shape[2]}",
           argmin_parity=f"{argmin_parity:.6f}",
           depth_vs_oracle_max_abs_diff=f"{depth_err:.3e}")


def integral_phase():
    """The integral-image kernel alone against XLA's cumsum: at the 4K VR
    eye (column-tiled) and exactly on integer-valued cost-volume tiles."""
    import jax
    import jax.numpy as jnp

    from repro.configs.shapes import KERNEL_SHAPES
    from repro.kernels.integral_image.ops import integral_image
    from repro.kernels.integral_image.ref import integral_ref

    c = next(c for c in KERNEL_SHAPES["integral_image"]
             if c["case"] == "vr_4k_eye")
    img = jax.random.uniform(jax.random.PRNGKey(0), (c["n"], c["h"], c["w"]))
    first, steady, out = first_and_steady(lambda: integral_image(img))
    paths = kernel_paths(integral_image.lower(img), ("integral_image",))
    check_paths("integral", paths)
    ref = integral_ref(img)
    rel = float(jnp.abs(out[..., 1:, 1:] - ref).max() / jnp.abs(ref).max())
    check(rel <= 1e-5, f"integral: 4K eye differs from cumsum by {rel} "
                       "of the total")
    # 7-bit integers at the rig's padded SAD tile (260x324): every prefix
    # sum is an integer below 2^24
    q = jnp.round(127 * jax.random.uniform(jax.random.PRNGKey(1),
                                           (8, 260, 324)))
    exact = bool(jnp.all(integral_image(q)[..., 1:, 1:] == integral_ref(q)))
    check(exact, "integral: integer-valued frames not exact")
    report("integral", first, steady, paths,
           size=f"{c['h']}x{c['w']}", max_rel_diff=f"{rel:.3e}",
           integer_exact=int(exact))


# ---------------------------------------------------------------------------
# phases on several chips
# ---------------------------------------------------------------------------


def rig_multichip_phase(lefts, rights):
    """The rig's pairs split over the devices vs vmapped on device 0."""
    import jax
    import jax.numpy as jnp

    from benchmarks.vr_depth_hotpath import MAX_DISP, N_ITERS, SIGMA
    from repro.camera.bssa import GridSpec
    from repro.camera.pipelines import VRRigExecutor, _device_groups, _group

    spec = GridSpec(sigma_spatial=SIGMA)
    n, ndev = lefts.shape[0], jax.local_device_count()
    groups = _device_groups(n, ndev)
    check(groups == ndev, f"rig: {n} pairs do not split over {ndev} devices")
    par = VRRigExecutor(spec, max_disp=MAX_DISP, n_iters=N_ITERS,
                        rig_parallel=True)
    one = VRRigExecutor(spec, max_disp=MAX_DISP, n_iters=N_ITERS,
                        rig_parallel=False)
    first, steady, d_par = first_and_steady(
        lambda: par.depth_maps(lefts, rights))
    first1, steady1, d_one = first_and_steady(
        lambda: one.depth_maps(lefts, rights))
    # where the pairs ran: the pmapped program's output shards
    raw = par._depth_pmap(_group(lefts, groups), _group(rights, groups))
    placement = sorted((s.device.id, s.data.shape[1])
                       for s in raw.addressable_shards)
    check(len(placement) == ndev and all(p == n // ndev
                                         for _, p in placement),
          f"rig: placement {placement}")
    err = float(jnp.abs(d_par - d_one).max())
    check(err <= 1e-5, f"rig: pmapped vs vmapped depth differ by {err}")
    report("rig.multichip", first, steady, {}, pairs=n, devices=ndev,
           pairs_per_device=[p for _, p in placement],
           device_ids=[d for d, _ in placement],
           vmapped_dev0_steady_s=f"{steady1:.4f}",
           vmapped_dev0_first_call_s=f"{first1:.3f}",
           max_abs_diff=f"{err:.3e}")


def batch_step_multichip_phase(ex, frames, n_streams: int = 8,
                               chunk: int = 4):
    """batch_step pmapped over the devices vs the vmapped step."""
    import jax
    import jax.numpy as jnp

    ndev = jax.local_device_count()
    stack = jnp.asarray(np.stack([np.roll(frames, 5 * s, axis=0)[:chunk]
                                  for s in range(n_streams)]))
    valid = jnp.ones((n_streams,), bool)
    par = ex.batch_step(n_streams, chunk, stream_parallel=True)
    one = ex.batch_step(n_streams, chunk, stream_parallel=False)
    first, steady, out_p = first_and_steady(lambda: par(stack, valid))
    first1, steady1, out_1 = first_and_steady(lambda: one(stack, valid))
    check(np.asarray(out_p["motion"]).any(), "batch_step: no motion")
    diff = same_result(out_p, out_1, RESULT_KEYS)
    check(not diff, f"batch_step: pmapped vs vmapped differ: {diff}")
    report("batch_step.multichip", first, steady, {}, streams=n_streams,
           devices=ndev, vmapped_steady_s=f"{steady1:.4f}",
           windows=int(np.asarray(out_p["n_windows"]).sum()), bitexact=1)


def device_kill_phase(ex, frames, chunk: int = 4, feed_ticks: int = 6,
                      kill_at: int = 2):
    """A device dies mid-run: the local group re-shards onto the
    survivors and every frame is still delivered exactly once."""
    import jax

    from repro.camera.serve import ServeConfig, StreamingServer

    ndev = jax.local_device_count()
    # capacity divisible by ndev and ndev - 1: the group pmaps before and
    # after the kill
    cap = ndev * (ndev - 1)
    srv = StreamingServer(ex, config=ServeConfig(
        chunk=chunk, capacity=cap, tick_s=1.0, max_queue_s=1e9))
    for k in range(cap):
        check(srv.register(f"s{k}", fps=1.0, cut=None).admitted,
              "kill: admission")
    srv.prewarm([(None, None)], device_counts=(ndev - 1,))
    delivered, t, ticks = {}, 0.0, []
    for tick in range(feed_ticks + 2):
        if tick == kill_at:
            srv.kill_device(ndev - 1)
        if tick < feed_ticks:
            for k in range(cap):
                for j in range(chunk):
                    idx = (5 * k + tick * chunk + j) % len(frames)
                    srv.enqueue(f"s{k}", frames[idx], t=t + j / chunk)
        t += 1.0
        t0 = time.perf_counter()
        rep = srv.tick(t)
        ticks.append(time.perf_counter() - t0)
        for comp in rep.completions:
            delivered.setdefault(comp.sid, []).extend(comp.seqs)
    audit = srv.seq_audit()
    check(audit["ok"] and audit["shed"] == 0 and audit["queued"] == 0
          and audit["delivered"] == cap * chunk * feed_ticks,
          f"kill: seq_audit {audit}")
    for k in range(cap):
        check(sorted(delivered.get(f"s{k}", []))
              == list(range(chunk * feed_ticks)),
              f"kill: stream s{k} not delivered exactly once")
    # the local group's step now pmaps over the survivors
    survivors = tuple(d.id for d in jax.local_devices()[:ndev - 1])
    keys = [k for k in ex._batch_steps if k[2] and k[3] == survivors]
    check(keys, f"kill: no step over survivors {survivors}: "
                f"{list(ex._batch_steps)}")
    log(f"[device_kill] devices={ndev} killed={ndev - 1} "
        f"survivors={list(survivors)} streams={cap} "
        f"delivered={audit['delivered']} exactly_once=1 "
        f"tick_s={' '.join(f'{x:.4f}' for x in ticks)}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _device():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX sees {devs}); "
                         "this check runs only on the chip")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phases")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print("chip_smoke: run from a checkout of the repository "
              f"(no src/repro beside {__file__})", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]

    device = _device()
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but only "
                         f"{device['count']} devices")
    from repro.compile_cache import enable_compile_cache

    log(f"[device] {device} compile_cache={enable_compile_cache()}")

    from benchmarks.fa_hotpath import _workload
    from benchmarks.vr_depth_hotpath import N_PAIRS, _rig

    t0 = time.perf_counter()
    frames, casc, nn, scan = _workload(smoke=False)
    log(f"[setup] workload {frames.shape} cascade="
        f"{len(casc.stage_sizes)}x{max(casc.stage_sizes)} scan={scan} "
        f"s={time.perf_counter() - t0:.3f}")
    lefts, rights = _rig(h=256, w=320, n_pairs=N_PAIRS)   # stereo_pair's size
    if args.chips == 4:
        from repro.camera.pipelines import FaceAuthExecutor

        rig_multichip_phase(lefts, rights)
        ex = FaceAuthExecutor(casc, nn, frames.shape[1], frames.shape[2],
                              **scan)
        ex.calibrate(frames)
        batch_step_multichip_phase(ex, frames)
        device_kill_phase(ex, frames)
    else:
        ex = funnel_phase(frames, casc, nn, scan)
        serving_phase(ex, frames)
        rig_phase(lefts, rights)
        integral_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
