"""A fleet of WISPCam face-authentication cameras served by the program's
``StreamingServer`` on one chip, under an open loop of frames.

Set-up loads the deployment's detector from the frozen file the
configuration names, builds and calibrates the
``FaceAuthExecutor``, registers the traffic's streams on the local rung,
compiles the micro-batch shapes the streams can reach, and runs the open
loop for ``lead_s`` seconds.  The window then measures every frame due in
it (``bench/openloop.py``).  Afterwards every frame is drained, delivery is
audited, and a sample of the decided chunks, drawn from the seed, is
compared with the plain reference (``bench/reference/wispcam.py``).
"""

from __future__ import annotations

import hashlib
import os
import sys
import time

import numpy as np

from bench import costs, gen, hostwatch, openloop
from bench.reference import wispcam as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# limits of the numbers compared; PERF.md gives the readings each was set
# from.  Delivery, the motion gate and the auth decisions away from the
# threshold are exact.
LIMITS = {"delivery_errors": 0, "motion_flips": 0,
          "window_miss_share": 0.05, "score_gap_max": 0.03,
          "auth_flips": 0}


def load_detector(cfg: dict):
    """(cascade, network) of the deployment, and the plain tables the
    reference reads, from the frozen file the configuration names.  The
    file was trained once at the configuration's training seeds and is
    checked against the digest the configuration states, so a change to
    the program's trainers cannot move the workload."""
    import jax.numpy as jnp

    from repro.camera.face_nn import FaceNN
    from repro.camera.viola_jones import Cascade, HaarFeature

    wt, d = cfg["weights"], cfg["detector"]
    path = os.path.join(ROOT, wt["file"])
    with open(path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    if digest != wt["sha256"]:
        raise RuntimeError(f"{wt['file']}: sha256 {digest}, the "
                           f"configuration states {wt['sha256']}")
    z = np.load(path)
    sizes = [int(s) for s in z["stage_sizes"]]
    if sizes != [d["per_stage"]] * d["stages"]:
        raise RuntimeError(f"{wt['file']}: stages {sizes}, the "
                           f"configuration states {d['stages']} of "
                           f"{d['per_stage']}")
    casc = Cascade(feats=[HaarFeature(*map(int, r)) for r in z["feats"]],
                   thresholds=z["thresholds"], polarity=z["polarity"],
                   alphas=z["alphas"], stage_sizes=sizes,
                   stage_thresholds=z["stage_thresholds"])
    net = FaceNN(w1=jnp.asarray(z["w1"]), b1=jnp.asarray(z["b1"]),
                 w2=jnp.asarray(z["w2"]), b2=jnp.asarray(z["b2"]))
    plain_casc = {"feats": [tuple(map(int, r)) for r in z["feats"]],
                  "thresholds": z["thresholds"], "polarity": z["polarity"],
                  "alphas": z["alphas"], "stage_sizes": sizes,
                  "stage_thresholds": z["stage_thresholds"]}
    plain_nn = {k: np.asarray(z[k], np.float32)
                for k in ("w1", "b1", "w2", "b2")}
    return casc, net, plain_casc, plain_nn


def build_executor(cfg: dict, casc, net):
    """The deployment's executor, calibrated on the configuration's
    calibration video."""
    from repro.camera.pipelines import FaceAuthExecutor

    fr, sc = cfg["frame"], cfg["scan"]
    ex = FaceAuthExecutor(
        casc, net, fr["h"], fr["w"], scale_factor=sc["scale_factor"],
        step=sc["step"], adaptive=sc["adaptive"],
        motion_threshold=cfg["motion"]["threshold"],
        motion_factor=cfg["motion"]["factor"],
        auth_threshold=cfg["nn"]["auth_threshold"])
    cal = cfg["calibration"]
    ex.calibrate(gen.security_video(n_frames=cal["n_frames"],
                                    motion_frames=cal["motion_frames"],
                                    seed=cal["video_seed"]))
    return ex


def build_server(ctx, ex, telemetry=None):
    """A server with the traffic's streams on the local rung, every
    micro-batch shape they can reach compiled."""
    from repro.camera.serve import ServeConfig, StreamingServer

    cfg, tr = ctx.config, ctx.traffic
    fr, sv = cfg["frame"], cfg["serve"]
    srv = StreamingServer(ex, config=ServeConfig(
        chunk=sv["chunk"], capacity=sv["capacity"], tick_s=sv["tick_s"]),
        telemetry=telemetry)
    sids = [f"s{k}" for k in range(tr["streams"])]
    for sid in sids:
        dec = srv.register(sid, fps=fr["fps"], cut=None)
        if not dec.admitted:
            raise RuntimeError(f"stream {sid} refused: {dec.reason}")
    srv.prewarm([(None, None)], max_ready=tr["streams"])
    return srv, sids


def video_pool(ctx) -> list:
    tr, fr = ctx.traffic, ctx.config["frame"]
    return [gen.security_video(n_frames=tr["video_frames"], h=fr["h"],
                               w=fr["w"], motion_frames=tr["motion_frames"],
                               seed=(ctx.seed, k))
            for k in range(tr["pool"])]


def frame_source(ctx, pool):
    tr = ctx.traffic

    def frame_of(k, j):
        video = pool[k % len(pool)]
        return video[(k * tr["offset_stride"] + j) % len(video)]
    return frame_of


def audit(out: openloop.Outcome) -> int:
    """Frames not delivered exactly once and in order, shed ones aside."""
    seen: dict = {}
    for _t, comp in out.completions:
        seen.setdefault(comp.sid, []).extend(comp.seqs)
    errors = 0
    for sid in {key[0] for key in out.due}:
        got = seen.get(sid, [])
        errors += len(got) - len(set(got))
        if got != sorted(got):
            errors += 1
        want = {seq for (s, seq) in out.due if s == sid
                and (s, seq) not in out.shed}
        errors += len(want - set(got))
    return errors


def sample_chunks(ctx, out, w0, w1) -> list:
    """Chunks decided for frames due in the window, a sample drawn from
    the seed (at most the traffic's ``check_chunks``)."""
    comps = [c for _t, c in out.completions
             if any(w0 <= out.due[(c.sid, s)] < w1 for s in c.seqs)]
    rng = np.random.default_rng((ctx.seed, 7))
    n = min(len(comps), ctx.traffic["check_chunks"])
    pick = sorted(rng.choice(len(comps), n, replace=False)) if n else []
    return [comps[i] for i in pick]


def program_frames(comp) -> list:
    """(motion, windows, scores, auth) per real frame of a completion."""
    r = comp.result
    out = []
    for j in range(comp.n_frames):
        valid = np.asarray(r["window_valid"][j], bool)
        out.append((bool(r["motion"][j]),
                    np.asarray(r["window_id"][j])[valid],
                    np.asarray(r["scores"][j], np.float32)[valid],
                    np.asarray(r["auth"][j], bool)[valid]))
    return out


def chunk_frames(ctx, frame_of, comp) -> np.ndarray:
    """The frames the server batched for a completion, padded as it pads
    (the last frame repeated)."""
    k = int(comp.sid[1:])
    fr = [frame_of(k, s) for s in comp.seqs]
    while len(fr) < ctx.config["serve"]["chunk"]:
        fr.append(fr[-1])
    return np.stack(fr)


def check(ctx, frame_of, comps, plain_casc, plain_nn, *, dtype=np.float64,
          bits: int = 8) -> dict:
    """The reference's numbers over the sampled chunks (``dtype`` and
    ``bits`` lower for the control, which is then compared in the
    program's place)."""
    cfg = ctx.config
    pos = ref.positions(cfg["frame"]["h"], cfg["frame"]["w"], cfg["scan"])
    prog, want = [], []
    for comp in comps:
        frames = chunk_frames(ctx, frame_of, comp)
        want.extend(ref.chunk(frames, comp.n_frames, cfg, plain_casc,
                              plain_nn, pos))
        if dtype is np.float64 and bits == 8:
            prog.extend(program_frames(comp))
        else:
            thr = cfg["nn"]["auth_threshold"]
            prog.extend((m, w, s, s > thr) for m, w, s in ref.chunk(
                frames, comp.n_frames, cfg, plain_casc, plain_nn, pos,
                dtype=dtype, bits=bits))
    return ref.compare(prog, want, cfg["nn"]["auth_threshold"],
                       LIMITS["score_gap_max"])


def step_ops(ctx, ex, comps) -> float:
    """Operations the decided frames need (``costs.funnel_frame``)."""
    cfg = ctx.config
    n_scan = len(ref.positions(cfg["frame"]["h"], cfg["frame"]["w"],
                               cfg["scan"]))
    stage0 = int(ex.det.tables.stage_sizes[0])
    total = 0.0
    for comp in comps:
        r = comp.result
        for j in range(comp.n_frames):
            total += costs.funnel_frame(
                cfg["frame"]["h"], cfg["frame"]["w"], bool(r["motion"][j]),
                n_scan, stage0, int(r["n_windows"][j]),
                factor=cfg["motion"]["factor"])
    return total


def prepare(ctx) -> dict:
    """What does not depend on the seed: the detector and the calibrated
    executor."""
    casc, net, plain_casc, plain_nn = load_detector(ctx.config)
    return {"ex": build_executor(ctx.config, casc, net),
            "plain_casc": plain_casc, "plain_nn": plain_nn}


def serve(ctx, prep) -> dict:
    """One run of the open loop on a fresh server, then its checks."""
    from repro.obs import Telemetry

    cfg, tr = ctx.config, ctx.traffic
    ex = prep["ex"]
    tel = Telemetry() if ctx.trace else None
    srv, sids = build_server(ctx, ex, telemetry=tel)
    timed = hostwatch.TimedServer(srv)
    hostwatch.install()
    pool = video_pool(ctx)
    frame_of = frame_source(ctx, pool)
    sched = openloop.Schedule(tr["streams"], cfg["frame"]["fps"],
                              tr["lead_s"], ctx.seconds, tr["spread_s"])
    t_loop = time.perf_counter()
    out = openloop.drive(timed, sids, frame_of, sched, time.perf_counter,
                         time.sleep, tick_s=cfg["serve"]["tick_s"],
                         drain_limit_s=tr["drain_limit_s"],
                         on_window=ctx.window, annotate=ctx.annotate)
    setup_s = t_loop - ctx.t_start + tr["lead_s"]
    w0, w1 = sched.window
    summary = out.summary(w0, w1)
    peak = ctx.memory_peak_bytes()
    host = hostwatch.report(timed, t_loop, w0, w1)
    print("host " + " ".join(f"{k}={v!r}" for k, v in host.items()),
          file=sys.stderr, flush=True)

    comps = sample_chunks(ctx, out, w0, w1)
    numbers = check(ctx, frame_of, comps, prep["plain_casc"],
                    prep["plain_nn"])
    numbers["delivery_errors"] = audit(out)
    checks = [(k, numbers[k], LIMITS[k]) for k in LIMITS]
    res = {"e2e": {"setup_s": setup_s,
                   "frame_p95_s": summary["frame_p95_s"],
                   "frames_per_s": summary["frames_per_s"]},
           "attempted": summary["attempted"], "failed": summary["failed"],
           "checks": checks, "memory_peak_bytes": peak,
           "reading": reading(ctx, ex, out, tel, w0, w1), "outcome": out}
    if getattr(ctx, "control", False):
        # the reference in the next lower precision in the program's place
        import ml_dtypes

        res["control"] = check(ctx, frame_of, comps, prep["plain_casc"],
                               prep["plain_nn"], dtype=ml_dtypes.bfloat16,
                               bits=4)
    return res


def reading(ctx, ex, out, tel, w0, w1) -> dict:
    """What the per-layer metric readers need from the window."""
    cfg = ctx.config
    sv = cfg["serve"]
    in_window = [c for t, c in out.completions if w0 <= t < w1]
    ticks = [(s, e) for label, s, e in out.spans
             if label == "tick" and w0 <= s < w1]
    dispatches = ([r for r in tel.trace.records("dispatch")
                   if r.name.startswith("group") and w0 <= r.t < w1]
                  if tel is not None else [])
    frames_per_slot = max(1, min(int(ex.frame_capacity or sv["chunk"]),
                                 sv["chunk"]))
    windows = sv["capacity"] * frames_per_slot * ex.window_capacity
    return {
        "ticks": len(ticks),
        "tick_s": sum(e - s for s, e in ticks),
        "dispatch_s": sum(r.dur for r in dispatches),
        "dispatches": len(dispatches),
        "served_frames": sum(c.n_frames for c in in_window
                             if c.kind == "served"),
        "slots": len(dispatches) * sv["capacity"] * sv["chunk"],
        "step_ops": step_ops(ctx, ex, in_window),
        # per dispatch: the detector's two summed-area tables over every
        # frame slot, and the network's two layers over every window slot
        "integral_work": costs.integral_image(
            2 * sv["capacity"] * frames_per_slot, cfg["frame"]["h"],
            cfg["frame"]["w"]),
        "qmm_work": tuple(a + b for a, b in zip(
            costs.quant_matmul(windows, 400, 8),
            costs.quant_matmul(windows, 8, 1))),
    }


def run(ctx) -> dict:
    return serve(ctx, prepare(ctx))
