"""Detection hot-path: seed-style per-window front-end vs the frame-resident
fused gather path (one integral image + compacting cascade).

Three timed configurations on the paper's 176x144 security workload:

  old   — the seed ``detect_faces`` dataflow: materialize ~25.8k resampled
          20x20 windows (extract_windows), per-window integral images,
          Python loop over features (cascade_apply), no early-exit savings;
  ref   — the scaled-feature golden oracle (detect_faces), same per-window
          structure with native-resolution windows;
  new   — FusedDetector: one frame integral, Haar corner taps (sliced in
          stage 0, gathered in the survivor stages),
          compacting cascade with measured capacities.

Also reports the FLOP saving compaction realizes vs the masked oracle —
the paper's "86% fewer invocations" finally charged in real work, not
just counted.
"""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.camera.synthetic import security_video
from repro.camera.viola_jones import (
    FusedDetector, cascade_apply, detect_faces, extract_windows,
    scan_positions)
from repro.core.cascade import compaction_work


def _detect_seed_path(casc, frame, scan=(1.25, 0.025, True)):
    """The seed repo's detect_faces dataflow, kept verbatim for old-vs-new
    timing (resample-to-20x20 semantics; superseded by scaled features)."""
    pos = scan_positions(frame.shape[0], frame.shape[1], *scan)
    wins = extract_windows(frame, pos)
    accepted, _ = cascade_apply(casc, jnp.asarray(wins))
    return [pos[i] for i in np.where(np.asarray(accepted))[0]]


def rows(n_old_frames: int = 2, n_ref_frames: int = 2, smoke: bool = False):
    out = []
    from benchmarks.workloads import fa_cascade, fa_scan
    if smoke:
        frames, truth = security_video(n_frames=6, motion_frames=3, seed=1)
        casc = fa_cascade(smoke=True)
        n_old_frames = n_ref_frames = 1
    else:
        frames, truth = security_video()
        casc = fa_cascade(frames=frames, truth=truth)
    scan = fa_scan(smoke)

    h, w = frames.shape[1:]
    det = FusedDetector(casc, h, w, scale_factor=scan[0], step=scan[1],
                        adaptive=scan[2])
    det.calibrate(frames[:4])
    det.detect(frames)                       # compile + warm
    t0 = time.time()
    dets, stats = det.detect(frames)
    new_fps = len(frames) / (time.time() - t0)

    t0 = time.time()
    for i in range(n_old_frames):
        _detect_seed_path(casc, frames[i], scan)
    old_fps = n_old_frames / (time.time() - t0)

    t0 = time.time()
    ref_sets = {i: set(detect_faces(casc, frames[i], *scan)[0])
                for i in range(n_ref_frames)}
    ref_fps = n_ref_frames / (time.time() - t0)

    ident = sum(set(dets[i]) == ref_sets[i] for i in ref_sets)
    stage_cost = [sz * (8 + 2) for sz in det.tables.stage_sizes]
    masked, compacted = compaction_work(stage_cost, stats["n_windows"],
                                        det.capacities)
    out.append(("detect", "windows_per_frame", stats["n_windows"],
                "176x144, scale 1.25, adaptive 2.5%"))
    out.append(("detect", "old_fps", f"{old_fps:.2f}",
                f"seed per-window path, {n_old_frames} frames"))
    out.append(("detect", "ref_fps", f"{ref_fps:.2f}",
                f"scaled-feature golden oracle, {n_ref_frames} frames"))
    out.append(("detect", "new_fps", f"{new_fps:.1f}",
                f"fused gathers + compaction, {len(frames)} frames steady"))
    out.append(("detect", "speedup_vs_seed", f"{new_fps / old_fps:.1f}x",
                "acceptance: >= 10x"))
    out.append(("detect", "identical_detections",
                f"{ident}/{len(ref_sets)} frames vs oracle",
                "isolated fp-borderline stumps may flip single windows"))
    out.append(("detect", "capacities",
                "/".join(str(c) for c in det.capacities),
                "from measured stage selectivities (calibrate)"))
    out.append(("detect", "flops_masked_oracle", f"{masked:.4g}",
                "per frame: every stage on every window"))
    out.append(("detect", "flops_compacted", f"{compacted:.4g}",
                f"{100 * (1 - compacted / masked):.0f}% fewer "
                "(paper: 86% fewer invocations)"))
    out.append(("detect", "stage_evals_per_frame",
                stats["stage_evals"] // len(frames),
                "data-dependent count the energy model charges"))
    out.append(("detect", "capacity_drops", stats["dropped"],
                "0 = compaction lossless on this workload"))
    return out


def main():
    for row in rows():
        print(",".join(str(c) for c in row))


if __name__ == "__main__":
    main()
