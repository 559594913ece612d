"""The rig step's share of the chip's bf16 peak: operations one pair frame
needs (``costs.bssa_pair``: rough disparity, splat, refinement, slice),
times the pairs per frame and the frames per second of the traced window,
over the peak.  Moves ``rig_fps``."""


def read(r):
    s = r.system
    if not s["frames"] or s["window_s"] <= 0:
        return None
    rate = s["frames"] / s["window_s"]
    return 100.0 * s["pair_ops"] * s["pairs"] * rate / r.peaks["bf16_flops"]
