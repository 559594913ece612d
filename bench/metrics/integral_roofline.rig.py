"""The integral-image kernel's share of its roofline in the SAD cost
volume: one summed-area table of the edge-padded pair per disparity
hypothesis and frame (``costs.integral_image``), over the device time of
the kernel's events in the window.  Moves ``rig_fps``."""

from bench import costs

KERNEL = "integral_image"


def read(r):
    t, n = r.kernel_s(KERNEL)
    f = r.system["frames"]
    if not n or not f:
        return None
    ops, nbytes = (f * x for x in r.system["integral_work"])
    share, bound = costs.roofline(ops, nbytes, t, r.peaks["bf16_flops"],
                                  r.peaks["hbm_bytes_per_s"])
    return share, {"bound": bound}
