"""The bilateral-blur kernel's share of its roofline in the refinement:
one [1,2,1] pass over the value and weight grids per iteration and frame
(``costs.bilateral_blur``), over the device time of the kernel's events in
the window.  Moves ``rig_fps``."""

from bench import costs

KERNEL = "bilateral_blur"


def read(r):
    t, n = r.kernel_s(KERNEL)
    f = r.system["frames"]
    if not n or not f:
        return None
    ops, nbytes = (f * x for x in r.system["blur_work"])
    share, bound = costs.roofline(ops, nbytes, t, r.peaks["bf16_flops"],
                                  r.peaks["hbm_bytes_per_s"])
    return share, {"bound": bound}
