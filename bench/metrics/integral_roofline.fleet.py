"""The integral-image kernel's share of its roofline in the serving step:
the least time the summed-area tables of every dispatch in the window need
(two per dispatch over every frame slot, ``costs.integral_image``) over
the device time of the kernel's events.  Moves ``frame_p95_s``."""

from bench import costs

KERNEL = "integral_image"


def read(r):
    t, n = r.kernel_s(KERNEL)
    d = r.system["dispatches"]
    if not n or not d:
        return None
    ops, nbytes = (d * x for x in r.system["integral_work"])
    share, bound = costs.roofline(ops, nbytes, t, r.peaks["bf16_flops"],
                                  r.peaks["hbm_bytes_per_s"])
    return share, {"bound": bound}
