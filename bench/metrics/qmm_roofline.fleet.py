"""The int8 matmul kernel's share of its roofline in the network tail:
both layers of the 400-8-1 network over every window slot of every
dispatch in the window (``costs.quant_matmul``) against the int8 peak,
over the device time of the kernel's events.  Moves ``frame_p95_s``."""

from bench import costs

KERNEL = "quant_matmul"


def read(r):
    t, n = r.kernel_s(KERNEL)
    d = r.system["dispatches"]
    if not n or not d:
        return None
    ops, nbytes = (d * x for x in r.system["qmm_work"])
    share, bound = costs.roofline(ops, nbytes, t, r.peaks["int8_ops"],
                                  r.peaks["hbm_bytes_per_s"])
    return share, {"bound": bound}
