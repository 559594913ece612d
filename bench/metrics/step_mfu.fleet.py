"""The funnel step's share of the chip's bf16 peak: operations the frames
decided in the window need (``costs.funnel_frame``, from the frame shape
and each result's motion flag and window count) over the device's busy
time in the window times the peak.  Moves ``frame_p95_s``."""


def read(r):
    busy = r.busy_s()
    if busy <= 0 or not r.system["step_ops"]:
        return None
    return 100.0 * r.system["step_ops"] / (busy * r.peaks["bf16_flops"])
