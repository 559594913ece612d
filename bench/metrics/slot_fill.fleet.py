"""Share of the frame slots dispatched to the funnel step that held a real
frame: served frames over dispatches x capacity x chunk, from the
telemetry's dispatch events and the ticks' completions.  The rest is
capacity padding.  Moves ``frame_p95_s``."""


def read(r):
    s = r.system
    if not s["slots"]:
        return None
    return 100.0 * s["served_frames"] / s["slots"]
