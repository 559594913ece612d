"""Device idle time per tick while the host builds and sends a dispatch,
in ms: the idle time inside the ``serve.stage`` (the host stack and its
copy to the device) and ``serve.cascade`` (``cascade_serve``'s eager
scorer, compaction and scatter around the step) spans.  With
``tick_idle_readback_ms.fleet`` and ``sched_idle_ms.fleet`` it splits the
idle time inside the tick.  Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.idle_in(pt, ("serve.stage", "serve.cascade"),
                                   r.w0, r.w1)
    return got[0] if got else None
