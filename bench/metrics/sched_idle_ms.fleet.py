"""Device idle time per tick while the host schedules, in ms: the idle
time inside the ``serve.gather``, ``serve.settle`` and
``serve.admission`` spans.  Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.idle_in(
        pt, ("serve.gather", "serve.settle", "serve.admission"), r.w0, r.w1)
    return got[0] if got else None
