"""Device time of the window gather per dispatch, in ms: the operations
under the funnel step's ``gather`` name scope, which compact the windows
the cascade kept into the window capacity and resample each to 20x20.
Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "gather", r.w0, r.w1)
    return got[0] if got else None
