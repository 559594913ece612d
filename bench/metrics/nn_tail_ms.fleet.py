"""Device time of the int8 network per dispatch, in ms: the operations
under the funnel step's ``nn`` name scope (both layers on the quantised
matmul kernel, the sigmoid table, the decision).  Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "nn", r.w0, r.w1)
    return got[0] if got else None
