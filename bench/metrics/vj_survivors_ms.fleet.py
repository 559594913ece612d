"""Device time of the Haar cascade's later stages per dispatch of the
funnel step, in ms: the operations under the ``vj1`` .. ``vjN`` name
scopes, which score only the windows compacted out of the stage before.
Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, r"vj[1-9]\d*", r.w0,
                                               r.w1)
    return got[0] if got else None
