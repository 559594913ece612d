"""Device time of the rough disparity per depth step, in ms: the
operations under the ``rough`` name scope (``camera.bssa.bssa_depth``: the
chunked SAD cost volume and its running min, the loop's own time included)
that begin inside a ``dispatch`` span of ``VRRigExecutor.depth_maps``.
Extras: the dispatches counted, and the share of the device's busy time in
the window that falls on operations under one of the depth step's four
scopes, with the largest operation under none.  Moves ``frames_per_s``."""

from bench import progtrace

SCOPES = "rough|splat|refine|slice"


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "rough", r.w0, r.w1)
    if not got:
        return None
    share, rest = progtrace.scoped_share(pt, SCOPES, r.w0, r.w1)
    return got[0], {"dispatches": got[1], "scoped_busy": 100.0 * share,
                    "largest_unscoped": rest[0][0] if rest else None}
