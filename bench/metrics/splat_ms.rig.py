"""Device time of the bilateral grid's construction per depth step, in ms:
each pixel's disparity and unit weight added at its nearest vertex.
The operations under the ``splat`` name scope (``camera.bssa.bssa_depth``)
that begin inside a ``dispatch`` span of ``VRRigExecutor.depth_maps``.
Moves ``frames_per_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "splat", r.w0, r.w1)
    return got[0] if got else None
