"""Device time of the slice per depth step, in ms: the trilinear reads
of the refined grids at every pixel.
The operations under the ``slice`` name scope (``camera.bssa.bssa_depth``)
that begin inside a ``dispatch`` span of ``VRRigExecutor.depth_maps``.
Moves ``frames_per_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "slice", r.w0, r.w1)
    return got[0] if got else None
