"""Device time of the refinement per depth step, in ms: the
bilateral-blur kernel's iterations over the value and weight grids,
the loop's own time included.
The operations under the ``refine`` name scope (``camera.bssa.bssa_depth``)
that begin inside a ``dispatch`` span of ``VRRigExecutor.depth_maps``.
Moves ``frames_per_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "refine", r.w0, r.w1)
    return got[0] if got else None
