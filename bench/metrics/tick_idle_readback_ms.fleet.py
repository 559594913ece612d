"""Device idle time per tick while the host reads a dispatch's results
back, in ms: the idle time inside the ``serve.readback`` spans, where
``np.asarray`` copies the outputs to the host.  The copies are not
device operations, so the chip idles here; a runtime that stands still
in the copy shows here, not in the scheduler's metrics.  Moves
``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.idle_in(pt, ("serve.readback",), r.w0, r.w1)
    return got[0] if got else None
